"""Per-architecture smoke tests: reduced config, one forward + train step on CPU,
shape checks, no NaNs, decode/forward consistency."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch
from repro.configs.base import MoEConfig
from repro.models import build_model, init_params

RNG = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_forward_and_train_step(arch_id):
    cfg = get_arch(arch_id).smoke()
    model = build_model(cfg)
    params = init_params(model.blueprint(), RNG)
    B, S = 2, 64
    tokens = jax.random.randint(RNG, (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.frontend != "none":
        batch["frontend_embeds"] = jnp.ones(
            (B, cfg.n_frontend_tokens, cfg.frontend_dim), jnp.float32
        )
    logits, aux = model.forward(params, tokens, batch.get("frontend_embeds"))
    assert logits.shape == (B, S, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()
    (loss, metrics), grads = jax.value_and_grad(model.loss, has_aux=True)(
        params, batch
    )
    assert np.isfinite(float(loss))
    gnorm = jnp.sqrt(
        sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree.leaves(grads))
    )
    assert np.isfinite(float(gnorm)) and float(gnorm) < 1e4, float(gnorm)
    # loss near ln(V) at random init (sanity against logits blowups)
    assert abs(float(metrics["ce"]) - np.log(cfg.vocab)) < 1.5


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_decode_matches_forward(arch_id):
    cfg = get_arch(arch_id).smoke()
    if cfg.moe is not None:  # make MoE dropless so routing is order-independent
        cfg = dataclasses.replace(
            cfg, moe=MoEConfig(cfg.moe.n_experts, cfg.moe.top_k, float(cfg.moe.n_experts))
        )
    model = build_model(cfg)
    params = init_params(model.blueprint(), RNG)
    B, S = 2, 8
    tokens = jax.random.randint(RNG, (B, S), 0, cfg.vocab)
    logits_full, _ = model.forward(params, tokens)
    cache = model.init_cache(B, 16)
    lg = None
    for t in range(S):
        lg, cache = model.decode_step(params, cache, tokens[:, t : t + 1])
    np.testing.assert_allclose(
        np.asarray(lg[:, 0]), np.asarray(logits_full[:, -1]), rtol=2e-3, atol=2e-3
    )


@pytest.mark.parametrize("arch_id", ["olmo-1b", "zamba2-7b"])
def test_cached_prefill_matches_forward(arch_id):
    """A multi-token prefill through the cache (the serve engine's path) gives
    every prompt token its own position, then keeps decoding in step."""
    cfg = get_arch(arch_id).smoke()
    model = build_model(cfg)
    params = init_params(model.blueprint(), RNG)
    B, S = 2, 8
    tokens = jax.random.randint(RNG, (B, S + 1), 0, cfg.vocab)
    logits_full, _ = model.forward(params, tokens)
    cache = model.init_cache(B, 16)
    prefill, cache = model.decode_step(params, cache, tokens[:, :S])
    np.testing.assert_allclose(
        np.asarray(prefill), np.asarray(logits_full[:, :S]), rtol=2e-3, atol=2e-3
    )
    step, _ = model.decode_step(params, cache, tokens[:, S:])
    np.testing.assert_allclose(
        np.asarray(step[:, 0]), np.asarray(logits_full[:, S]), rtol=2e-3, atol=2e-3
    )


def test_train_step_decreases_loss():
    """A few steps on the structured synthetic data must reduce loss (learnable
    Markov structure — data/pipeline.py)."""
    from repro.data.pipeline import SyntheticTokenDataset
    from repro.optim.optimizers import make_optimizer

    cfg = get_arch("olmo-1b").smoke()
    model = build_model(cfg)
    params = init_params(model.blueprint(), RNG)
    opt = make_optimizer("adamw")
    state = opt.init(params)
    ds = SyntheticTokenDataset(cfg.vocab, 64, 8, seed=1)

    @jax.jit
    def step(params, state, batch):
        (loss, _), grads = jax.value_and_grad(model.loss, has_aux=True)(params, batch)
        params, state = opt.update(grads, state, params, 3e-3)
        return params, state, loss

    losses = []
    for i in range(8):
        b = ds.batch(i)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        params, state, loss = step(params, state, batch)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.05, losses


def test_moe_capacity_drops_tokens():
    """Capacity factor 0 < cf << 1 must drop tokens (keep mask active)."""
    cfg = get_arch("dbrx-132b").smoke()
    cfg = dataclasses.replace(cfg, moe=MoEConfig(4, 2, 0.25))
    model = build_model(cfg)
    params = init_params(model.blueprint(), RNG)
    tokens = jax.random.randint(RNG, (2, 64), 0, cfg.vocab)
    logits, aux = model.forward(params, tokens)
    assert np.isfinite(np.asarray(logits)).all()
    assert float(aux) > 0.0  # load-balance loss reported


def test_shardctx_axes_size_roundtrip_with_graph_tracer():
    """`axes_size` is the one logical->physical translation shared by
    `constrain()` and the graph tracer; the tracer's local matmul dims must
    equal the divisibility-gated dims it implies, per family."""
    from repro.graph import rules_for_spec, trace_step
    from repro.launch.mesh import mesh_spec
    from repro.models.shardctx import _axes_size, axes_size

    mesh = mesh_spec("data=2,model=2")
    sizes = dict(mesh.axes)
    rules = rules_for_spec(mesh)
    assert _axes_size is axes_size  # back-compat alias for the old spelling
    assert axes_size(rules.tp, sizes) == 2
    assert axes_size(rules.fsdp, sizes) == 2
    assert axes_size(None, sizes) == 1
    assert axes_size(("data", "model"), sizes) == 4
    for arch_id in ("olmo-1b", "rwkv6-1.6b", "zamba2-7b", "dbrx-132b"):
        cfg = get_arch(arch_id).smoke()
        dag = trace_step(cfg, batch=8, seq=64, mesh=mesh, backend="gpu")
        head = next(n for nid, n in dag.nodes.items() if nid.endswith(".head"))
        tp = axes_size(rules.tp, sizes)
        want_v = cfg.vocab // tp if cfg.vocab % tp == 0 else cfg.vocab
        assert head.meta["dims"] == (8 * 64 // 2, want_v, cfg.d_model)
