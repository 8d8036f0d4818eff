"""Run the system's main path once on a TPU and check what comes out.

    python chip_smoke.py                # one chip: four kernels + olmo-1b serving
    python chip_smoke.py --four-chips   # four chips: sharded olmo-1b training only

One chip, two phases, in one process:

* kernels -- each Pallas kernel at its registry shape, called through its
  jitted ``ops`` entry point with the configuration left to the estimator,
  compared with the kernel's own ``ref.py``;
* serving -- olmo-1b at published widths and depth with random weights through
  ``ServeEngine`` (the ``launch/serve.py`` path), compared position by position
  with ``model.forward``.

``--four-chips`` runs only the trainer on a 2x2 ``data x model`` mesh with
olmo-1b cut to 4 layers, against the same steps on a 1x1 mesh.

Every phase raises on failure.  The last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.  With
no TPU the script fails before any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

SEED = 0
# max |kernel - ref| allowed per kernel: f32 stencil and LBM are exact up to
# summation order; attention is bf16 out; wkv is relative to max |ref|
# because its state sums thousands of decayed terms
KERNEL_TOL = {"stencil25": 1e-5, "lbm_d3q15": 1e-4, "attention": 2e-2, "wkv": 1e-3}
# olmo-1b serving computes in bf16, so the cached path and forward round
# differently: |logit difference| (logits have std ~1) must stay within these
# in max and in mean.  A wrong position or cache entry moves the mean to ~1.
SERVE_TOL = {"max": 1.0, "mean": 0.05}
TRAIN_LOSS_TOL = 2e-2  # |loss(2x2) - loss(1x1)| per step, losses ~ ln(vocab)


def log(msg: str) -> None:
    print(msg, flush=True)


def _max_err(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))


def _check(name: str, err: float, tol: float, what: str = "max error") -> None:
    log(f"  {name}: {what} {err:.3e} (tolerance {tol:.3g})")
    if not err <= tol:
        raise AssertionError(f"{name}: {what} {err:.3e} exceeds {tol:.3g}")


def _timed(fn, *args, **kw):
    """Call ``fn`` twice -> (output, "first call .. s, warm .. s"): the first
    call traces, selects and compiles; the second is a warm wall time."""
    import jax

    secs = []
    for _ in range(2):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args, **kw))
        secs.append(time.perf_counter() - t0)
    return out, f"first call {secs[0]:.3f} s, warm {secs[1]:.4f} s wall"


def kernel_phase(machine) -> None:
    import jax
    import jax.numpy as jnp

    from repro.kernels.attention import flash_attention, mha_ref, select_blocks
    from repro.kernels.lbm_d3q15 import init_fields, lbm_step, lbm_step_ref
    from repro.kernels.lbm_d3q15 import select_block as lbm_select
    from repro.kernels.stencil25 import select_block as stencil_select
    from repro.kernels.stencil25 import stencil25, stencil25_ref
    from repro.kernels.wkv import select_chunk, wkv, wkv_ref

    keys = jax.random.split(jax.random.PRNGKey(SEED), 8)
    # references run at full f32 matmul precision: the TPU default rounds
    # f32 matmul inputs to bf16
    ref_precision = jax.default_matmul_precision("float32")

    # stencil25: 256x256x512, r=4, f32; interior [r:-r]^3 is stencil-defined
    r = 4
    src = jax.random.normal(keys[0], (256, 256, 512), jnp.float32)
    pick, est = stencil_select(src.shape, r, src.dtype, machine=machine)
    out, timing = _timed(stencil25, src, r=r)
    with ref_precision:
        ref = jax.jit(stencil25_ref, static_argnums=1)(src, r)
    log(f"stencil25 256x256x512 r=4 f32: pick block {pick} "
        f"(predicted {est.time:.3e} s, {est.limiter}), {timing}")
    interior = (slice(r, -r),) * 3
    _check("stencil25", _max_err(out[interior], ref[interior]), KERNEL_TOL["stencil25"])

    # LBM D3Q15: 128^3 f32; the 1-cell z/y boundary shell is not defined
    f, phase, vel = init_fields((128, 128, 128), seed=SEED)
    pick, est = lbm_select(phase.shape, phase.dtype, machine=machine)
    (fo, po), timing = _timed(lbm_step, f, phase, vel)
    fr, pr = jax.jit(lbm_step_ref)(f, phase, vel)
    log(f"lbm_d3q15 128^3 f32: pick block {pick} "
        f"(predicted {est.time:.3e} s, {est.limiter}), {timing}")
    err = max(
        _max_err(fo[:, 1:-1, 1:-1], fr[:, 1:-1, 1:-1]),
        _max_err(po[1:-1, 1:-1], pr[1:-1, 1:-1]),
    )
    _check("lbm_d3q15", err, KERNEL_TOL["lbm_d3q15"])

    # attention: GQA b4 hq32 hkv8 s8192 d128 bf16, causal.  The full
    # reference's score tensor (~34 GB) does not fit, so compare (batch, head)
    # pairs that span all four batches and four different kv groups.
    b, hq, hkv, s, d = 4, 32, 8, 8192, 128
    q = jax.random.normal(keys[1], (b, hq, s, d), jnp.bfloat16)
    k = jax.random.normal(keys[2], (b, hkv, s, d), jnp.bfloat16)
    v = jax.random.normal(keys[3], (b, hkv, s, d), jnp.bfloat16)
    pick, est = select_blocks(b, hq, hkv, s, d, q.dtype, True, machine=machine)
    out, timing = _timed(flash_attention, q, k, v, causal=True)
    log(f"attention b4 hq32 hkv8 s8192 d128 bf16: pick (block_q, block_kv) {pick} "
        f"(predicted {est.time:.3e} s, {est.limiter}), {timing}")
    group = hq // hkv
    ref_one = jax.jit(lambda q1, k1, v1: mha_ref(q1, k1, v1, causal=True))
    err = 0.0
    for bi, h in ((0, 0), (1, 5), (2, 18), (3, 31)):
        g = h // group
        with ref_precision:
            ref = ref_one(q[bi:bi + 1, h:h + 1], k[bi:bi + 1, g:g + 1], v[bi:bi + 1, g:g + 1])
        err = max(err, _max_err(out[bi:bi + 1, h:h + 1], ref))
    _check("attention (batch, head) (0,0) (1,5) (2,18) (3,31)", err, KERNEL_TOL["attention"])

    # wkv: BH64 S4096 K64 f32, log-decays in [-e^4, -e^-8] as in the tests
    BH, S, K = 64, 4096, 64
    rr, kk, vv = (jax.random.normal(keys[4 + i], (BH, S, K), jnp.float32) for i in range(3))
    wlog = -jnp.exp(jnp.clip(jax.random.normal(keys[7], (BH, S, K), jnp.float32), -8, 4))
    u = jax.random.normal(jax.random.fold_in(keys[7], 1), (K,), jnp.float32)
    pick, est = select_chunk(BH, S, K, machine=machine)
    out, timing = _timed(wkv, rr, kk, vv, wlog, u)
    with ref_precision:
        ref, _ = jax.jit(wkv_ref)(rr, kk, vv, wlog, u)
    log(f"wkv BH64 S4096 K64 f32: pick chunk {pick} "
        f"(predicted {est.time:.3e} s, {est.limiter}), {timing}")
    scale = max(1.0, float(jnp.max(jnp.abs(ref))))
    _check(f"wkv (relative to max |ref| = {scale:.1f})", _max_err(out, ref) / scale, KERNEL_TOL["wkv"])


def serve_phase() -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.models.params import init_params
    from repro.models.registry import build_model
    from repro.serve.engine import ServeEngine

    requests, prompt_len, new_tokens = 4, 64, 16
    cfg = get_arch("olmo-1b")
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(init_params(model.blueprint(), jax.random.PRNGKey(SEED)))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    log(f"olmo-1b: {cfg.n_layers} layers d{cfg.d_model} vocab {cfg.vocab}, "
        f"{n_params / 1e9:.3f} B f32 params, init {time.perf_counter() - t0:.2f} s")
    engine = ServeEngine(model, params, max_len=prompt_len + new_tokens + 8)
    prompts = (
        np.random.default_rng(SEED)
        .integers(0, cfg.vocab, size=(requests, prompt_len))
        .astype(np.int32)
    )
    t0 = time.perf_counter()
    tokens, logits = engine.generate(prompts, n_steps=new_tokens, return_logits=True)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = engine.generate(prompts, n_steps=new_tokens)
    warm = time.perf_counter() - t0
    log(f"  serve {requests} requests x {prompt_len} prompt + {new_tokens} new tokens: "
        f"warm {warm:.3f} s wall; first call {cold:.3f} s, so compile ~{cold - warm:.3f} s")
    if not np.array_equal(tokens, again):
        raise AssertionError("greedy generation is not deterministic across two runs")

    seq = np.concatenate([prompts, tokens[:, :-1]], axis=1)
    fwd = np.asarray(jax.jit(lambda p, t: model.forward(p, t)[0])(params, jnp.asarray(seq)))
    if fwd.shape != logits.shape or not np.isfinite(logits).all():
        raise AssertionError(f"engine logits {logits.shape} vs forward {fwd.shape}, or not finite")
    log(f"  logits max |forward| {np.max(np.abs(fwd)):.3f}")
    diff = np.abs(logits - fwd)
    for part, cols in (("prefill", slice(None, prompt_len)), ("decode", slice(prompt_len, None))):
        for stat in ("max", "mean"):
            err = float(getattr(np, stat)(diff[:, cols]))
            _check(f"{part} logits vs forward", err, SERVE_TOL[stat], f"{stat} |difference|")
    greedy = np.argmax(logits[:, prompt_len - 1:], axis=-1)
    if not np.array_equal(greedy, tokens):
        raise AssertionError("generated tokens are not the argmax of the engine's logits")


def four_chip_phase() -> None:
    import jax

    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import SyntheticTokenDataset
    from repro.launch.mesh import make_test_mesh
    from repro.models.registry import build_model
    from repro.optim.optimizers import make_optimizer
    from repro.train.trainer import Trainer, TrainerConfig

    if len(jax.devices()) < 4:
        raise RuntimeError(f"--four-chips needs 4 TPU chips, JAX sees {len(jax.devices())}")
    full = get_arch("olmo-1b")
    cfg = dataclasses.replace(full, n_layers=4)
    shape = ShapeConfig("chip_smoke", seq_len=128, global_batch=8, kind="train")
    n_steps = 3
    log(f"train olmo-1b at published widths, depth cut {full.n_layers} -> {cfg.n_layers} "
        f"layers; batch {shape.global_batch} x seq {shape.seq_len}, {n_steps} adamw steps")
    ckpt_root = ROOT / "results" / "chip_smoke_ckpt"

    def run(data: int, model: int):
        mesh = make_test_mesh(data, model)
        ckpt = ckpt_root / f"{data}x{model}"
        shutil.rmtree(ckpt, ignore_errors=True)
        trainer = Trainer(
            build_model(cfg), make_optimizer("adamw"), mesh, shape,
            TrainerConfig(ckpt_dir=str(ckpt), ckpt_every=n_steps),
        )
        ds = SyntheticTokenDataset(cfg.vocab, shape.seq_len, shape.global_batch, seed=SEED)
        t0 = time.perf_counter()
        state = trainer.fit(jax.random.PRNGKey(SEED), ds, n_steps=n_steps, resume=False)
        secs = time.perf_counter() - t0
        steps = [e for e in trainer.log if e["event"] == "step"]
        used = [dev.memory_stats()["bytes_in_use"] for dev in mesh.devices.flat]
        log(f"  mesh {data}x{model}: losses {[round(e['loss'], 5) for e in steps]}, "
            f"step seconds {[round(e['dt'], 3) for e in steps]}, fit {secs:.1f} s, "
            f"restarts {trainer.restarts}, bytes in use per device {used}")
        del state
        shutil.rmtree(ckpt, ignore_errors=True)
        if trainer.restarts:
            raise AssertionError(f"mesh {data}x{model}: trainer restarted {trainer.restarts} times")
        return [e["loss"] for e in steps], used

    sharded, used4 = run(2, 2)
    single, used1 = run(1, 1)
    err = max(abs(a - b) for a, b in zip(sharded, single))
    _check("2x2 vs 1x1 loss per step", err, TRAIN_LOSS_TOL)
    # the sharded state must be spread, not piled on device 0: every chip holds
    # well under the 1x1 run's total, and no chip holds twice another's share
    if max(used4) > 0.5 * used1[0] or max(used4) > 2 * min(used4):
        raise AssertionError(f"state not spread over four chips: {used4} vs 1x1 {used1}")
    log(f"  state spread: largest chip {max(used4) / used1[0]:.3f} of the 1x1 total")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only sharded training on a 2x2 mesh against 1x1")
    args = ap.parse_args()

    import jax

    from repro.core.machine import device_machine

    cache = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU -- JAX's device is {dev.platform!r}; "
              "this script runs only on a TPU chip", file=sys.stderr)
        return 1
    machine = device_machine()
    log(f"device {dev.device_kind} x{len(jax.devices())} -> machine {machine.name}; "
        f"compile cache {cache}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase()
    else:
        kernel_phase(machine)
        serve_phase()
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
