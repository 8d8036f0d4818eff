"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracles."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tpu_estimator as te
from repro.core.machine import TPU_V5E
from repro.kernels.attention import flash_attention, mha_ref, select_blocks
from repro.kernels.lbm_d3q15 import init_fields, lbm_step, lbm_step_ref
from repro.kernels.stencil25 import config_space, select_block, stencil25, stencil25_ref
from repro.kernels.stencil25.kernel import INPUTS as STENCIL_INPUTS
from repro.kernels.stencil25.kernel import block_specs, strip_heights

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=4e-2, atol=4e-2) if dtype == jnp.bfloat16 else dict(rtol=3e-5, atol=3e-5)


STENCIL_SHAPES = [(16, 16, 32), (32, 16, 48), (24, 32, 16), (32, 32, 16)]
STENCIL_BLOCKS = [(8, 8), (8, 16), (16, 8), (32, 32)]


# every block that tiles each shape; (32, 32, 16) is one block in z and y at
# (32, 32), so every strip clamps onto the centre tile
@pytest.mark.parametrize(
    "shape, dtype, block",
    [
        pytest.param(shape, dtype, block, id=f"block{b}-{jnp.dtype(dtype).name}-shape{s}")
        for b, block in enumerate(STENCIL_BLOCKS)
        for dtype in (jnp.float32, jnp.bfloat16)
        for s, shape in enumerate(STENCIL_SHAPES)
        if not (shape[0] % block[0] or shape[1] % block[1])
    ],
)
def test_stencil25_allclose(shape, dtype, block):
    r = 4
    src = jnp.asarray(RNG.normal(size=shape), dtype)
    out = stencil25(src, r=r, block=block, interpret=True)
    ref = stencil25_ref(src, r=r)
    sl = (slice(r, -r),) * 3
    np.testing.assert_allclose(
        np.asarray(out[sl], np.float32), np.asarray(ref[sl], np.float32), **_tol(dtype)
    )


# r = 3 makes the z strip a divisor of bz taller than r; (16, 8) on a 16-plane
# field is one block in z, so both z strips clamp onto the centre
@pytest.mark.parametrize(
    "r, dtype, block",
    [
        pytest.param(
            r, dtype, block,
            id=str(r) if (dtype, block) == (jnp.float32, (8, 8))
            else f"{r}-{jnp.dtype(dtype).name}-{block[0]}x{block[1]}",
        )
        for dtype in (jnp.float32, jnp.bfloat16)
        for block in ((8, 8), (16, 8))
        for r in (1, 2, 3, 4)
    ],
)
def test_stencil_ranges(r, dtype, block):
    src = jnp.asarray(RNG.normal(size=(16, 16, 24)), dtype)
    out = stencil25(src, r=r, block=block, interpret=True)
    ref = stencil25_ref(src, r=r)
    sl = (slice(r, -r),) * 3
    np.testing.assert_allclose(
        np.asarray(out[sl], np.float32), np.asarray(ref[sl], np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize(
    "r, block, dtype_bits, heights",
    [
        (4, (32, 32), 32, (4, 8)),
        (4, (32, 32), 16, (4, 16)),
        (3, (8, 8), 32, (4, 8)),
        (4, (8, 8), 16, (4, 8)),  # no 16-row divisor of 8: the whole neighbour tile
        (1, (8, 16), 32, (1, 8)),
        (2, (16, 8), 32, (2, 8)),
    ],
)
def test_stencil_strip_heights(r, block, dtype_bits, heights):
    assert strip_heights(r, block, dtype_bits) == heights


def test_stencil_config_space_matches_kernel_block_specs():
    """The estimator's candidate describes the kernel's own BlockSpecs: the
    centre, two z strips, two y strips and ``out``, with no (z, y) corner."""
    shape, r, bits = (1024, 1024, 512), 4, 32
    cfg = next(c for c in config_space(shape, r, bits) if c.meta["block"] == (32, 32))
    in_specs, out_spec = block_specs(shape, r, (32, 32), bits)
    assert len(in_specs) == 5 and len(cfg.accesses) == 6
    assert [a.is_output for a in cfg.accesses] == [False] * 5 + [True]
    assert [a.name for a in cfg.accesses[:5]] == list(STENCIL_INPUTS)
    for acc, spec in zip(cfg.accesses, [*in_specs, out_spec]):
        assert tuple(acc.block_shape) == tuple(spec.block_shape), acc.name
        for i, j in ((1, 1), (5, 17), (30, 30)):  # interior: no clamp applies
            assert tuple(int(v) for v in spec.index_map(i, j)) == acc.index_map(i, j), acc.name


def test_stencil_estimator_counts_strip_bytes():
    """``hbm_bytes`` of the (32, 32) candidate at 1024x1024x512 is the hand
    count: every operand's block index moves with j, so the revisiting rule
    fetches each of the five inputs and writes ``out`` at all 32 x 32 grid
    steps, each block padded to (8, 128) tiles (520 lanes -> 640)."""
    cands = config_space((1024, 1024, 512), 4, 32)
    cfg = next(c for c in cands if c.meta["block"] == (32, 32))
    f32 = 4
    per_step = (
        32 * 32 * 640 * f32  # centre
        + 2 * 4 * 32 * 640 * f32  # z strips, (4, 32, 520)
        + 2 * 32 * 8 * 640 * f32  # y strips, (32, 8, 520)
        + 32 * 32 * 512 * f32  # out
    )
    est = te.estimate(cfg, TPU_V5E)
    assert est.hbm_bytes == 32 * 32 * per_step
    ranked = [c.meta["block"] for c, _ in te.rank_configs(cands, TPU_V5E)]
    assert ranked.index((32, 32)) < ranked.index((8, 8))
    assert ranked[0] == (32, 32)


def test_interpret_mode_needs_an_explicit_block():
    """Interpret mode runs on no chip, so there is no machine to select for."""
    src = jnp.zeros((16, 16, 32), jnp.float32)
    with pytest.raises(ValueError, match="TPUMachine"):
        stencil25(src, r=4, interpret=True)


def test_attention_selection_raises_without_candidates():
    with pytest.raises(ValueError, match="divide sequence length 64"):
        select_blocks(1, 2, 2, 64, 32, machine=TPU_V5E)


def test_stencil_estimator_selection_valid():
    blk, est = select_block((64, 64, 128), r=4, machine=TPU_V5E)
    assert est.feasible
    assert est.vmem_bytes < 100 * 2**20
    src = jnp.asarray(RNG.normal(size=(64, 64, 128)), jnp.float32)
    out = stencil25(src, r=4, block=blk, interpret=True)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("shape", [(16, 16, 32), (16, 32, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("block", [(8, 8), (4, 16)])
def test_lbm_allclose(shape, dtype, block):
    f, phase, vel = init_fields(shape, dtype=dtype)
    fo, po = lbm_step(f, phase, vel, block=block, interpret=True)
    fr, pr = lbm_step_ref(f, phase, vel)
    s = (slice(None), slice(1, -1), slice(1, -1), slice(None))
    np.testing.assert_allclose(fo[s], fr[s], **_tol(dtype))
    np.testing.assert_allclose(po[1:-1, 1:-1], pr[1:-1, 1:-1], **_tol(dtype))


def test_lbm_mass_conservation():
    """Collision conserves phi (sum over q of f_eq == phi); streaming only moves
    mass: interior sum drift must be tiny for zero velocity."""
    f, phase, vel = init_fields((16, 16, 32))
    fr, pr = lbm_step_ref(f, phase, 0.0 * vel)
    assert abs(float(pr.sum()) - float(phase.sum())) / float(phase.sum()) < 1e-3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_allclose(dtype, hq, hkv, causal):
    B, S, D = 2, 256, 64
    q = jnp.asarray(RNG.normal(size=(B, hq, S, D)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, hkv, S, D)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, hkv, S, D)), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_kv=64, interpret=True)
    ref = mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("bq,bkv", [(64, 64), (128, 256), (256, 128)])
def test_flash_attention_block_invariance(bq, bkv):
    """Output must be block-size invariant (online softmax correctness)."""
    B, H, S, D = 1, 2, 256, 32
    q = jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, H, S, D)), jnp.float32)
    a = flash_attention(q, k, v, causal=True, block_q=bq, block_kv=bkv, interpret=True)
    b = mha_ref(q, k, v, causal=True)
    np.testing.assert_allclose(a, b, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("chunk", [16, 32, 64])
@pytest.mark.parametrize("K", [16, 32])
def test_wkv_pallas_allclose(chunk, K):
    from repro.kernels.wkv import wkv, wkv_ref

    BH, S = 3, 128
    r, k, v = (
        jnp.asarray(RNG.normal(size=(BH, S, K)).astype(np.float32)) for _ in range(3)
    )
    wlog = -jnp.exp(
        jnp.asarray(RNG.normal(size=(BH, S, K)).astype(np.float32)).clip(-8, 4)
    )
    u = jnp.asarray(RNG.normal(size=(K,)).astype(np.float32))
    ref, _ = wkv_ref(r, k, v, wlog, u)
    out = wkv(r, k, v, wlog, u, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=5e-4, atol=5e-4)


def test_wkv_estimator_matches_dryrun_finding():
    """The analytic estimator must pick the chunk the dry-run hillclimb found
    empirically (L=64 for the rwkv6 production shape) — the paper's core thesis."""
    from repro.kernels.wkv import select_chunk

    L, est = select_chunk(BH=64, S=4096, K=64, machine=TPU_V5E)
    assert L == 64
    assert est.feasible
