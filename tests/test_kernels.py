"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs pure-jnp oracles."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tpu_estimator as te
from repro.core.machine import TPU_V5E
from repro.kernels.attention import flash_attention, mha_ref, select_blocks
from repro.kernels.lbm_d3q15 import config_space as lbm_space
from repro.kernels.lbm_d3q15 import init_fields, lbm_step, lbm_step_ref
from repro.kernels.lbm_d3q15 import select_block as lbm_select
from repro.kernels.lbm_d3q27 import (
    TwoPhaseParams,
    equilibrium,
    hydro_step_ref,
    twophase_step,
    twophase_step_ref,
)
from repro.kernels.lbm_d3q27 import config_space as lbm27_space
from repro.kernels.lbm_d3q27 import select_block as lbm27_select
from repro.kernels.lbm_d3q27.kernel import hydro_step_pallas
from repro.kernels.lbm_d3q27.ref import DIRS as DIRS27
from repro.kernels.stencil25 import config_space, select_block, stencil25, stencil25_ref
from repro.kernels.stencil25.kernel import INPUTS as STENCIL_INPUTS
from repro.kernels.stencil25.kernel import strip_heights
from repro.kernels.tiling import Block, pallas_specs

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


# (kernel, shape, block), with each kernel's count of inputs: stencil25's
# centre and four strips; D3Q15's centre of f, ten z strips, ten y strips and
# eight corner pieces of its components, phase's centre and four strips, and
# vel's centre; D3Q27's nine class centres, six z strips, six y strips, four
# corner pieces, phase's centre, four strips and four corners, and vel's centre
BLOCK_DECLARATIONS = {"stencil25": 5, "lbm_d3q15": 1 + 10 + 10 + 8 + 1 + 4 + 1,
                      "lbm_d3q27": 9 + 6 + 6 + 4 + 1 + 4 + 4 + 1}


@pytest.mark.parametrize(
    "kernel, shape, block",
    [
        ("stencil25", (1024, 1024, 512), (32, 32)),
        ("lbm_d3q15", (256, 256, 256), (8, 8)),
        ("lbm_d3q15", (256, 256, 256), (16, 16)),
        ("lbm_d3q15", (32, 32, 256), (8, 8)),
        ("lbm_d3q27", (256, 256, 256), (16, 16)),
        ("lbm_d3q15", (256, 256, 256), (8, 32)),
    ],
)
def test_config_space_matches_kernel_block_specs(monkeypatch, kernel, shape, block):
    """The estimator's candidate describes the BlockSpecs the kernel itself
    builds: the same blocks by name, shape and interior index map, inputs
    then outputs, with no (z, y) corner in stencil25's and every LBM block
    over whole x rows (no ghost-padded copy)."""
    import repro.kernels.lbm_d3q15.kernel as lbm_kernel
    import repro.kernels.lbm_d3q27.kernel as lbm27_kernel
    import repro.kernels.stencil25.kernel as stencil_kernel

    nz, ny, nx = shape
    bits, r = 32, 4
    field = lambda *lead: jax.ShapeDtypeStruct((*lead, *shape), jnp.float32)  # noqa: E731
    if kernel == "stencil25":
        module, space = stencil_kernel, config_space(shape, r, bits)
        run, args = functools.partial(stencil_kernel.stencil25_pallas, r=r), (field(),)
    elif kernel == "lbm_d3q15":
        module, space = lbm_kernel, lbm_space(shape, bits)
        run, args = lbm_kernel.lbm_step_pallas, (field(15), field(), field(3))
    else:
        module, space = lbm27_kernel, lbm27_space(shape, bits)
        run, args = lbm27_kernel.hydro_step_pallas, (field(27), field(), field(3))
    built = []
    monkeypatch.setattr(module, "pallas_specs",
                        lambda blocks, extents: built.append((blocks, extents))
                        or pallas_specs(blocks, extents))
    jax.eval_shape(functools.partial(run, block=block), *args)
    ((blocks, extents),) = built
    in_specs, out_specs = pallas_specs(blocks, extents)
    n_inputs = BLOCK_DECLARATIONS[kernel]
    assert len(in_specs) == n_inputs
    cfg = next(c for c in space if c.meta["block"] == block)
    assert len(cfg.accesses) == n_inputs + len(out_specs) == len(blocks)
    assert [a.is_output for a in cfg.accesses] == [False] * n_inputs + [True] * len(out_specs)
    assert [a.name for a in cfg.accesses] == [b.name for b in blocks]
    if kernel == "stencil25":
        assert [a.name for a in cfg.accesses[:5]] == list(STENCIL_INPUTS)
        assert extents == {"padded": (nz, ny, nx + 2 * r)}
    nzb, nyb = nz // block[0], ny // block[1]
    for acc, spec in zip(cfg.accesses, [*in_specs, *out_specs]):
        assert tuple(acc.block_shape) == tuple(spec.block_shape), acc.name
        if kernel != "stencil25":
            assert acc.block_shape[-1] == nx, acc.name
        for i, j in ((1, 1), (1, nyb - 2), (nzb - 2, nyb - 2)):  # interior: no clamp applies
            assert tuple(int(v) for v in spec.index_map(i, j)) == acc.index_map(i, j), acc.name


def test_pallas_specs_clamp_inputs_to_the_grid_and_leave_outputs():
    """An input whose interior map reads the blocks i - 1 and i + 1 is clamped
    to block 0 and to block n/s - 1 at the two ends of the grid; an output
    block keeps its map as declared."""
    n, s = 64, 8
    blocks = (
        Block("lo", (s, 128), lambda i, j: (i - 1, 0), "a"),
        Block("hi", (s, 128), lambda i, j: (i + 1, 0), "a"),
        Block("out", (s, 128), lambda i, j: (i - 1, 0), is_output=True),
    )
    (lo, hi), (out,) = pallas_specs(blocks, {"a": (n, 128)})
    last = n // s - 1
    assert [int(lo.index_map(i, 0)[0]) for i in (0, 1, last)] == [0, 0, last - 1]
    assert [int(hi.index_map(i, 0)[0]) for i in (0, last - 1, last)] == [1, last, last]
    assert [int(out.index_map(i, 0)[0]) for i in (0, last)] == [-1, last - 1]


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


def test_lbm_d3q15_estimator_counts_strip_bytes():
    """``hbm_bytes`` of the (8, 32) candidate at 256^3 is the hand count:
    every block index moves with j, so each of the 35 inputs is fetched and
    both outputs written at all 32 x 8 grid steps.  One z plane and 8 y rows
    make the strips (``strip_heights``); a leading extent of 1 picks an f
    component, whose (by, nx) or (8, nx) rows need no tile padding."""
    cands = lbm_space((256, 256, 256), 32)
    cfg = next(c for c in cands if c.meta["block"] == (8, 32))
    f32, bz, by, nx = 4, 8, 32, 256
    per_step = (
        15 * bz * by * nx * f32  # f centre, 3,932,160
        + 10 * 1 * by * nx * f32  # f z strips, (1, 1, 32, 256), 327,680
        + 10 * bz * 8 * nx * f32  # f y strips, (1, 8, 8, 256), 655,360
        + 8 * 1 * 8 * nx * f32  # f corners, (1, 1, 8, 256), 65,536
        + bz * by * nx * f32  # phase centre, 262,144
        + 2 * 1 * by * nx * f32  # phase z strips, 65,536
        + 2 * bz * 8 * nx * f32  # phase y strips, 131,072
        + 3 * bz * by * nx * f32  # vel centre, 786,432
        + 15 * bz * by * nx * f32  # f_out, 3,932,160
        + bz * by * nx * f32  # phase_out, 262,144
    )
    assert per_step == 10_420_224
    est = te.estimate(cfg, TPU_V5E)
    assert est.hbm_bytes == 32 * 8 * per_step
    ranked = [c.meta["block"] for c, _ in te.rank_configs(cands, TPU_V5E)]
    assert ranked[0] == (8, 32)


@pytest.mark.parametrize("block_mib, feasible", [(90, True), (100, True), (102, False)])
def test_vmem_gate_holds_the_blocks_to_vmem_usable(block_mib, feasible):
    """The gate is the double-buffered blocks against ``vmem_usable``, the
    limit every kernel passes the compiler: 100 MiB on v5e."""
    rows = block_mib * 256 // 2  # f32 rows of 1024 lanes, 4 KiB each, double-buffered
    cfg = te.PallasConfig(
        name="gate",
        grid=(4,),
        accesses=(te.BlockAccess("x", (rows, 1024), lambda i: (i, 0), 32),),
        is_matmul=False,
    )
    est = te.estimate(cfg, TPU_V5E)
    assert est.vmem_bytes == block_mib * 2**20
    assert est.feasible == feasible


@pytest.mark.parametrize(
    "kernel, shape, pick",
    [
        ("stencil25", (1024, 1024, 512), (32, 32)),  # stencil25.bulk
        ("stencil25", (32, 32, 512), (32, 32)),  # stencil25.ensemble
        ("lbm_d3q15", (256, 256, 256), (8, 32)),  # lbm_d3q15.bulk
        ("lbm_d3q15", (32, 32, 256), (8, 32)),  # lbm_d3q15.ensemble
        ("lbm_d3q27", (256, 256, 256), (8, 32)),  # lbm_twophase.bulk
    ],
)
def test_estimator_picks_of_the_benchmark_cells_stand(kernel, shape, pick):
    """The picks of the chip benchmark's accepted cells stand.  The D3Q15
    kernel's strips make its wider blocks cheaper in bytes, so it picks
    (8, 32), as the D3Q27 kernel does."""
    if kernel == "stencil25":
        got, _ = select_block(shape, 4, jnp.float32, machine=TPU_V5E)
    elif kernel == "lbm_d3q15":
        got, _ = lbm_select(shape, jnp.float32, machine=TPU_V5E)
    else:
        got, _ = lbm27_select(shape, jnp.float32, machine=TPU_V5E)
    assert got == pick


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


LBM_SHAPES = [(16, 16, 32), (16, 32, 64), (24, 96, 128), (48, 48, 128)]
LBM_BLOCKS = [(8, 8), (4, 16), (8, 32), (16, 16)]


# the first two shapes at the first two blocks; then tiles with interior
# neighbours in z and in y whose y strips (8 rows) are thinner than the tile
@pytest.mark.parametrize(
    "shape, dtype, block",
    [
        pytest.param(LBM_SHAPES[s], jnp.float32, LBM_BLOCKS[b], id=f"block{b}-float32-shape{s}")
        for b, s in ((0, 0), (0, 1), (1, 0), (1, 1), (2, 2), (3, 3))
    ],
)
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


LBM27_SHAPES = [(16, 16, 128), (32, 32, 128)]
LBM27_BLOCKS = [(8, 8), (16, 8), (16, 16)]
LBM27_SHELL = (Ellipsis, slice(2, -2), slice(2, -2), slice(None))  # the kernels clamp their z/y halo


def _lbm27_close(got, want):
    """max |got - want| <= 1e-5 max |want|.  The kernel sums the 27-point
    phase derivatives one axis at a time where the oracle sums term by term,
    and multiplies by 1/rho where the oracle divides: f32 reassociation, a
    few 1e-7 of the largest value (rho falls to rho_light, so forces over rho
    are the field's largest terms).  A wrong neighbour, sign or weight moves
    values by 1e-3 or more of it."""
    got, want = np.asarray(got[LBM27_SHELL]), np.asarray(want[LBM27_SHELL])
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()


def _lbm27_fields(shape, seed):
    rng = np.random.default_rng(seed)
    phase = jnp.asarray(rng.uniform(0.0, 1.0, shape), jnp.float32)
    vel = jnp.asarray(0.02 * rng.normal(size=(3, *shape)), jnp.float32)
    g = equilibrium(vel, 0.01) + jnp.asarray(1e-3 * rng.normal(size=(27, *shape)), jnp.float32)
    return g, phase, vel


@pytest.mark.parametrize("block", LBM27_BLOCKS)
@pytest.mark.parametrize("shape", LBM27_SHAPES)
def test_lbm27_kernel_allclose(shape, block):
    """The D3Q27 kernel against the oracle on random fields: phase uniform in
    [0, 1], so every neighbour of the 27-point derivatives differs."""
    g, phase, vel = _lbm27_fields(shape, 27)
    params = TwoPhaseParams()
    go, uo = hydro_step_pallas(g, phase, vel, params, block=block, interpret=True)
    gr, ur = jax.jit(hydro_step_ref, static_argnums=3)(g, phase, vel, params)
    _lbm27_close(go, gr)
    _lbm27_close(uo, ur)


# both kernels at each block of LBM27_BLOCKS; then both at their 256^3 picks,
# (8, 32), on a domain with interior tiles in z and y
@pytest.mark.parametrize(
    "shape, block, phase_block",
    [
        *(pytest.param(shape, block, block, id=f"shape{s}-block{b}")
          for s, shape in enumerate(LBM27_SHAPES) for b, block in enumerate(LBM27_BLOCKS)),
        pytest.param((24, 96, 128), (8, 32), (8, 32), id="shape24x96x128-block8x32-phase8x32"),
    ],
)
def test_twophase_step_allclose(shape, block, phase_block):
    """The coupled entry (D3Q15 then D3Q27, one jit) against the coupled
    oracle from the droplet, outside the two-cell shell: the D3Q15 step
    leaves one cell undefined and the D3Q27 kernel reads phi' one further."""
    f, phase, vel = init_fields(shape, seed=5)
    g = equilibrium(vel)
    params = TwoPhaseParams()
    out = twophase_step(f, g, phase, vel, params=params, block=block, phase_block=phase_block,
                        interpret=True)
    for got, want in zip(out, jax.jit(twophase_step_ref, static_argnums=4)(f, g, phase, vel, params)):
        _lbm27_close(got, want)


def test_twophase_interpret_needs_both_blocks():
    """Interpret mode runs on no chip: neither kernel's block can be picked."""
    f, phase, vel = init_fields((16, 16, 128))
    with pytest.raises(ValueError, match="phase_block"):
        twophase_step(f, equilibrium(vel), phase, vel, block=(8, 8), interpret=True)


def test_sharpening_width_gives_the_sources_collision():
    """The D3Q15 step adds its sharpening term in full; at
    ``sharpening_width`` it adds the sources' 1 - 1/(2 tau_phi) of the term
    at the interface width xi, the step being affine in the term."""
    params = TwoPhaseParams()
    f, phase, vel = init_fields((16, 16, 32), seed=2)
    unforced, _ = lbm_step_ref(f, phase, vel, tau=params.tau_phase, width=float("inf"))
    full, _ = lbm_step_ref(f, phase, vel, tau=params.tau_phase, width=params.width)
    got, _ = lbm_step_ref(f, phase, vel, tau=params.tau_phase, width=params.sharpening_width)
    want = unforced + (1.0 - 0.5 / params.tau_phase) * (full - unforced)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=1e-7)


def test_twophase_droplet_holds_at_density_ratio_1000():
    """The coupled oracle at the benchmark's parameters (density ratio 1000)
    from a droplet under N(0, 0.01) velocity noise: 300 steps stay finite,
    phi stays in [0, 1] to 1e-4 and the flow slow, as the chip runs it."""
    shape = (24, 24, 24)
    f, phase, vel = init_fields(shape, seed=11)
    params = TwoPhaseParams()
    state = jax.jit(lambda s: jax.lax.fori_loop(
        0, 300, lambda _, s: twophase_step_ref(*s, params=params), s))((f, equilibrium(vel), phase, vel))
    _, g, phase, vel = (np.asarray(a) for a in state)
    assert np.isfinite(g).all() and np.isfinite(vel).all()
    assert -1e-4 <= phase.min() and phase.max() <= 1.0 + 1e-4
    assert np.abs(vel).max() < 0.1


def test_lbm27_fluid_at_rest_stays_at_rest():
    """One phase (phi = 1) at rest under a uniform pressure, g at equilibrium:
    no force, nothing streams in that differs, so ten steps leave it as it was."""
    shape = (8, 8, 16)
    phase = jnp.ones(shape, jnp.float32)
    vel = jnp.zeros((3, *shape), jnp.float32)
    g0 = equilibrium(vel, 0.01)
    g, u = g0, vel
    for _ in range(10):
        g, u = hydro_step_ref(g, phase, u)
    assert float(jnp.abs(u).max()) <= 1e-7
    assert float(jnp.abs(g - g0).max()) <= 1e-7


def test_lbm27_momentum_is_conserved_without_force():
    """Uniform phase (no force) on a periodic domain: streaming moves
    momentum and collision keeps each cell's, so sum_p sum_a c_a g_a stays."""
    shape = (8, 8, 16)
    rng = np.random.default_rng(3)
    phase = jnp.full(shape, 0.7, jnp.float32)
    vel = jnp.asarray(0.02 * rng.normal(size=(3, *shape)), jnp.float32)
    g = equilibrium(vel) + jnp.asarray(1e-3 * rng.normal(size=(27, *shape)), jnp.float32)
    c = jnp.asarray(DIRS27, jnp.float32)  # (27, 3)

    def momentum(g):
        return jnp.einsum("ai,a...->i", c, g, precision="highest")

    before = momentum(g)
    u = vel
    for _ in range(5):
        g, u = hydro_step_ref(g, phase, u)
    assert float(jnp.abs(momentum(g) - before).max()) <= 1e-6


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
