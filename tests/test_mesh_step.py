"""The two-phase step on a domain split in y over a chain of chips
(``twophase_step(..., mesh=...)``), on the four virtual CPU devices that
``conftest.py`` gives, in interpret mode; and the estimator's candidates
for one chip's block, ghosts included."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import tpu_estimator as te
from repro.core.machine import TPU_V5E
from repro.kernels import tiling
from repro.kernels.lbm_d3q15 import config_space as lbm_space
from repro.kernels.lbm_d3q15 import init_fields
from repro.kernels.lbm_d3q15 import select_block as lbm_select
from repro.kernels.lbm_d3q27 import TwoPhaseParams, equilibrium, twophase_step, twophase_step_ref
from repro.kernels.lbm_d3q27 import config_space as lbm27_space
from repro.kernels.lbm_d3q27 import select_block as lbm27_select
from test_kernels import LBM27_SHELL, _lbm27_close

CHIPS = 4
PARAMS = TwoPhaseParams()

# (global shape, D3Q27 block, D3Q15 block): 16 rows of y a chip, two (8, 8)
# tiles, each an edge tile; the D3Q15 kernel at (8, 16), one tile a chip,
# the lo and the hi edge at once; 32 rows a chip, interior y tiles on every chip
MESH_CASES = [
    pytest.param((16, 64, 128), (8, 8), (8, 8), id="16x64x128-8x8"),
    pytest.param((16, 64, 128), (8, 8), (8, 16), id="16x64x128-8x8-phase8x16"),
    pytest.param((16, 128, 128), (8, 8), (8, 8), id="16x128x128-8x8"),
]


def _mesh():
    assert jax.device_count() >= CHIPS, "conftest.py gives the CPU four devices"
    return jax.make_mesh((CHIPS,), (tiling.AXIS,), axis_types=(AxisType.Auto,),
                         devices=jax.devices()[:CHIPS])


def _split(a):
    return P(*(None,) * (a.ndim - 2), tiling.AXIS, None)


def _fields(shape):
    f, phase, vel = init_fields(shape, seed=5)
    return f, equilibrium(vel), phase, vel


def _mesh_step(mesh, args, block, phase_block):
    split = [jax.device_put(a, NamedSharding(mesh, _split(a))) for a in args]
    return twophase_step(*split, params=PARAMS, block=block, phase_block=phase_block,
                         interpret=True, mesh=mesh)


def _on_every_chip(mesh, fn, args):
    """``fn`` of the whole domain, run by every chip of ``mesh`` in one
    program: the one-chip step compiled as the mesh step is, for four devices."""
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=(P(),) * len(args),
                                 out_specs=(P(),) * 4, check_vma=False))(*args)


@pytest.mark.parametrize("shape, block, phase_block", MESH_CASES)
def test_mesh_step_equals_the_one_chip_step(shape, block, phase_block):
    """Outside the domain's two-cell shell the mesh step's outputs, split in
    y, are the one-chip step's on the whole domain, bit for bit, where both
    are compiled for the four devices: the CPU compiler rounds the D3Q15
    collision of a four-device program and of a one-device program apart by
    up to 5e-10 (the one-chip step in a ``shard_map`` on each chip's block,
    no exchange, against the same step alone), so the one-chip step runs on
    every chip of the same mesh.  Against the one-device entry and the
    oracle they hold the two-phase tolerance."""
    mesh = _mesh()
    args = _fields(shape)
    got = _mesh_step(mesh, args, block, phase_block)
    whole = _on_every_chip(mesh, lambda *a: twophase_step(
        *a, params=PARAMS, block=block, phase_block=phase_block, interpret=True), args)
    one = twophase_step(*args, params=PARAMS, block=block, phase_block=phase_block,
                        interpret=True)
    ref = jax.jit(twophase_step_ref, static_argnums=4)(*args, PARAMS)
    for g, w, o, r in zip(got, whole, one, ref):
        assert g.sharding.is_equivalent_to(NamedSharding(mesh, _split(g)), g.ndim)
        np.testing.assert_array_equal(np.asarray(g)[LBM27_SHELL], np.asarray(w)[LBM27_SHELL])
        _lbm27_close(np.asarray(g), np.asarray(o))
        _lbm27_close(np.asarray(g), np.asarray(r))


def test_the_seams_are_exercised():
    """Each chip stepping its own block with the one-chip step, with no
    exchange, misses the oracle at the rows next to the seams by more than
    1e-3 of its largest value, in every output: the comparisons above see
    what the exchange brings."""
    shape, block = (16, 64, 128), (8, 8)
    mesh = _mesh()
    args = _fields(shape)
    alone = jax.jit(jax.shard_map(
        lambda *a: twophase_step(*a, params=PARAMS, block=block, phase_block=block,
                                 interpret=True),
        mesh=mesh, in_specs=tuple(map(_split, args)), out_specs=tuple(map(_split, args)),
        check_vma=False))(*args)
    ref = jax.jit(twophase_step_ref, static_argnums=4)(*args, PARAMS)
    rows = shape[1] // CHIPS
    seams = np.concatenate([np.arange(s - 2, s + 2) for s in range(rows, shape[1], rows)])
    for got, want in zip(alone, ref):
        got, want = np.asarray(got)[..., 2:-2, :, :], np.asarray(want)[..., 2:-2, :, :]
        err = np.abs(got - want)[..., seams, :].max()
        assert err > 1e-3 * np.abs(want).max()


def test_exchange_sends_the_rows_the_kernels_read():
    """Per direction the chain sends the D3Q15 kernel's 5 f components with
    cy != 0 on that side and one row of phase, the D3Q27 kernel's 9 g
    components (three classes) and one row of phi': 16 one-row planes."""
    from repro.kernels.lbm_d3q15 import kernel as lbm15
    from repro.kernels.lbm_d3q27 import kernel as lbm27

    rows15 = tiling.ghost_rows(lbm15.input_blocks((8, 32), 256, 32))
    rows27 = tiling.ghost_rows(lbm27.input_blocks((8, 32), 256, 32))
    assert rows15 == {("f", "lo"): (3, 7, 8, 11, 12), ("f", "hi"): (4, 9, 10, 13, 14),
                      ("phase", "lo"): (), ("phase", "hi"): ()}
    assert rows27 == {("g", "lo"): (3, 4, 5, 12, 13, 14, 21, 22, 23),
                      ("g", "hi"): (6, 7, 8, 15, 16, 17, 24, 25, 26),
                      ("phase", "lo"): (), ("phase", "hi"): ()}
    planes = {side: sum(max(1, len(e)) for (_, s), e in (*rows15.items(), *rows27.items())
                        if s == side) for side in tiling.SIDES}
    assert planes == {"lo": 16, "hi": 16}


GLOBAL = (256, 1024, 256)  # lbm_twophase_y4.bulk: 256^3 a chip over four
LOCAL = (256, 256, 256)


@pytest.mark.parametrize("kernel", ["lbm_d3q15", "lbm_d3q27"])
def test_mesh_candidates_count_the_ghost_bytes(kernel):
    """``hbm_bytes`` of the (8, 32) candidate for one chip's 256^3 block of
    256x1024x256 is the one-chip candidate's plus the ghosts': every ghost
    block's index moves with i alone, so it is fetched at the 32 z steps,
    its one row padded to the 8 sublanes of an f32 tile.  The D3Q15 kernel
    reads 10 f y strips (1, 8, 1, 256), 8 f corners (1, 1, 1, 256) and 2
    phase strips (8, 1, 256); the D3Q27 kernel 6 g class strips (3, 8, 1,
    256), 4 g class corners (3, 1, 1, 256), 2 phi' strips and 4 phi'
    corners (1, 1, 256)."""
    mesh = AbstractMesh((CHIPS,), (tiling.AXIS,))
    space = lbm_space if kernel == "lbm_d3q15" else lbm27_space
    (split,) = [c for c in space(GLOBAL, 32, mesh) if c.meta["block"] == (8, 32)]
    (one,) = [c for c in space(LOCAL, 32) if c.meta["block"] == (8, 32)]
    assert split.grid == one.grid == (32, 8)
    row, f32, nx, bz = 8, 4, 256, 8  # a ghost row takes a whole (8, 128) tile's sublanes
    if kernel == "lbm_d3q15":
        per_z_step = (10 * bz + 8 + 2 * bz) * row * nx * f32
        assert per_z_step == 851_968
    else:
        per_z_step = (6 * 3 * bz + 4 * 3 + 2 * bz + 4) * row * nx * f32
        assert per_z_step == 1_441_792
    ghosts = te.estimate(split, TPU_V5E).hbm_bytes - te.estimate(one, TPU_V5E).hbm_bytes
    assert ghosts == 32 * per_z_step


@pytest.mark.parametrize("kernel", ["lbm_d3q15", "lbm_d3q27"])
def test_mesh_picks_equal_the_one_chip_picks(kernel):
    """At 256^3 a chip the ghosts change no pick: (8, 32), as on one chip."""
    mesh = AbstractMesh((CHIPS,), (tiling.AXIS,))
    select = lbm_select if kernel == "lbm_d3q15" else lbm27_select
    split, _ = select(GLOBAL, jnp.float32, machine=TPU_V5E, mesh=mesh)
    one, _ = select(LOCAL, jnp.float32, machine=TPU_V5E)
    assert split == one == (8, 32)


def test_a_domain_must_split_evenly():
    with pytest.raises(ValueError, match="does not split over 4 chips"):
        tiling.local_shape((16, 66, 128), AbstractMesh((CHIPS,), (tiling.AXIS,)))
