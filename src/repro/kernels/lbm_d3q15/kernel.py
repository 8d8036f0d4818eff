"""Pallas TPU kernel: D3Q15 Allen-Cahn interface-tracking LB step (paper app 2).

TPU adaptation: tiles over (z, y); x is the lane dimension and every block
holds whole x rows, so the periodic x neighbours are lane rotations of the
row (no ghost-padded copy of f, phase or vel).
Pull streaming reads component q at (z - cz, y - cy), so each component
needs only the halo on the side it streams from, as in
:mod:`repro.kernels.lbm_d3q27`: f is one centre tile of all 15 components
plus, per component, a z strip where cz != 0, a y strip where cy != 0 and a
(z, y) corner piece where both are (a leading block extent of 1 picks the
component).  The 7-point phase gradient reads phase's centre and its four
strips; the velocity only its centre tile.  Strip heights come from
:func:`repro.kernels.stencil25.kernel.strip_heights` at range 1: one z
plane, and y rows on the dtype's sublane tile.  The body walks the tile's z
planes.  :func:`input_blocks` and :func:`output_blocks` are the one
description of these blocks, which :mod:`repro.kernels.tiling` turns into
the BlockSpecs and the estimator's candidates.  Block shape selection is
estimator-guided via `ops.select_block` — exactly the paper's
configuration-selection use-case, with VMEM feasibility as the hard
capacity gate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..stencil25.kernel import strip_heights
from ..tiling import SIDE_READ_BY, SIDES, Block, _plane, _yshift, halo_block, pallas_specs
from .ref import DIRS, WEIGHTS

# the (bz, by) blocks the estimator ranks, for this kernel and the D3Q27 one
CANDIDATE_BLOCKS = ((4, 4), (8, 8), (8, 16), (16, 8), (16, 16), (32, 8), (8, 32))


def _lbm_kernel(*refs, names, hy: int, nx: int, tau: float, width: float):
    """One (bz, by, nx) output tile of f' and phi' from the blocks named in
    ``names`` (:func:`input_blocks` order), then the two output refs.  The
    body walks the tile's z planes, so its code and its values are those of
    one (by, nx) plane whatever bz is."""
    ins = dict(zip(names, refs))
    f_out_ref, phase_out_ref = refs[len(names)], refs[len(names) + 1]
    phase = ins["phase"]

    def component(name, q=0):
        """Component q of the f block ``name``, or None where there is none."""
        return ins[name].at[q] if name in ins else None

    def xshift(a, cx: int):
        """a(x - cx) at x, periodic: a lane rotation of the whole row."""
        return pltpu.roll(a, cx % nx, a.ndim - 1) if cx else a

    def plane_step(z, carry):
        # pull streaming: value at p comes from p - c_q
        pulled = []
        for q, (cx, cy, cz) in enumerate(DIRS):
            row = _plane(component("f", q), component(f"f{q}_z"), z, -cz)
            if cy:
                row = _yshift(row, _plane(component(f"f{q}_y"), component(f"f{q}_zy"), z, -cz),
                              cy, hy)
            pulled.append(xshift(row, cx))
        phi_new = pulled[0]
        for q in range(1, 15):
            phi_new = phi_new + pulled[q]
        # 7pt central differences on the input phase
        prow = phase[z]
        gx = 0.5 * (xshift(prow, -1) - xshift(prow, 1))
        gy = 0.5 * (_yshift(prow, ins["phase_yhi"][z], -1, hy)
                    - _yshift(prow, ins["phase_ylo"][z], 1, hy))
        gz = 0.5 * (_plane(phase, ins["phase_zhi"], z, 1) - _plane(phase, ins["phase_zlo"], z, -1))
        inv_norm = jax.lax.rsqrt(gx * gx + gy * gy + gz * gz + 1e-12)
        nxv, nyv, nzv = gx * inv_norm, gy * inv_norm, gz * inv_norm
        sharp = (4.0 * phi_new * (1.0 - phi_new)) / width
        vel = ins["vel"][:, z]
        ux, uy, uz = vel[0], vel[1], vel[2]
        inv_tau = 1.0 / tau
        for q, (cx, cy, cz) in enumerate(DIRS):
            w = WEIGHTS[q]
            cu = 3.0 * (cx * ux + cy * uy + cz * uz)
            heq = w * phi_new * (1.0 + cu)
            forcing = w * sharp * (cx * nxv + cy * nyv + cz * nzv)
            f_out_ref[q, z] = pulled[q] - inv_tau * (pulled[q] - heq) + forcing
        phase_out_ref[z] = phi_new
        return carry

    jax.lax.fori_loop(0, phase.shape[0], plane_step, 0)


def input_blocks(block: tuple[int, int], nx: int, dtype_bits: int):
    """The kernel's inputs (:class:`~repro.kernels.tiling.Block`), in the
    kernel's order: f's centre tile of all 15 components, then for each
    component (``ref.DIRS`` order) the z strip, y strip and corner piece it
    streams from; phase's centre and its four strips; vel's centre.
    ``operand`` is ``f`` (15, nz, ny, nx), ``phase`` (nz, ny, nx) or ``vel``
    (3, nz, ny, nx).  The index maps are the interior ones."""
    heights = strip_heights(1, block, dtype_bits)
    out = [halo_block("f", "f", ((15, 0),), block, heights, nx)]
    for q, (_, cy, cz) in enumerate(DIRS):
        zs, ys = SIDE_READ_BY.get(cz), SIDE_READ_BY.get(cy)
        for suffix, zside, yside in (("_z", zs, None), ("_y", None, ys), ("_zy", zs, ys)):
            if ("z" in suffix and zs is None) or ("y" in suffix and ys is None):
                continue  # a side this component does not stream from
            out.append(halo_block(f"f{q}{suffix}", "f", ((1, q),), block, heights, nx,
                                  zside, yside))
    out.append(halo_block("phase", "phase", (), block, heights, nx))
    out += [halo_block(f"phase_z{side}", "phase", (), block, heights, nx, zside=side)
            for side in SIDES]
    out += [halo_block(f"phase_y{side}", "phase", (), block, heights, nx, yside=side)
            for side in SIDES]
    out.append(halo_block("vel", "vel", ((3, 0),), block, heights, nx))
    return tuple(out)


def output_blocks(block: tuple[int, int], nx: int):
    """The output tiles of f' and phi'."""
    bz, by = block
    return (Block("f_out", (15, bz, by, nx), lambda i, j: (0, i, j, 0), is_output=True),
            Block("phase_out", (bz, by, nx), lambda i, j: (i, j, 0), is_output=True))


def lbm_step_pallas(
    f: jnp.ndarray,
    phase: jnp.ndarray,
    vel: jnp.ndarray,
    tau: float = 0.8,
    width: float = 4.0,
    block: tuple[int, int] = (8, 8),
    vmem_limit_bytes: int | None = None,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One LB interface-tracking step; valid outside the one-cell z/y shell,
    where the clamped halo stands in for the neighbours."""
    _, nz, ny, nx = f.shape
    bz, by = block
    if nz % bz or ny % by:
        raise ValueError(f"grid {(nz, ny, nx)} not divisible by block {block}")
    bits = f.dtype.itemsize * 8
    fields = {"f": f, "phase": phase, "vel": vel}
    inputs = input_blocks(block, nx, bits)
    in_specs, out_specs = pallas_specs((*inputs, *output_blocks(block, nx)),
                                       {op: a.shape for op, a in fields.items()})
    kernel = functools.partial(_lbm_kernel, names=tuple(b.name for b in inputs),
                               hy=strip_heights(1, block, bits)[1], nx=nx, tau=tau, width=width)
    return pl.pallas_call(
        kernel,
        grid=(nz // bz, ny // by),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=(
            jax.ShapeDtypeStruct(f.shape, f.dtype),
            jax.ShapeDtypeStruct(phase.shape, phase.dtype),
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="lbm_step",
    )(*(fields[b.operand] for b in inputs))
