"""Pallas TPU kernel: D3Q15 Allen-Cahn interface-tracking LB step (paper app 2).

TPU adaptation: tiles over (z, y); x is the lane dimension and every block
holds whole x rows, so the periodic x neighbours are lane rotations of the
row (no ghost-padded copy of f, phase or vel).
Halo (range-1, including corners, for the pull streaming and the 7pt phase
stencil) is expressed with 3x3 overlapping neighbor BlockSpecs for the pdf and
phase arrays; velocity needs the center tile only.  :func:`input_blocks` and
:func:`output_blocks` are the one description of these blocks, which
:mod:`repro.kernels.tiling` turns into the BlockSpecs and the estimator's
candidates.  Block shape selection is estimator-guided via
`ops.select_block` — exactly the paper's configuration-selection use-case,
with VMEM feasibility as the hard capacity gate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tiling import Block, pallas_specs
from .ref import DIRS, WEIGHTS

NEIGHBORS = [(dz, dy) for dz in (-1, 0, 1) for dy in (-1, 0, 1)]
# the (bz, by) blocks the estimator ranks, for this kernel and the D3Q27 one
CANDIDATE_BLOCKS = ((4, 4), (8, 8), (8, 16), (16, 8), (16, 16), (32, 8), (8, 32))


def _assemble(tiles, bz: int, by: int, halo: int):
    """3x3 tiles (each (..., bz, by, nx)) -> (..., bz+2h, by+2h, nx) window."""
    rows = []
    for iz in range(3):
        rows.append(jnp.concatenate([tiles[iz * 3 + iy] for iy in range(3)], axis=-2))
    vol = jnp.concatenate(rows, axis=-3)
    return vol[
        ...,
        bz - halo : 2 * bz + halo,
        by - halo : 2 * by + halo,
        :,
    ]


def _lbm_kernel(*refs, bz: int, by: int, nx: int, tau: float, width: float):
    """refs: 9 pdf tiles (15,bz,by,nx), 9 phase tiles (bz,by,nx), 1 vel tile
    (3,bz,by,nx), then outputs: f_out (15,bz,by,nx), phase_out (bz,by,nx)."""
    f_tiles = [refs[i][...] for i in range(9)]
    p_tiles = [refs[9 + i][...] for i in range(9)]
    vel = refs[18][...]
    f_out_ref, phase_out_ref = refs[19], refs[20]

    fwin = _assemble(f_tiles, bz, by, 1)  # (15, bz+2, by+2, nx)
    pwin = _assemble(p_tiles, bz, by, 1)  # (bz+2, by+2, nx)

    def xshift(a, cx: int):
        """a(x - cx) at x, periodic: a lane rotation of the whole row."""
        return pltpu.roll(a, cx % nx, a.ndim - 1) if cx else a

    # pull streaming: value at p comes from p - c_q
    pulled = []
    for q, (cx, cy, cz) in enumerate(DIRS):
        pulled.append(xshift(fwin[q, 1 - cz : 1 - cz + bz, 1 - cy : 1 - cy + by], cx))
    phi_new = pulled[0]
    for q in range(1, 15):
        phi_new = phi_new + pulled[q]
    # 7pt central differences on the input phase window
    prow = pwin[1 : 1 + bz, 1 : 1 + by]
    gx = 0.5 * (xshift(prow, -1) - xshift(prow, 1))
    gy = 0.5 * (pwin[1 : 1 + bz, 2 : 2 + by] - pwin[1 : 1 + bz, 0:by])
    gz = 0.5 * (pwin[2 : 2 + bz, 1 : 1 + by] - pwin[0:bz, 1 : 1 + by])
    inv_norm = jax.lax.rsqrt(gx * gx + gy * gy + gz * gz + 1e-12)
    nxv, nyv, nzv = gx * inv_norm, gy * inv_norm, gz * inv_norm
    sharp = (4.0 * phi_new * (1.0 - phi_new)) / width
    ux, uy, uz = vel[0], vel[1], vel[2]
    inv_tau = 1.0 / tau
    outs = []
    for q, (cx, cy, cz) in enumerate(DIRS):
        w = WEIGHTS[q]
        cu = 3.0 * (cx * ux + cy * uy + cz * uz)
        heq = w * phi_new * (1.0 + cu)
        forcing = w * sharp * (cx * nxv + cy * nyv + cz * nzv)
        outs.append(pulled[q] - inv_tau * (pulled[q] - heq) + forcing)
    f_out_ref[...] = jnp.stack(outs, axis=0)
    phase_out_ref[...] = phi_new


def input_blocks(block: tuple[int, int], nx: int):
    """The kernel's inputs (:class:`~repro.kernels.tiling.Block`), in the
    kernel's order: the nine (dz, dy) neighbour tiles of f (15, nz, ny, nx),
    the nine of phase (nz, ny, nx), then vel's (3, nz, ny, nx) centre tile.
    Every block holds whole x rows.  The index maps are the interior ones."""
    bz, by = block
    out = [Block(f"f{k}", (15, bz, by, nx), lambda i, j, dz=dz, dy=dy: (0, i + dz, j + dy, 0), "f")
           for k, (dz, dy) in enumerate(NEIGHBORS)]
    out += [Block(f"p{k}", (bz, by, nx), lambda i, j, dz=dz, dy=dy: (i + dz, j + dy, 0), "phase")
            for k, (dz, dy) in enumerate(NEIGHBORS)]
    out.append(Block("vel", (3, bz, by, nx), lambda i, j: (0, i, j, 0), "vel"))
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
    """One LB interface-tracking step; valid on the interior (1-cell shell excluded)."""
    _, nz, ny, nx = f.shape
    bz, by = block
    if nz % bz or ny % by:
        raise ValueError(f"grid {(nz, ny, nx)} not divisible by block {block}")
    fields = {"f": f, "phase": phase, "vel": vel}
    inputs = input_blocks(block, nx)
    in_specs, out_specs = pallas_specs((*inputs, *output_blocks(block, nx)),
                                       {op: a.shape for op, a in fields.items()})
    kernel = functools.partial(_lbm_kernel, bz=bz, by=by, nx=nx, tau=tau, width=width)
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
