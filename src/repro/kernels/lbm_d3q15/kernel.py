"""Pallas TPU kernel: D3Q15 Allen-Cahn interface-tracking LB step (paper app 2).

TPU adaptation: tiles over (z, y); x is the lane dimension, ghost-padded by 1.
Halo (range-1, including corners, for the pull streaming and the 7pt phase
stencil) is expressed with 3x3 overlapping neighbor BlockSpecs for the pdf and
phase arrays; velocity needs the center tile only.  Block shape selection is
estimator-guided via `ops.select_block` — exactly the paper's configuration-
selection use-case, with VMEM feasibility as the hard capacity gate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import DIRS, WEIGHTS

NEIGHBORS = [(dz, dy) for dz in (-1, 0, 1) for dy in (-1, 0, 1)]


def _assemble(tiles, bz: int, by: int, halo: int):
    """3x3 tiles (each (..., bz, by, nxp)) -> (..., bz+2h, by+2h, nxp) window."""
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
    """refs: 9 pdf tiles (15,bz,by,nxp), 9 phase tiles (bz,by,nxp), 1 vel tile
    (3,bz,by,nxp), then outputs: f_out (15,bz,by,nx), phase_out (bz,by,nx)."""
    f_tiles = [refs[i][...] for i in range(9)]
    p_tiles = [refs[9 + i][...] for i in range(9)]
    vel = refs[18][...]
    f_out_ref, phase_out_ref = refs[19], refs[20]

    fwin = _assemble(f_tiles, bz, by, 1)  # (15, bz+2, by+2, nxp)
    pwin = _assemble(p_tiles, bz, by, 1)  # (bz+2, by+2, nxp)

    def center_x(a):  # crop the ghost-padded x dim of an unassembled tile
        return a[..., 1 : 1 + nx]

    # pull streaming: value at p comes from p - c_q
    pulled = []
    for q, (cx, cy, cz) in enumerate(DIRS):
        pulled.append(
            fwin[
                q,
                1 - cz : 1 - cz + bz,
                1 - cy : 1 - cy + by,
                1 - cx : 1 - cx + nx,
            ]
        )
    phi_new = pulled[0]
    for q in range(1, 15):
        phi_new = phi_new + pulled[q]
    # 7pt central differences on the input phase window
    gx = 0.5 * (pwin[1 : 1 + bz, 1 : 1 + by, 2 : 2 + nx] - pwin[1 : 1 + bz, 1 : 1 + by, 0:nx])
    gy = 0.5 * (pwin[1 : 1 + bz, 2 : 2 + by, 1 : 1 + nx] - pwin[1 : 1 + bz, 0:by, 1 : 1 + nx])
    gz = 0.5 * (pwin[2 : 2 + bz, 1 : 1 + by, 1 : 1 + nx] - pwin[0:bz, 1 : 1 + by, 1 : 1 + nx])
    inv_norm = jax.lax.rsqrt(gx * gx + gy * gy + gz * gz + 1e-12)
    nxv, nyv, nzv = gx * inv_norm, gy * inv_norm, gz * inv_norm
    sharp = (4.0 * phi_new * (1.0 - phi_new)) / width
    ux = center_x(vel[0])
    uy = center_x(vel[1])
    uz = center_x(vel[2])
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
    nzb, nyb = nz // bz, ny // by
    nxp = nx + 2
    fp = jnp.pad(f, ((0, 0), (0, 0), (0, 0), (1, 1)), mode="wrap")
    pp = jnp.pad(phase, ((0, 0), (0, 0), (1, 1)), mode="wrap")
    vp = jnp.pad(vel, ((0, 0), (0, 0), (0, 0), (1, 1)), mode="wrap")

    def make_map4(dz, dy):  # (component, z, y, x) arrays
        def index_map(i, j):
            return (
                0,
                jnp.clip(i + dz, 0, nzb - 1),
                jnp.clip(j + dy, 0, nyb - 1),
                0,
            )

        return index_map

    def make_map3(dz, dy):  # (z, y, x) arrays
        def index_map(i, j):
            return (
                jnp.clip(i + dz, 0, nzb - 1),
                jnp.clip(j + dy, 0, nyb - 1),
                0,
            )

        return index_map

    in_specs = [
        pl.BlockSpec((15, bz, by, nxp), make_map4(dz, dy)) for dz, dy in NEIGHBORS
    ]
    in_specs += [
        pl.BlockSpec((bz, by, nxp), make_map3(dz, dy)) for dz, dy in NEIGHBORS
    ]
    in_specs += [pl.BlockSpec((3, bz, by, nxp), make_map4(0, 0))]
    out_specs = (
        pl.BlockSpec((15, bz, by, nx), lambda i, j: (0, i, j, 0)),
        pl.BlockSpec((bz, by, nx), lambda i, j: (i, j, 0)),
    )
    kernel = functools.partial(_lbm_kernel, bz=bz, by=by, nx=nx, tau=tau, width=width)
    return pl.pallas_call(
        kernel,
        grid=(nzb, nyb),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=(
            jax.ShapeDtypeStruct(f.shape, f.dtype),
            jax.ShapeDtypeStruct(phase.shape, phase.dtype),
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="lbm_step",
    )(*([fp] * 9 + [pp] * 9 + [vp]))
