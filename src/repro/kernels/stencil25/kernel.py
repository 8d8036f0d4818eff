"""Pallas TPU kernel: range-r 3D star stencil (paper app 1, TPU-adapted).

TPU adaptation (DESIGN.md §2): instead of CUDA thread blocks, the configuration
space is the BlockSpec tiling.  The grid is 2D over (z, y) tiles; x (the lane
dimension) stays whole per tile and is ghost-padded by r.  Halo exchange in z/y is
expressed with nine overlapping input BlockSpecs (the 3x3 neighborhood of the
center tile) — the redundant neighbor fetches are exactly the V_red the paper's
estimator models, and `ops.select_block()` picks (bz, by) by ranking candidates
with `core.tpu_estimator` instead of autotuning.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import star_offsets, star_weights_np

NEIGHBORS = [(dz, dy) for dz in (-1, 0, 1) for dy in (-1, 0, 1)]


def _stencil_kernel(*refs, r: int, bz: int, by: int, nx: int, weights):
    """refs = 9 input tiles (3x3 neighborhood, each (bz, by, nxp)) + out ref."""
    out_ref = refs[-1]
    tiles = refs[:-1]
    # assemble the (3bz, 3by, nxp) neighborhood, then crop to the halo window
    rows = []
    for iz in range(3):
        row = jnp.concatenate(
            [tiles[iz * 3 + iy][...] for iy in range(3)], axis=1
        )
        rows.append(row)
    vol = jnp.concatenate(rows, axis=0)  # (3bz, 3by, nxp)
    win = vol[bz - r : 2 * bz + r, by - r : 2 * by + r, :]  # (bz+2r, by+2r, nxp)
    acc = jnp.zeros((bz, by, nx), dtype=out_ref.dtype)
    for k, (dz, dy, dx) in enumerate(star_offsets(r)):
        acc = acc + weights[k] * win[
            r + dz : r + dz + bz, r + dy : r + dy + by, r + dx : r + dx + nx
        ]
    out_ref[...] = acc


def stencil25_pallas(
    src: jnp.ndarray,
    r: int = 4,
    block: tuple[int, int] = (16, 16),
    vmem_limit_bytes: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Apply the stencil to ``src`` (nz, ny, nx).

    Interior [r:-r, r:-r, r:-r] matches :func:`ref.stencil25_ref`; cells closer to
    the global boundary than r use clamped tile indices and are not defined.
    """
    nz, ny, nx = src.shape
    bz, by = block
    if bz < r or by < r:
        raise ValueError(f"block {block} must be >= r={r} in z and y")
    if nz % bz or ny % by:
        raise ValueError(f"grid {src.shape} not divisible by block {block}")
    nzb, nyb = nz // bz, ny // by
    nxp = nx + 2 * r
    padded = jnp.pad(src, ((0, 0), (0, 0), (r, r)), mode="edge")
    # weights as python floats: compile-time constants inside the kernel body
    w = tuple(float(v) for v in star_weights_np(r))

    def make_index_map(dz, dy):
        def index_map(i, j):
            zi = jnp.clip(i + dz, 0, nzb - 1)
            yj = jnp.clip(j + dy, 0, nyb - 1)
            return (zi, yj, 0)

        return index_map

    in_specs = [
        pl.BlockSpec((bz, by, nxp), make_index_map(dz, dy)) for dz, dy in NEIGHBORS
    ]
    out_spec = pl.BlockSpec((bz, by, nx), lambda i, j: (i, j, 0))
    kernel = functools.partial(
        _stencil_kernel, r=r, bz=bz, by=by, nx=nx, weights=w
    )
    return pl.pallas_call(
        kernel,
        grid=(nzb, nyb),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((nz, ny, nx), src.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="stencil25",
    )(*([padded] * 9))
