"""Pallas TPU kernel: range-r 3D star stencil (paper app 1, TPU-adapted).

TPU adaptation (DESIGN.md §2): instead of CUDA thread blocks, the configuration
space is the BlockSpec tiling.  The grid is 2D over (z, y) tiles; x (the lane
dimension) stays whole per tile and is ghost-padded by r.  The star reads no
(z, y) corner, so the z/y halo is five input BlockSpecs over the padded array
(:func:`input_blocks`): the centre tile, a thin strip on each z side and a
thin strip on each y side.  The strips' overlap with the neighbour tiles is
the V_red the paper's estimator models.  :mod:`repro.kernels.tiling` turns
these blocks and :func:`output_blocks` into the kernel's BlockSpecs and the
estimator's candidates, and ``ops.select_block()`` picks (bz, by) by ranking
them with `core.tpu_estimator` instead of autotuning.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..tiling import Block, pallas_specs
from .ref import star_offsets, star_weights_np

INPUTS = ("centre", "z_lo", "z_hi", "y_lo", "y_hi")


def strip_heights(r: int, block: tuple[int, int], dtype_bits: int) -> tuple[int, int]:
    """(hz, hy): the z and y extents of the halo strips of a (bz, by) tile.

    ``hz`` is the smallest divisor of ``bz`` that holds ``r`` planes.  ``hy``
    is the smallest divisor of ``by`` that holds ``r`` rows and is a multiple
    of the dtype's sublane tile (8 rows of 32 bits, 16 of 16 bits), so the y
    strips keep Mosaic's block-shape rule; without one it is ``by``, a whole
    neighbour tile.
    """
    bz, by = block
    sublanes = 8 * max(1, 32 // dtype_bits)
    hz = min(d for d in range(r, bz + 1) if bz % d == 0)
    hy = min(
        (d for d in range(r, by + 1) if by % d == 0 and d % sublanes == 0),
        default=by,
    )
    return hz, hy


def input_blocks(r: int, block: tuple[int, int], nx: int, dtype_bits: int):
    """The kernel's five inputs (:class:`~repro.kernels.tiling.Block`) over the
    x-padded (nz, ny, nx + 2r) array ``padded``, in :data:`INPUTS` order.

    The index maps are the interior ones: the strips sit in the blocks of
    their own height just outside the centre tile.
    """
    bz, by = block
    hz, hy = strip_heights(r, block, dtype_bits)
    kz, ky = bz // hz, by // hy
    nxp = nx + 2 * r
    maps = (
        ((bz, by, nxp), lambda i, j: (i, j, 0)),
        ((hz, by, nxp), lambda i, j: (i * kz - 1, j, 0)),
        ((hz, by, nxp), lambda i, j: ((i + 1) * kz, j, 0)),
        ((bz, hy, nxp), lambda i, j: (i, j * ky - 1, 0)),
        ((bz, hy, nxp), lambda i, j: (i, (j + 1) * ky, 0)),
    )
    return tuple(Block(name, shape, fn, "padded") for name, (shape, fn) in zip(INPUTS, maps))


def output_blocks(block: tuple[int, int], nx: int):
    """The (bz, by, nx) output tile."""
    return (Block("out", (*block, nx), lambda i, j: (i, j, 0), is_output=True),)


def _stencil_kernel(c_ref, zlo_ref, zhi_ref, ylo_ref, yhi_ref, out_ref, *, r, nx, weights):
    """One (bz, by, nx) output tile from its centre tile and four halo strips.

    z offsets read a (bz + 2r)-plane window: the last r planes of the lower z
    strip, the centre, the first r of the upper.  y offsets read the centre
    between the whole y strips, which keeps the concatenation on sublane
    tiles; only the r rows of each strip next to the centre are read.  x
    offsets read the centre's ghost-padded lanes.  Terms are summed in
    :func:`star_offsets` order, as the reference sums them.
    """
    bz, by, _ = c_ref.shape
    hz, hy = zlo_ref.shape[0], ylo_ref.shape[1]
    c = c_ref[...]
    zwin = jnp.concatenate([zlo_ref[hz - r :], c, zhi_ref[:r]], axis=0)
    ywin = jnp.concatenate([ylo_ref[...], c, yhi_ref[...]], axis=1)
    acc = jnp.zeros((bz, by, nx), dtype=out_ref.dtype)
    for k, (dz, dy, dx) in enumerate(star_offsets(r)):
        if dz:
            term = zwin[r + dz : r + dz + bz, :, r : r + nx]
        elif dy:
            term = ywin[:, hy + dy : hy + dy + by, r : r + nx]
        else:
            term = c[:, :, r + dx : r + dx + nx]
        acc = acc + weights[k] * term
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
    the global boundary than r read clamped strips and are not defined.
    """
    nz, ny, nx = src.shape
    bz, by = block
    if bz < r or by < r:
        raise ValueError(f"block {block} must be >= r={r} in z and y")
    if nz % bz or ny % by:
        raise ValueError(f"grid {src.shape} not divisible by block {block}")
    padded = jnp.pad(src, ((0, 0), (0, 0), (r, r)), mode="edge")
    # weights as python floats: compile-time constants inside the kernel body
    w = tuple(float(v) for v in star_weights_np(r))
    blocks = (*input_blocks(r, block, nx, src.dtype.itemsize * 8), *output_blocks(block, nx))
    in_specs, (out_spec,) = pallas_specs(blocks, {"padded": padded.shape})
    kernel = functools.partial(_stencil_kernel, r=r, nx=nx, weights=w)
    return pl.pallas_call(
        kernel,
        grid=(nz // bz, ny // by),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((nz, ny, nx), src.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="stencil25",
    )(*([padded] * len(in_specs)))
