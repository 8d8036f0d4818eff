"""jit'd public wrapper for the stencil kernel + estimator-guided block selection."""
from __future__ import annotations

import jax.numpy as jnp

from ...core import tpu_estimator as te
from ...core.machine import TPUMachine
from .. import tiling
from ..entry import chip, entry_point, timed_pick
from .kernel import input_blocks, output_blocks, stencil25_pallas
from .ref import stencil25_ref

CANDIDATE_BLOCKS = ((8, 8), (8, 16), (16, 8), (16, 16), (16, 32), (32, 16), (32, 32), (64, 8), (8, 64))


def config_space(shape: tuple[int, int, int], r: int, dtype_bits: int):
    """Candidate PallasConfigs for `core.tpu_estimator` ranking: the kernel's
    own five inputs (:func:`kernel.input_blocks`: the centre tile and four
    halo strips, whose overlap with the neighbour tiles is the refetch
    redundancy) plus ``out``, at every block of :data:`CANDIDATE_BLOCKS` of
    at least ``r`` in z and y that tiles the grid."""
    nx = shape[2]
    return tiling.block_space(
        "stencil", shape, CANDIDATE_BLOCKS,
        lambda b: (*input_blocks(r, b, nx, dtype_bits), *output_blocks(b, nx)),
        dtype_bits, 2.0 * (6 * r + 1), skip=lambda bz, by: bz < r or by < r)


def select_block(
    shape: tuple[int, int, int],
    r: int = 4,
    dtype=jnp.float32,
    *,
    machine: TPUMachine,
) -> tuple[tuple[int, int], te.TPUEstimate]:
    """Estimator-guided configuration selection (the paper's selection problem)."""
    cands = config_space(shape, r, jnp.dtype(dtype).itemsize * 8)
    cfg, est = tiling.pick(cands, machine, f"no candidate block tiles divide grid {shape}")
    return cfg.meta["block"], est


@entry_point(static_argnames=("r", "block", "interpret"))
def stencil25(
    src: jnp.ndarray,
    r: int = 4,
    block: tuple[int, int] | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Range-r 3D star stencil; picks the block via the estimator when not given.

    On the chip the block is selected for, and compiled under the VMEM limit
    of, :func:`repro.kernels.entry.chip`; ``interpret=True`` runs on no chip
    and needs an explicit ``block``.  Each call runs in the span
    ``stencil25.call``, the pick in ``stencil25.pick`` (see
    :mod:`repro.kernels.entry`).
    """
    machine, vmem_limit = chip(interpret)
    if block is None:
        block, _ = timed_pick("stencil25", select_block, src.shape, r, src.dtype, machine=machine)
    return stencil25_pallas(src, r=r, block=block, interpret=interpret,
                            vmem_limit_bytes=vmem_limit)


__all__ = ["stencil25", "stencil25_ref", "select_block", "config_space"]
