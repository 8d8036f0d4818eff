"""The one tiling seam of the lattice kernels (stencil25, lbm_d3q15, lbm_d3q27).

Each kernel declares its operand blocks once, as :class:`Block` records over
a 2D (z, y) grid whose blocks hold whole x rows: ``kernel.input_blocks`` and
``kernel.output_blocks``.  The index maps are the interior ones.  From that
declaration this module builds the Pallas BlockSpecs the kernel compiles with
(:func:`pallas_specs`, inputs clamped to the grid), the estimator's candidates
(:func:`block_space`, maps as declared: the paper's representative group away
from the boundary, §III.D) and the estimator's pick among candidates
(:func:`pick`, which the attention and WKV selections share).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Sequence

import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core import tpu_estimator as te
from ..core.machine import TPUMachine

@dataclass(frozen=True)
class Block:
    """One block of a kernel operand: ``index_map`` takes the grid step
    (i, j) to the interior block index.  An input names its ``operand``, the
    array whose shape clamps it; an output is not clamped and names none."""

    name: str
    shape: tuple[int, ...]
    index_map: Callable[[int, int], tuple]
    operand: str | None = None
    is_output: bool = False


def pallas_specs(blocks: Sequence[Block], extents: dict[str, tuple[int, ...]]):
    """``(in_specs, out_specs)`` of ``blocks`` in their order: each input's
    block index clamped to the blocks of its shape in ``extents[operand]``
    (an edge tile's halo reads the edge block), the outputs as declared."""

    def clamped(b: Block):
        full = extents[b.operand]

        def clamped_map(i, j):
            return tuple(jnp.clip(k, 0, n // s - 1)
                         for k, s, n in zip(b.index_map(i, j), b.shape, full))

        return clamped_map

    in_specs = [pl.BlockSpec(b.shape, clamped(b)) for b in blocks if not b.is_output]
    out_specs = tuple(pl.BlockSpec(b.shape, b.index_map) for b in blocks if b.is_output)
    return in_specs, out_specs


def block_space(
    prefix: str,
    shape: tuple[int, int, int],
    candidates: Sequence[tuple[int, int]],
    blocks: Callable[[tuple[int, int]], Sequence[Block]],
    dtype_bits: int,
    flops_per_cell: float,
    skip: Callable[[int, int], bool] | None = None,
) -> list[te.PallasConfig]:
    """One ``<prefix>_bz<bz>_by<by>`` candidate for each (bz, by) of
    ``candidates`` that tiles the (nz, ny) of ``shape`` and that ``skip``
    does not refuse: the accesses of ``blocks((bz, by))`` in their order,
    ``flops_per_cell`` for each cell of the (bz, by, nx) tile."""
    nz, ny, nx = shape
    out = []
    for bz, by in candidates:
        if nz % bz or ny % by or (skip is not None and skip(bz, by)):
            continue
        accesses = tuple(te.BlockAccess(b.name, b.shape, b.index_map, dtype_bits, b.is_output)
                         for b in blocks((bz, by)))
        out.append(te.PallasConfig(
            name=f"{prefix}_bz{bz}_by{by}",
            grid=(nz // bz, ny // by),
            accesses=accesses,
            flops_per_step=float(flops_per_cell * bz * by * nx),
            is_matmul=False,
            meta={"block": (bz, by)},
        ))
    return out


def pick(candidates: Sequence[te.PallasConfig], machine: TPUMachine, empty: str):
    """``(config, estimate)`` of the estimator's best feasible candidate for
    ``machine``.  Raises ``ValueError(empty)`` when there is no candidate, and
    as :func:`~repro.core.tpu_estimator.select_config` when there is no
    machine or no candidate fits."""
    if not candidates:
        raise ValueError(empty)
    return te.select_config(candidates, machine)
