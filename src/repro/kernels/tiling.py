"""The one tiling seam of the lattice kernels (stencil25, lbm_d3q15, lbm_d3q27).

Each kernel declares its operand blocks once, as :class:`Block` records over
a 2D (z, y) grid whose blocks hold whole x rows: ``kernel.input_blocks`` and
``kernel.output_blocks``.  The index maps are the interior ones.  From that
declaration this module builds the Pallas BlockSpecs the kernel compiles with
(:func:`pallas_specs`, inputs clamped to the grid), the estimator's candidates
(:func:`block_space`, maps as declared: the paper's representative group away
from the boundary, §III.D) and the estimator's pick among candidates
(:func:`pick`, which the attention and WKV selections share).

The two LBM kernels read the same z/y halo layout: a centre tile, and per
field only the z strip, y strip and (z, y) corner piece on the sides it
pulls from (:func:`halo_block`, :data:`SIDE_READ_BY`).  Their bodies walk a
tile's z planes and put the halo planes and rows together with
:func:`_plane` and :func:`_yshift`.

A domain too large for one chip is split in y over a chain of chips (a mesh
axis :data:`AXIS`), each holding a block of whole z planes.  An edge tile of
a chip's block reads, in place of its y strip, the one row next to the seam
that the neighbouring chip sent (:func:`exchange`): a ghost of y extent 1
declared beside each y strip and corner piece (:func:`ghost_blocks`), and
taken in the body by :func:`_ystrip`.  Only the rows some y strip reads
are sent (:func:`ghost_rows`).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Sequence

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from ..core import tpu_estimator as te
from ..core.machine import TPUMachine

SIDES = ("lo", "hi")
SIDE_READ_BY = {1: "lo", -1: "hi"}  # pulling along c = +1 reads the rows below
AXIS = "y"  # the mesh axis a domain is split along, a chain of chips
GHOST = "_ghost"  # suffix of a ghost block's name, and of a ghost operand's before its side


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
    lead: tuple[tuple[int, int], ...] = ()  # (extent, block index) of each axis before z
    yside: str | None = None  # the side of a y strip or corner piece


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


def _strip_index(side: str, i, k: int):
    """Block index of the strip of height ``b / k`` that lies just below
    (``side`` "lo") or just above ("hi") the centre tile ``i`` of height ``b``."""
    return i * k - 1 if side == "lo" else (i + 1) * k


def halo_block(name: str, operand: str, lead, block: tuple[int, int],
               heights: tuple[int, int], nx: int, zside=None, yside=None) -> Block:
    """The input :class:`Block` of ``operand``'s centre tile at ``block``
    (both sides ``None``), or of its strip or corner piece of the (hz, hy)
    ``heights`` on the given z and y sides, over whole x rows.  ``lead``
    gives the (extent, block index) of each axis before z."""
    (bz, by), (hz, hy) = block, heights
    kz, ky = bz // hz, by // hy
    shape = (*(n for n, _ in lead), hz if zside else bz, hy if yside else by, nx)

    def index_map(i, j):
        return (*(b for _, b in lead),
                i if zside is None else _strip_index(zside, i, kz),
                j if yside is None else _strip_index(yside, j, ky), 0)

    return Block(name, shape, index_map, operand, lead=tuple(lead), yside=yside)


def _plane(centre, strip, z, dz: int):
    """Plane z + dz (dz in -1, 0, 1) of a tile's z extension, as a value:
    the centre block's own plane, or past its lower (upper) end the last
    (first) plane of ``strip``, the z strip on that side."""
    def at(ref, k):
        return ref[k] if len(ref.shape) == 3 else ref[:, k]

    bz = centre.shape[-3]
    if dz == 0:
        return at(centre, z)
    own = at(centre, jnp.clip(z + dz, 0, bz - 1))
    if dz < 0:
        return jnp.where(z == 0, at(strip, strip.shape[-3] - 1), own)
    return jnp.where(z == bz - 1, at(strip, 0), own)


def _yshift(centre, strip, cy: int, hy: int):
    """Rows y - cy of ``centre`` (y on axis -2), between it and its y strip
    so that the concatenation stays on sublane tiles."""
    by = centre.shape[-2]
    if cy == 1:
        return jnp.concatenate([strip, centre], axis=-2)[..., hy - 1 : hy - 1 + by, :]
    if cy == -1:
        return jnp.concatenate([centre, strip], axis=-2)[..., 1 : 1 + by, :]
    return centre


def _ystrip(ins, name: str, corner: str | None, z, dz: int, at_edge, part=lambda ref: ref):
    """Plane z + dz of the y strip ``ins[name]``, past the tile's z ends its
    corner piece ``ins[corner]`` (:func:`_plane`), each ref seen through
    ``part``.  Where the kernel reads ghosts (``at_edge`` not ``None``), an
    edge tile of the chip's block takes the ghost row of the same plane in
    its place, over all the strip's rows: the strip's row next to the tile,
    the one :func:`_yshift` reads, is then the neighbouring chip's."""
    def plane(n, c):
        return _plane(part(ins[n]), None if c is None else part(ins[c]), z, dz)

    strip = plane(name, corner)
    if at_edge is None:
        return strip
    ghost = plane(name + GHOST, None if corner is None else corner + GHOST)
    return jnp.where(at_edge, jnp.broadcast_to(ghost, strip.shape), strip)


def edges(names) -> dict:
    """``{side: whether this grid step is the chip's edge tile on that
    side}`` for a kernel whose inputs ``names`` hold ghosts; ``None`` on
    each side for one that reads none."""
    if not any(n.endswith(GHOST) for n in names):
        return dict.fromkeys(SIDES)
    j = pl.program_id(1)
    return {"lo": j == 0, "hi": j == pl.num_programs(1) - 1}


def ghost_operand(operand: str, side: str) -> str:
    return f"{operand}{GHOST}_{side}"


def ghost_rows(blocks: Sequence[Block]) -> dict[tuple[str, str], tuple[int, ...]]:
    """``{(operand, side): elements of its leading axis}``: what the y strips
    and corner pieces among ``blocks`` read on each side, so what the chip
    on that side sends; ``()`` for an operand with no leading axis."""
    rows: dict[tuple[str, str], set[int]] = {}
    for b in blocks:
        if b.yside is not None:
            got = rows.setdefault((b.operand, b.yside), set())
            for n, k in b.lead:
                got.update(range(n * k, n * (k + 1)))
    return {key: tuple(sorted(e)) for key, e in rows.items()}


def ghost_blocks(blocks: Sequence[Block]) -> tuple[Block, ...]:
    """The ghost of each y strip and corner piece among ``blocks``: the same
    z extent and z index over one row, of the operand
    :func:`ghost_operand` names, whose leading axis holds :func:`ghost_rows`'
    elements in order."""
    rows = ghost_rows(blocks)
    out = []
    for b in blocks:
        if b.yside is None:
            continue
        lead = tuple(rows[(b.operand, b.yside)].index(n * k) // n for n, k in b.lead)
        out.append(Block(b.name + GHOST, (*b.shape[:-2], 1, b.shape[-1]),
                         lambda i, j, m=b.index_map, lead=lead: (*lead, m(i, j)[-3], 0, 0),
                         ghost_operand(b.operand, b.yside)))
    return tuple(out)


def with_ghosts(blocks: Sequence[Block], mesh) -> tuple[Block, ...]:
    """``blocks``, and their ghosts where the domain is split over ``mesh``."""
    return (*blocks, *(ghost_blocks(blocks) if mesh is not None else ()))


def local_shape(shape: tuple[int, int, int], mesh) -> tuple[int, int, int]:
    """The (nz, ny, nx) block one chip holds of a domain of ``shape`` split
    in y over ``mesh`` (the whole domain without one)."""
    if mesh is None:
        return tuple(shape)
    nz, ny, nx = shape
    chips = mesh.shape[AXIS]
    if ny % chips:
        raise ValueError(f"ny {ny} does not split over {chips} chips")
    return nz, ny // chips, nx


def exchange(fields: dict, rows: dict[tuple[str, str], tuple[int, ...]]) -> dict:
    """Inside a ``shard_map`` over :data:`AXIS`: the ghost operand of each
    (operand, side) of ``rows`` whose operand is among ``fields``, as
    :func:`ghost_blocks` reads it.  Each chip sends its last y row to the
    chip above, whose "lo" ghost it is, and its first row to the chip below
    (the "hi" ghost there), of the leading elements ``rows`` names.  The
    seams form a chain, not a ring: the first chip's "lo" ghost and the
    last's "hi" ghost are zeros, left in the domain's undefined shell."""
    chips = lax.axis_size(AXIS)
    perm = {"lo": [(k, k + 1) for k in range(chips - 1)],
            "hi": [(k + 1, k) for k in range(chips - 1)]}
    out = {}
    for (operand, side), elems in rows.items():
        if operand not in fields:
            continue
        a = fields[operand]
        y = a.shape[-2] - 1 if side == "lo" else 0
        row = lax.slice_in_dim(a, y, y + 1, axis=a.ndim - 2)
        if elems:
            row = jnp.concatenate([row[e : e + 1] for e in elems])
        out[ghost_operand(operand, side)] = lax.ppermute(row, AXIS, perm[side])
    return out


def sent_bytes(ghosts: dict) -> int:
    """Bytes of ``ghosts``: what one chip with a neighbour on each side sends."""
    return sum(g.size * g.dtype.itemsize for g in ghosts.values())
