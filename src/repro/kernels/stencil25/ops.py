"""jit'd public wrapper for the stencil kernel + estimator-guided block selection."""
from __future__ import annotations

import jax.numpy as jnp

from ...core import tpu_estimator as te
from ...core.machine import TPUMachine, device_machine
from ..entry import entry_point, timed_pick
from .kernel import input_blocks, stencil25_pallas
from .ref import stencil25_ref

CANDIDATE_BLOCKS = ((8, 8), (8, 16), (16, 8), (16, 16), (16, 32), (32, 16), (32, 32), (64, 8), (8, 64))


def config_space(shape: tuple[int, int, int], r: int, dtype_bits: int):
    """Candidate PallasConfigs for `core.tpu_estimator` ranking.

    Each candidate's accesses are the kernel's own five inputs
    (:func:`kernel.input_blocks`: the centre tile and four halo strips, whose
    overlap with the neighbour tiles is the refetch redundancy) plus ``out``;
    interior (unclamped) index maps are used as the representative group
    (paper §III.D: representative collaborative groups away from boundaries).
    """
    nz, ny, nx = shape
    out = []
    for bz, by in CANDIDATE_BLOCKS:
        if bz < r or by < r or nz % bz or ny % by:
            continue
        accesses = [
            te.BlockAccess(name=name, block_shape=block_shape, index_map=index_map,
                           dtype_bits=dtype_bits)
            for name, block_shape, index_map in input_blocks(r, (bz, by), nx, dtype_bits)
        ]
        accesses.append(
            te.BlockAccess(
                name="out",
                block_shape=(bz, by, nx),
                index_map=lambda i, j: (i, j, 0),
                dtype_bits=dtype_bits,
                is_output=True,
            )
        )
        out.append(
            te.PallasConfig(
                name=f"stencil_bz{bz}_by{by}",
                grid=(nz // bz, ny // by),
                accesses=tuple(accesses),
                flops_per_step=2.0 * (6 * r + 1) * bz * by * nx,
                is_matmul=False,
                meta={"block": (bz, by)},
            )
        )
    return out


def select_block(
    shape: tuple[int, int, int],
    r: int = 4,
    dtype=jnp.float32,
    *,
    machine: TPUMachine,
) -> tuple[tuple[int, int], te.TPUEstimate]:
    """Estimator-guided configuration selection (the paper's selection problem)."""
    bits = jnp.dtype(dtype).itemsize * 8
    cands = config_space(shape, r, bits)
    if not cands:
        raise ValueError(f"no candidate block tiles divide grid {shape}")
    cfg, est = te.select_config(cands, machine)
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
    of, :func:`device_machine`; ``interpret=True`` runs on no chip and needs
    an explicit ``block``.  Each call runs in the span ``stencil25.call``, the
    pick in ``stencil25.pick`` (see :mod:`repro.kernels.entry`).
    """
    machine = None if interpret else device_machine()
    if block is None:
        block, _ = timed_pick("stencil25", select_block, src.shape, r, src.dtype, machine=machine)
    return stencil25_pallas(
        src, r=r, block=block, interpret=interpret,
        vmem_limit_bytes=None if machine is None else machine.vmem_usable,
    )


__all__ = ["stencil25", "stencil25_ref", "select_block", "config_space"]
