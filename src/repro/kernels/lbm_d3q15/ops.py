"""jit'd wrapper + estimator-guided block selection for the LBM kernel."""
from __future__ import annotations

import jax.numpy as jnp

from ...core import tpu_estimator as te
from ...core.machine import TPUMachine
from .. import tiling
from ..entry import chip, entry_point, timed_pick
from .kernel import CANDIDATE_BLOCKS, input_blocks, lbm_step_pallas, output_blocks
from .ref import init_fields, lbm_step_ref


def config_space(shape: tuple[int, int, int], dtype_bits: int, mesh=None):
    """Candidate PallasConfigs for `core.tpu_estimator` ranking: the kernel's
    own inputs (:func:`kernel.input_blocks`: f's centre and each component's
    z strip, y strip and corner piece it streams from, phase's centre and
    four strips, vel's centre, all over whole x rows) plus the outputs f'
    and phi', at every block of :data:`kernel.CANDIDATE_BLOCKS` that tiles
    the grid.  With a ``mesh`` the domain of ``shape`` is split in y over
    its chips (:mod:`repro.kernels.tiling`): the candidates tile one chip's
    block and read the ghosts too."""
    nx = shape[2]
    return tiling.block_space(
        "lbm", tiling.local_shape(shape, mesh), CANDIDATE_BLOCKS,
        lambda b: (*tiling.with_ghosts(input_blocks(b, nx, dtype_bits), mesh),
                   *output_blocks(b, nx)), dtype_bits,
        350.0)


def select_block(
    shape: tuple[int, int, int], dtype=jnp.float32, *, machine: TPUMachine, mesh=None
) -> tuple[tuple[int, int], te.TPUEstimate]:
    cands = config_space(shape, jnp.dtype(dtype).itemsize * 8, mesh)
    cfg, est = tiling.pick(cands, machine, f"no candidate block tiles divide grid {shape}")
    return cfg.meta["block"], est


def lbm_step(
    f: jnp.ndarray,
    phase: jnp.ndarray,
    vel: jnp.ndarray,
    tau: float = 0.8,
    width: float = 4.0,
    block: tuple[int, int] | None = None,
    interpret: bool = False,
):
    """One LB step; block, VMEM limit and spans (``lbm_step.call``,
    ``lbm_step.pick``) as in :func:`stencil25.ops.stencil25`."""
    machine, vmem_limit = chip(interpret)
    if block is None:
        block, _ = timed_pick("lbm_step", select_block, f.shape[1:], f.dtype, machine=machine)
    return lbm_step_pallas(f, phase, vel, tau=tau, width=width, block=block,
                           interpret=interpret, vmem_limit_bytes=vmem_limit)


# the step un-jitted, for a caller that traces it into its own jit (the
# two-phase step); the entry keeps its name, so its jit is ``jit_lbm_step``
traced_lbm_step = lbm_step
lbm_step = entry_point(static_argnames=("tau", "width", "block", "interpret"))(lbm_step)


__all__ = ["lbm_step", "traced_lbm_step", "lbm_step_ref", "init_fields", "select_block",
           "config_space"]
