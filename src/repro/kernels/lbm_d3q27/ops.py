"""jit'd two-phase step + estimator-guided block selection for the D3Q27
hydrodynamic kernel."""
from __future__ import annotations

import jax.numpy as jnp

from ...core import tpu_estimator as te
from ...core.machine import TPUMachine
from .. import tiling
from ..entry import chip, entry_point, timed_pick
from ..lbm_d3q15.kernel import CANDIDATE_BLOCKS
from ..lbm_d3q15.ops import traced_lbm_step
from .kernel import hydro_step_pallas, input_blocks, output_blocks
from .ref import TwoPhaseParams, equilibrium, hydro_step_ref, twophase_step_ref


# the kernel's arithmetic per cell, counted from its body: phase derivatives
# 35, fluid properties 14, p* 26, equilibria at the carried u 218 (u.u 5, c.u
# 28, 26 x 7 + 3), non-equilibrium 27, second moments 117, their contraction
# with grad rho 15, forces 16, new velocity 58, u'.u' 5, 1/(tau + 1/2) and
# 1/rho 3, equilibria at u' 213, c.F 28, collision 26 x 8 + 3
FLOPS_PER_CELL = 35 + 14 + 26 + 218 + 27 + 117 + 15 + 16 + 58 + 5 + 3 + 213 + 28 + 211


def config_space(shape: tuple[int, int, int], dtype_bits: int):
    """Candidate PallasConfigs for `core.tpu_estimator` ranking: the kernel's
    own inputs (:func:`kernel.input_blocks`: each class's centre and the
    strips and corner pieces it streams from, phase's centre, strips and
    corners, vel's centre) plus the outputs g' and u', at every block of
    :data:`repro.kernels.lbm_d3q15.kernel.CANDIDATE_BLOCKS` that tiles the grid."""
    nx = shape[2]
    return tiling.block_space(
        "lbm27", shape, CANDIDATE_BLOCKS,
        lambda b: (*input_blocks(b, nx, dtype_bits), *output_blocks(b, nx)),
        dtype_bits, FLOPS_PER_CELL)


def select_block(
    shape: tuple[int, int, int], dtype=jnp.float32, *, machine: TPUMachine
) -> tuple[tuple[int, int], te.TPUEstimate]:
    cands = config_space(shape, jnp.dtype(dtype).itemsize * 8)
    cfg, est = tiling.pick(cands, machine, f"no candidate block tiles divide grid {shape}")
    return cfg.meta["block"], est


@entry_point(static_argnames=("params", "block", "phase_block", "interpret"))
def twophase_step(
    f: jnp.ndarray,
    g: jnp.ndarray,
    phase: jnp.ndarray,
    vel: jnp.ndarray,
    *,
    params: TwoPhaseParams = TwoPhaseParams(),
    block: tuple[int, int] | None = None,
    phase_block: tuple[int, int] | None = None,
    interpret: bool = False,
):
    """One coupled two-phase step -> (f', g', phi', u').

    The D3Q15 Allen-Cahn step (:func:`repro.kernels.lbm_d3q15.lbm_step`) moves
    the interface at the carried velocity, at ``phase_block`` or at its own
    pick, ``lbm_step.pick``; the D3Q27 hydrodynamic kernel then takes
    (g, phi', u) at ``block`` or at the estimator's pick, ``lbm_d3q27.pick``.
    One jit holds both; each call runs in the span ``twophase_step.call``.
    ``interpret=True`` runs on no chip, so nothing can be picked: it needs
    both blocks.  The outputs are defined outside a two-cell z/y shell.
    """
    if interpret and (block is None or phase_block is None):
        raise ValueError("interpret mode runs on no chip: pass block and phase_block")
    machine, vmem_limit = chip(interpret)
    # the D3Q15 step, traced into this jit with its pick span
    f_new, phase_new = traced_lbm_step(
        f, phase, vel, tau=params.tau_phase, width=params.sharpening_width,
        block=phase_block, interpret=interpret)
    if block is None:
        block, _ = timed_pick("lbm_d3q27", select_block, g.shape[1:], g.dtype, machine=machine)
    g_new, vel_new = hydro_step_pallas(g, phase_new, vel, params=params, block=block,
                                       interpret=interpret, vmem_limit_bytes=vmem_limit)
    return f_new, g_new, phase_new, vel_new


__all__ = ["twophase_step", "twophase_step_ref", "hydro_step_ref", "TwoPhaseParams",
           "equilibrium", "select_block", "config_space"]
