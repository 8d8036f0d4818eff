"""jit'd two-phase step + estimator-guided block selection for the D3Q27
hydrodynamic kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...core import tpu_estimator as te
from ...core.machine import TPUMachine
from ...obs import metrics
from .. import tiling
from ..entry import chip, entry_point, timed_pick
from ..lbm_d3q15 import kernel as lbm15
from ..lbm_d3q15.kernel import CANDIDATE_BLOCKS
from ..lbm_d3q15.ops import select_block as lbm15_select
from ..lbm_d3q15.ops import traced_lbm_step
from .kernel import hydro_step_pallas, input_blocks, output_blocks
from .ref import TwoPhaseParams, equilibrium, hydro_step_ref, twophase_step_ref


# the kernel's arithmetic per cell, counted from its body: phase derivatives
# 35, fluid properties 14, p* 26, equilibria at the carried u 218 (u.u 5, c.u
# 28, 26 x 7 + 3), non-equilibrium 27, second moments 117, their contraction
# with grad rho 15, forces 16, new velocity 58, u'.u' 5, 1/(tau + 1/2) and
# 1/rho 3, equilibria at u' 213, c.F 28, collision 26 x 8 + 3
FLOPS_PER_CELL = 35 + 14 + 26 + 218 + 27 + 117 + 15 + 16 + 58 + 5 + 3 + 213 + 28 + 211


def config_space(shape: tuple[int, int, int], dtype_bits: int, mesh=None):
    """Candidate PallasConfigs for `core.tpu_estimator` ranking: the kernel's
    own inputs (:func:`kernel.input_blocks`: each class's centre and the
    strips and corner pieces it streams from, phase's centre, strips and
    corners, vel's centre) plus the outputs g' and u', at every block of
    :data:`repro.kernels.lbm_d3q15.kernel.CANDIDATE_BLOCKS` that tiles the
    grid; with a ``mesh``, one chip's block and its ghosts, as
    :func:`repro.kernels.lbm_d3q15.config_space`."""
    nx = shape[2]
    return tiling.block_space(
        "lbm27", tiling.local_shape(shape, mesh), CANDIDATE_BLOCKS,
        lambda b: (*tiling.with_ghosts(input_blocks(b, nx, dtype_bits), mesh),
                   *output_blocks(b, nx)),
        dtype_bits, FLOPS_PER_CELL)


def select_block(
    shape: tuple[int, int, int], dtype=jnp.float32, *, machine: TPUMachine, mesh=None
) -> tuple[tuple[int, int], te.TPUEstimate]:
    cands = config_space(shape, jnp.dtype(dtype).itemsize * 8, mesh)
    cfg, est = tiling.pick(cands, machine, f"no candidate block tiles divide grid {shape}")
    return cfg.meta["block"], est


@entry_point(static_argnames=("params", "block", "phase_block", "interpret", "mesh"))
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
    mesh=None,
):
    """One coupled two-phase step -> (f', g', phi', u').

    The D3Q15 Allen-Cahn step (:func:`repro.kernels.lbm_d3q15.lbm_step`) moves
    the interface at the carried velocity, at ``phase_block`` or at its own
    pick, ``lbm_step.pick``; the D3Q27 hydrodynamic kernel then takes
    (g, phi', u) at ``block`` or at the estimator's pick, ``lbm_d3q27.pick``.
    One jit holds both; each call runs in the span ``twophase_step.call``.
    ``interpret=True`` runs on no chip, so nothing can be picked: it needs
    both blocks.  The outputs are defined outside a two-cell z/y shell.

    With a ``mesh`` (a 1D mesh over :data:`repro.kernels.tiling.AXIS`), the
    fields are split in y over its chips, a chain of blocks, and so are the
    outputs: each chip runs both kernels on its block, picked at the block's
    shape, after receiving from its neighbours the rows next to its y edges
    that the kernels read (:func:`_chain_step`).
    """
    if interpret and (block is None or phase_block is None):
        raise ValueError("interpret mode runs on no chip: pass block and phase_block")
    machine, vmem_limit = chip(interpret)
    if mesh is not None:
        return _chain_step(f, g, phase, vel, params, block, phase_block, interpret, mesh,
                           machine, vmem_limit)
    # the D3Q15 step, traced into this jit with its pick span
    f_new, phase_new = traced_lbm_step(
        f, phase, vel, tau=params.tau_phase, width=params.sharpening_width,
        block=phase_block, interpret=interpret)
    if block is None:
        block, _ = timed_pick("lbm_d3q27", select_block, g.shape[1:], g.dtype, machine=machine)
    g_new, vel_new = hydro_step_pallas(g, phase_new, vel, params=params, block=block,
                                       interpret=interpret, vmem_limit_bytes=vmem_limit)
    return f_new, g_new, phase_new, vel_new


def _chain_step(f, g, phase, vel, params, block, phase_block, interpret, mesh, machine,
                vmem_limit):
    """:func:`twophase_step` over a chain of chips: a ``shard_map`` over y in
    which each chip receives the rows of f, phase and g next to its y edges
    that the D3Q15 kernel and the D3Q27 kernel's g strips read, runs the
    D3Q15 kernel, receives the same of phi' for the D3Q27 kernel's phase
    strips, and runs that.  The exchanges run in the scope
    ``halo_exchange``; the bytes one chip with two neighbours sends a step go
    to the gauge ``lattice.halo_bytes{entry=twophase_step}``."""
    if phase_block is None:
        phase_block, _ = timed_pick("lbm_step", lbm15_select, f.shape[1:], f.dtype,
                                    machine=machine, mesh=mesh)
    if block is None:
        block, _ = timed_pick("lbm_d3q27", select_block, g.shape[1:], g.dtype,
                              machine=machine, mesh=mesh)
    nx, bits = g.shape[-1], g.dtype.itemsize * 8
    rows15 = tiling.ghost_rows(lbm15.input_blocks(phase_block, nx, bits))
    rows27 = tiling.ghost_rows(input_blocks(block, nx, bits))

    def local(f, g, phase, vel):
        with jax.named_scope("halo_exchange"):
            ghosts15 = tiling.exchange({"f": f, "phase": phase}, rows15)
            ghosts_g = tiling.exchange({"g": g}, rows27)
        f_new, phase_new = lbm15.lbm_step_pallas(
            f, phase, vel, tau=params.tau_phase, width=params.sharpening_width, block=phase_block,
            vmem_limit_bytes=vmem_limit, interpret=interpret, ghosts=ghosts15)
        with jax.named_scope("halo_exchange"):
            ghosts_phase = tiling.exchange({"phase": phase_new}, rows27)
        g_new, vel_new = hydro_step_pallas(g, phase_new, vel, params=params, block=block,
                                           vmem_limit_bytes=vmem_limit, interpret=interpret,
                                           ghosts={**ghosts_g, **ghosts_phase})
        metrics.gauge("lattice.halo_bytes", entry="twophase_step").set(
            sum(map(tiling.sent_bytes, (ghosts15, ghosts_g, ghosts_phase))))
        return f_new, g_new, phase_new, vel_new

    def split(a):
        return P(*(None,) * (a.ndim - 2), tiling.AXIS, None)

    args = (f, g, phase, vel)
    return jax.shard_map(local, mesh=mesh, in_specs=tuple(map(split, args)),
                         out_specs=tuple(map(split, args)), check_vma=False)(*args)


__all__ = ["twophase_step", "twophase_step_ref", "hydro_step_ref", "TwoPhaseParams",
           "equilibrium", "select_block", "config_space"]
