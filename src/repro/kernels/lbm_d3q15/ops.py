"""jit'd wrapper + estimator-guided block selection for the LBM kernel."""
from __future__ import annotations

import jax.numpy as jnp

from ...core import tpu_estimator as te
from ...core.machine import TPUMachine, device_machine
from ..entry import entry_point, timed_pick
from .kernel import input_blocks, lbm_step_pallas, output_blocks
from .ref import init_fields, lbm_step_ref

CANDIDATE_BLOCKS = ((4, 4), (8, 8), (8, 16), (16, 8), (16, 16), (32, 8), (8, 32))


def config_space(shape: tuple[int, int, int], dtype_bits: int):
    """Candidate PallasConfigs for `core.tpu_estimator` ranking: the kernel's
    own inputs (:func:`kernel.input_blocks`: nine neighbour tiles of f and of
    phase, vel's centre, all over whole x rows) plus the outputs f' and phi',
    at every block of :data:`CANDIDATE_BLOCKS` that tiles the grid."""
    nz, ny, nx = shape
    out = []
    for bz, by in CANDIDATE_BLOCKS:
        if nz % bz or ny % by:
            continue
        accesses = [
            te.BlockAccess(name=name, block_shape=block_shape, index_map=index_map,
                           dtype_bits=dtype_bits)
            for name, _, block_shape, index_map in input_blocks((bz, by), nx)
        ]
        accesses += [
            te.BlockAccess(name=name, block_shape=block_shape, index_map=index_map,
                           dtype_bits=dtype_bits, is_output=True)
            for name, block_shape, index_map in output_blocks((bz, by), nx)
        ]
        out.append(
            te.PallasConfig(
                name=f"lbm_bz{bz}_by{by}",
                grid=(nz // bz, ny // by),
                accesses=tuple(accesses),
                flops_per_step=350.0 * bz * by * nx,
                is_matmul=False,
                meta={"block": (bz, by)},
            )
        )
    return out


def select_block(
    shape: tuple[int, int, int], dtype=jnp.float32, *, machine: TPUMachine
) -> tuple[tuple[int, int], te.TPUEstimate]:
    bits = jnp.dtype(dtype).itemsize * 8
    cands = config_space(shape, bits)
    if not cands:
        raise ValueError(f"no candidate block tiles divide grid {shape}")
    cfg, est = te.select_config(cands, machine)
    return cfg.meta["block"], est


@entry_point(static_argnames=("tau", "width", "block", "interpret"))
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
    machine = None if interpret else device_machine()
    if block is None:
        block, _ = timed_pick("lbm_step", select_block, f.shape[1:], f.dtype, machine=machine)
    return lbm_step_pallas(
        f, phase, vel, tau=tau, width=width, block=block, interpret=interpret,
        vmem_limit_bytes=None if machine is None else machine.vmem_usable,
    )


__all__ = ["lbm_step", "lbm_step_ref", "init_fields", "select_block", "config_space"]
