"""jit'd wrapper + estimator-guided block selection for the LBM kernel."""
from __future__ import annotations

import jax.numpy as jnp

from ...core import tpu_estimator as te
from ...core.machine import TPUMachine, device_machine
from ..entry import entry_point, timed_pick
from .kernel import lbm_step_pallas
from .ref import init_fields, lbm_step_ref

CANDIDATE_BLOCKS = ((4, 4), (8, 8), (8, 16), (16, 8), (16, 16), (32, 8), (8, 32))


def config_space(shape: tuple[int, int, int], dtype_bits: int):
    """Candidate PallasConfigs for the LBM step (pdf 3x3 + phase 3x3 + vel + outs)."""
    nz, ny, nx = shape
    nxp = nx + 2
    neighbors = [(dz, dy) for dz in (-1, 0, 1) for dy in (-1, 0, 1)]
    out = []
    for bz, by in CANDIDATE_BLOCKS:
        if nz % bz or ny % by:
            continue
        accesses = []
        for k, (dz, dy) in enumerate(neighbors):
            accesses.append(
                te.BlockAccess(
                    f"f{k}",
                    (15, bz, by, nxp),
                    (lambda dz=dz, dy=dy: (lambda i, j: (0, i + dz, j + dy, 0)))(),
                    dtype_bits,
                )
            )
        for k, (dz, dy) in enumerate(neighbors):
            accesses.append(
                te.BlockAccess(
                    f"p{k}",
                    (bz, by, nxp),
                    (lambda dz=dz, dy=dy: (lambda i, j: (i + dz, j + dy, 0)))(),
                    dtype_bits,
                )
            )
        accesses.append(
            te.BlockAccess("vel", (3, bz, by, nxp), lambda i, j: (0, i, j, 0), dtype_bits)
        )
        accesses.append(
            te.BlockAccess(
                "f_out", (15, bz, by, nx), lambda i, j: (0, i, j, 0), dtype_bits, True
            )
        )
        accesses.append(
            te.BlockAccess(
                "phase_out", (bz, by, nx), lambda i, j: (i, j, 0), dtype_bits, True
            )
        )
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
