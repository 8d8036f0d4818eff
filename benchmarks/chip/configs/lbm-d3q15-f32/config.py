"""lbm-d3q15-f32: one D3Q15 interface-tracking LB step through the program's
jitted entry point with the block left to its estimator.  The kernel clamps
its z/y tiles at the domain edge, so a one-cell shell in z and y is not
defined; x wraps, as in the reference.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp

SPEC = json.loads(Path(__file__).with_name("config.json").read_text())
DTYPE = jnp.dtype(SPEC["dtype"])
# D3Q15 weights: rest, six faces, eight corners
WEIGHTS = (2.0 / 9.0,) + (1.0 / 9.0,) * 6 + (1.0 / 72.0,) * 8


def make_domain(key, shape) -> dict:
    """The droplet phase field, its equilibrium pdfs and a random velocity
    from ``key``, made on the device."""
    nz, ny, nx = shape
    z, y, x = (jnp.arange(n, dtype=jnp.float32) for n in shape)
    dist = jnp.sqrt((z[:, None, None] - nz / 2) ** 2 + (y[None, :, None] - ny / 2) ** 2
                    + (x[None, None, :] - nx / 2) ** 2)
    phase = 0.5 * (1.0 - jnp.tanh(2.0 * (dist - min(shape) / 4.0) / 4.0))
    vel = SPEC["velocity_std"] * jax.random.normal(key, (3, *shape), jnp.float32)
    return {"f": (jnp.asarray(WEIGHTS)[:, None, None, None] * phase).astype(DTYPE),
            "phase": phase.astype(DTYPE), "vel": vel.astype(DTYPE)}


def step(domain: dict, block=None, interpret: bool = False) -> dict:
    from repro.kernels.lbm_d3q15 import lbm_step

    f, phase = lbm_step(domain["f"], domain["phase"], domain["vel"], tau=SPEC["tau"],
                        width=SPEC["width"], block=block, interpret=interpret)
    return {"f": f, "phase": phase, "vel": domain["vel"]}


def select(shape) -> tuple[int, int]:
    from repro.core.machine import device_machine
    from repro.kernels.lbm_d3q15 import select_block

    return select_block(tuple(shape), DTYPE, machine=device_machine())[0]


def candidates(shape) -> list[tuple[int, int]]:
    from repro.kernels.lbm_d3q15 import config_space

    return [c.meta["block"] for c in config_space(tuple(shape), DTYPE.itemsize * 8)]
