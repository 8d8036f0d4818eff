"""stencil25-r4-f32: one time step of the range-four 3D25pt star stencil,
through the program's jitted entry point with the block left to its
estimator.  The kernel clamps its z/y tiles at the domain edge, so a shell of
``halo`` cells in z and y is not defined; x is edge-padded as in the reference.
"""
from __future__ import annotations

import json
from pathlib import Path

import jax
import jax.numpy as jnp

SPEC = json.loads(Path(__file__).with_name("config.json").read_text())
R = SPEC["r"]
DTYPE = jnp.dtype(SPEC["dtype"])


def make_domain(key, shape) -> dict:
    """A normal field from ``key``, made on the device."""
    return {"x": jax.random.normal(key, shape, DTYPE)}


def step(domain: dict, block=None, interpret: bool = False) -> dict:
    from repro.kernels.stencil25 import stencil25

    return {"x": stencil25(domain["x"], r=R, block=block, interpret=interpret)}


def select(shape) -> tuple[int, int]:
    from repro.core.machine import device_machine
    from repro.kernels.stencil25 import select_block

    return select_block(tuple(shape), R, DTYPE, machine=device_machine())[0]


def candidates(shape) -> list[tuple[int, int]]:
    from repro.kernels.stencil25 import config_space

    return [c.meta["block"] for c in config_space(tuple(shape), R, DTYPE.itemsize * 8)]
