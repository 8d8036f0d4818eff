"""lbm-twophase-d3q27-f32-y4: the coupled two-phase step of
``lbm-twophase-d3q27-f32`` (the same droplet, physics and parameters) on a
domain split in y over a chain of chips, each holding a block of whole z
planes, through the program's jitted entry point with ``mesh=``.  Each chip
receives from its neighbours the rows next to its seams that the kernels
read, so the domain's outputs are those of one chip's step on the whole
domain: a two-cell shell in z and y, at the domain's own edges, is not
defined, and x wraps, as in the reference.  The blocks are picked at one
chip's block.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

HERE = Path(__file__).resolve().parent
SPEC = json.loads((HERE / "config.json").read_text())
DTYPE = jnp.dtype(SPEC["dtype"])
AXIS = "y"


def _one_chip_config():
    """The one-chip configuration's ``config.py``, whose droplet and
    parameters this one shares."""
    path = HERE.parent / "lbm-twophase-d3q27-f32" / "config.py"
    spec = importlib.util.spec_from_file_location("config_lbm_twophase_d3q27_f32_of_y4", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ONE_CHIP = _one_chip_config()


@functools.cache
def mesh():
    """The chain of ``chips`` chips along y, in the order ``jax.make_mesh``
    gives; each chip's coordinates are logged once, to show which chips are
    neighbours in the chain."""
    m = jax.make_mesh((SPEC["chips"],), (AXIS,), axis_types=(AxisType.Auto,))
    coords = [getattr(d, "coords", d.id) for d in m.devices.flat]
    print(f"chain of chips along {AXIS}: device coords {coords}", file=sys.stderr, flush=True)
    return m


def _split(a):
    return NamedSharding(mesh(), P(*(None,) * (a.ndim - 2), AXIS, None))


def make_domain(key, shape) -> dict:
    """The one-chip configuration's droplet at the whole domain's ``shape``,
    each field split in y over the chain, so that no chip holds the whole
    state: called inside a jit, each chip makes its own block."""
    return {k: jax.lax.with_sharding_constraint(v, _split(v))
            for k, v in ONE_CHIP.make_domain(key, shape).items()}


def step(domain: dict, block=None, interpret: bool = False) -> dict:
    """One step over the chain at the D3Q27 ``block`` (the estimator's pick
    when ``None``); the D3Q15 block is the estimator's, or ``block`` in
    interpret mode, which has no chip to pick for."""
    from repro.kernels.lbm_d3q27 import TwoPhaseParams, twophase_step

    f, g, phase, vel = twophase_step(
        domain["f"], domain["g"], domain["phase"], domain["vel"],
        params=TwoPhaseParams(**{k: SPEC[k] for k in ONE_CHIP.PARAMS}),
        block=block, phase_block=block if interpret else None, interpret=interpret, mesh=mesh())
    return {"f": f, "g": g, "phase": phase, "vel": vel}


def select(shape) -> tuple[int, int]:
    """The D3Q27 pick the entry makes: at one chip's block of ``shape``."""
    from repro.core.machine import device_machine
    from repro.kernels.lbm_d3q27 import select_block

    return select_block(tuple(shape), DTYPE, machine=device_machine(), mesh=mesh())[0]


def candidates(shape) -> list[tuple[int, int]]:
    from repro.kernels.lbm_d3q27 import config_space

    return [c.meta["block"] for c in config_space(tuple(shape), DTYPE.itemsize * 8, mesh())]
