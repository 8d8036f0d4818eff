"""lbm-twophase-d3q27-f32: one coupled two-phase LB step through the
program's jitted entry point: the D3Q15 Allen-Cahn interface step, then the
D3Q27 velocity-based hydrodynamic kernel on the new phase field, each block
left to the estimator.  The kernels clamp their z/y halo at the domain edge:
the D3Q15 step leaves a one-cell shell undefined and the D3Q27 kernel reads
the new phase one cell further, so a two-cell shell in z and y is not
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
WEIGHTS15 = (2.0 / 9.0,) + (1.0 / 9.0,) * 6 + (1.0 / 72.0,) * 8
PARAMS = ("tau_phase", "width", "rho_heavy", "rho_light", "tau_heavy", "tau_light", "sigma")


def _equilibrium27(vel):
    """The 27 D3Q27 pdfs at equilibrium for p* = 0 at ``vel`` (3, ...), in
    the program's component order: (cz, cy, cx) each over (0, 1, -1)."""
    dirs = [(cx, cy, cz) for cz in (0, 1, -1) for cy in (0, 1, -1) for cx in (0, 1, -1)]
    c = jnp.asarray(dirs, jnp.float32).reshape(27, 3, 1, 1, 1)
    w = jnp.asarray([(8.0 / 27.0, 2.0 / 27.0, 1.0 / 54.0, 1.0 / 216.0)[sum(v * v for v in d)]
                     for d in dirs], jnp.float32).reshape(27, 1, 1, 1)
    ux, uy, uz = vel[0], vel[1], vel[2]
    uu = ux * ux + uy * uy + uz * uz
    cu = c[:, 0] * ux + c[:, 1] * uy + c[:, 2] * uz
    return w * (3.0 * cu + 4.5 * cu * cu - 1.5 * uu)


def make_domain(key, shape) -> dict:
    """The droplet phase field of ``lbm-d3q15-f32``, its D3Q15 pdfs, a random
    velocity from ``key`` and the D3Q27 pdfs at equilibrium for p* = 0 at
    that velocity, made on the device."""
    nz, ny, nx = shape
    z, y, x = (jnp.arange(n, dtype=jnp.float32) for n in shape)
    dist = jnp.sqrt((z[:, None, None] - nz / 2) ** 2 + (y[None, :, None] - ny / 2) ** 2
                    + (x[None, None, :] - nx / 2) ** 2)
    phase = 0.5 * (1.0 - jnp.tanh(2.0 * (dist - min(shape) / 4.0) / SPEC["width"]))
    vel = SPEC["velocity_std"] * jax.random.normal(key, (3, *shape), jnp.float32)
    return {"f": (jnp.asarray(WEIGHTS15)[:, None, None, None] * phase).astype(DTYPE),
            "g": _equilibrium27(vel).astype(DTYPE),
            "phase": phase.astype(DTYPE), "vel": vel.astype(DTYPE)}


def step(domain: dict, block=None, interpret: bool = False) -> dict:
    """One step at the D3Q27 ``block`` (the estimator's pick when ``None``);
    the D3Q15 block is the estimator's, or ``block`` in interpret mode, which
    has no chip to pick for."""
    from repro.kernels.lbm_d3q27 import TwoPhaseParams, twophase_step

    f, g, phase, vel = twophase_step(
        domain["f"], domain["g"], domain["phase"], domain["vel"],
        params=TwoPhaseParams(**{k: SPEC[k] for k in PARAMS}),
        block=block, phase_block=block if interpret else None, interpret=interpret)
    return {"f": f, "g": g, "phase": phase, "vel": vel}


def select(shape) -> tuple[int, int]:
    from repro.core.machine import device_machine
    from repro.kernels.lbm_d3q27 import select_block

    return select_block(tuple(shape), DTYPE, machine=device_machine())[0]


def candidates(shape) -> list[tuple[int, int]]:
    from repro.kernels.lbm_d3q27 import config_space

    return [c.meta["block"] for c in config_space(tuple(shape), DTYPE.itemsize * 8)]
