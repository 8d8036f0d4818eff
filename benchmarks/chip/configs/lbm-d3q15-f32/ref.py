"""Plain reference of one D3Q15 conservative Allen-Cahn interface-tracking
lattice-Boltzmann step (arXiv:2107.01143, second application), written from
its definition and sharing no code with the program.

Per cell p: pull-stream f_q(p) <- f_q(p - c_q); the new phase is sum_q f_q;
the phase gradient is the 7-point central difference of the input phase; the
15 pdfs relax with BGK towards w_q phi (1 + 3 c_q.u) and gain the sharpening
force w_q (4 phi (1 - phi) / width) c_q.n, n the unit gradient.  The field is
periodic in all three axes.
"""
from __future__ import annotations

import jax.numpy as jnp

TAU, WIDTH = 0.8, 4.0

# (cx, cy, cz): rest, the six faces, the eight corners
DIRS = ((0, 0, 0), (1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0), (0, 0, 1), (0, 0, -1),
        (1, 1, 1), (1, 1, -1), (1, -1, 1), (1, -1, -1),
        (-1, 1, 1), (-1, 1, -1), (-1, -1, 1), (-1, -1, -1))
WEIGHTS = (2.0 / 9.0,) + (1.0 / 9.0,) * 6 + (1.0 / 72.0,) * 8


def step(domain: dict, dtype=jnp.float32) -> dict:
    """One step over ``domain`` = f (15, nz, ny, nx), phase and vel (3, ...);
    f and phase are computed and returned in ``dtype``, vel is carried."""
    f, phase, vel = (domain[k].astype(dtype) for k in ("f", "phase", "vel"))
    ux, uy, uz = vel[0], vel[1], vel[2]
    pulled = [jnp.roll(f[q], (cz, cy, cx), axis=(0, 1, 2)) for q, (cx, cy, cz) in enumerate(DIRS)]
    phi = pulled[0]
    for q in range(1, 15):
        phi = phi + pulled[q]
    gx = 0.5 * (jnp.roll(phase, -1, 2) - jnp.roll(phase, 1, 2))
    gy = 0.5 * (jnp.roll(phase, -1, 1) - jnp.roll(phase, 1, 1))
    gz = 0.5 * (jnp.roll(phase, -1, 0) - jnp.roll(phase, 1, 0))
    inv = 1.0 / jnp.sqrt(gx * gx + gy * gy + gz * gz + 1e-12)
    sharp = 4.0 * phi * (1.0 - phi) / WIDTH
    out = []
    for q, (cx, cy, cz) in enumerate(DIRS):
        w = WEIGHTS[q]
        heq = w * phi * (1.0 + 3.0 * (cx * ux + cy * uy + cz * uz))
        force = w * sharp * (cx * gx * inv + cy * gy * inv + cz * gz * inv)
        out.append(pulled[q] - (pulled[q] - heq) / TAU + force)
    return {"f": jnp.stack(out), "phase": phi, "vel": domain["vel"]}
