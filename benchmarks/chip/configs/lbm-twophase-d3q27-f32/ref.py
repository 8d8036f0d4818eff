"""Plain reference of one coupled two-phase lattice-Boltzmann step
(arXiv:2107.01143 §IV.D; the conservative phase-field model of Fakhari et al.
2017 and Mitchell et al. 2018, as lbmpy generates it, arXiv:2012.06144),
written from its definition and sharing no code with the program.

Per cell p, from the D3Q15 pdfs f, the D3Q27 pdfs g, the phase phi and the
velocity u of the previous step (lattice units, c_s^2 = 1/3):

* the interface: f_q(p) <- f_q(p - c_q); phi' = sum_q f_q; the gradient of
  the input phi by 7-point central differences; the sharpening force
  F_q = w_q (4 phi' (1 - phi') / xi) c_q.n, n the unit gradient;
  f'_q = f_q - (f_q - w_q phi' (1 + 3 c_q.u) + F_q / 2) / tau_phi + F_q;
* the flow: g_a(p) <- g_a(p - c_a); grad phi' = 3 sum_a w_a c_a phi'(p + c_a),
  lap phi' = 6 sum_a w_a (phi'(p + c_a) - phi'(p)); rho and tau interpolated
  linearly in phi'; the chemical potential
  mu = 4 beta phi'(phi' - 1)(phi' - 1/2) - kappa lap phi' (beta = 12 sigma/xi,
  kappa = 3 sigma xi / 2); p* = sum_a g_a; the equilibrium
  w_a (p* + 3 c.u + 4.5 (c.u)^2 - 1.5 u.u) at the carried u; forces
  mu grad phi' - (p*/3) grad rho - tau [sum_a c c (g_a - eq_a)] . grad rho;
  u' = sum_a c_a g_a + F / (2 rho); F_a = 3 w_a c_a.F / rho;
  g'_a = g_a - (g_a - eq_a(u') + F_a / 2) / (tau + 1/2) + F_a.

The field is periodic in all three axes.  Parameters are the
configuration's (``config.json``).
"""
from __future__ import annotations

import json
from pathlib import Path

import jax.numpy as jnp

SPEC = json.loads(Path(__file__).with_name("config.json").read_text())

# D3Q15 (cx, cy, cz): rest, the six faces, the eight corners
DIRS15 = ((0, 0, 0), (1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0), (0, 0, 1), (0, 0, -1),
          (1, 1, 1), (1, 1, -1), (1, -1, 1), (1, -1, -1),
          (-1, 1, 1), (-1, 1, -1), (-1, -1, 1), (-1, -1, -1))
WEIGHTS15 = (2.0 / 9.0,) + (1.0 / 9.0,) * 6 + (1.0 / 72.0,) * 8
# D3Q27 (cx, cy, cz) in the program's component order: cz, cy, cx each over (0, 1, -1)
DIRS27 = tuple((cx, cy, cz) for cz in (0, 1, -1) for cy in (0, 1, -1) for cx in (0, 1, -1))
WEIGHTS27 = tuple((8.0 / 27.0, 2.0 / 27.0, 1.0 / 54.0, 1.0 / 216.0)[cx * cx + cy * cy + cz * cz]
                  for cx, cy, cz in DIRS27)


def _roll(a, c, sign):
    """a(p - sign c) at p: a roll by sign * c along (z, y, x)."""
    cx, cy, cz = c
    return jnp.roll(a, (sign * cz, sign * cy, sign * cx), axis=(0, 1, 2))


def interface(f, phase, vel):
    tau, width = SPEC["tau_phase"], SPEC["width"]
    ux, uy, uz = vel[0], vel[1], vel[2]
    pulled = [_roll(f[q], c, 1) for q, c in enumerate(DIRS15)]
    phi = pulled[0]
    for q in range(1, 15):
        phi = phi + pulled[q]
    gx = 0.5 * (jnp.roll(phase, -1, 2) - jnp.roll(phase, 1, 2))
    gy = 0.5 * (jnp.roll(phase, -1, 1) - jnp.roll(phase, 1, 1))
    gz = 0.5 * (jnp.roll(phase, -1, 0) - jnp.roll(phase, 1, 0))
    inv = 1.0 / jnp.sqrt(gx * gx + gy * gy + gz * gz + 1e-12)
    sharp = 4.0 * phi * (1.0 - phi) / width
    out = []
    for q, (cx, cy, cz) in enumerate(DIRS15):
        w = WEIGHTS15[q]
        heq = w * phi * (1.0 + 3.0 * (cx * ux + cy * uy + cz * uz))
        force = w * sharp * (cx * gx * inv + cy * gy * inv + cz * gz * inv)
        out.append(pulled[q] - (pulled[q] - (heq - 0.5 * force)) / tau + force)
    return jnp.stack(out), phi


def flow(g, phi, vel):
    s = SPEC
    beta, kappa = 12.0 * s["sigma"] / s["width"], 1.5 * s["sigma"] * s["width"]
    pulled = [_roll(g[a], c, 1) for a, c in enumerate(DIRS27)]
    near = [_roll(phi, c, -1) for c in DIRS27]
    grad = []
    for i in range(3):
        acc = jnp.zeros_like(phi)
        for a, c in enumerate(DIRS27):
            acc = acc + WEIGHTS27[a] * c[i] * near[a]
        grad.append(3.0 * acc)
    lap = jnp.zeros_like(phi)
    for a in range(27):
        lap = lap + WEIGHTS27[a] * (near[a] - phi)
    lap = 6.0 * lap
    rho = s["rho_light"] + phi * (s["rho_heavy"] - s["rho_light"])
    tau = s["tau_light"] + phi * (s["tau_heavy"] - s["tau_light"])
    mu = 4.0 * beta * phi * (phi - 1.0) * (phi - 0.5) - kappa * lap
    drho = [(s["rho_heavy"] - s["rho_light"]) * d for d in grad]
    pstar = jnp.zeros_like(phi)
    for a in range(27):
        pstar = pstar + pulled[a]

    def eq(a, u):
        c = DIRS27[a]
        cu = c[0] * u[0] + c[1] * u[1] + c[2] * u[2]
        return WEIGHTS27[a] * (pstar + 3.0 * cu + 4.5 * cu * cu
                               - 1.5 * (u[0] * u[0] + u[1] * u[1] + u[2] * u[2]))

    u = [vel[0], vel[1], vel[2]]
    force = []
    for i in range(3):
        visc = jnp.zeros_like(phi)
        for j in range(3):
            moment = jnp.zeros_like(phi)
            for a, c in enumerate(DIRS27):
                moment = moment + c[i] * c[j] * (pulled[a] - eq(a, u))
            visc = visc + moment * drho[j]
        force.append(mu * grad[i] - pstar / 3.0 * drho[i] - tau * visc)
    new_u = []
    for i in range(3):
        mom = jnp.zeros_like(phi)
        for a, c in enumerate(DIRS27):
            mom = mom + c[i] * pulled[a]
        new_u.append(mom + force[i] / (2.0 * rho))
    out = []
    for a, c in enumerate(DIRS27):
        fa = 3.0 * WEIGHTS27[a] * (c[0] * force[0] + c[1] * force[1] + c[2] * force[2]) / rho
        out.append(pulled[a] - (pulled[a] - (eq(a, new_u) - 0.5 * fa)) / (tau + 0.5) + fa)
    return jnp.stack(out), jnp.stack(new_u)


def step(domain: dict, dtype=jnp.float32) -> dict:
    """One coupled step over ``domain`` = f (15, nz, ny, nx), g (27, ...),
    phase and vel (3, ...), computed and returned in ``dtype``."""
    f, g, phase, vel = (domain[k].astype(dtype) for k in ("f", "g", "phase", "vel"))
    f_new, phi = interface(f, phase, vel)
    g_new, vel_new = flow(g, phi, vel)
    return {"f": f_new, "g": g_new, "phase": phi, "vel": vel_new}
