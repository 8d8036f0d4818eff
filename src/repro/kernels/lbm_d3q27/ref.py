"""Pure-jnp oracle: the D3Q27 velocity-based hydrodynamic LB step of the
conservative phase-field two-phase solver, and the coupled two-phase step.

The paper's second application (arXiv:2107.01143 §IV.D) is "a complex two
phase fluid solver based on the Lattice Boltzmann Method": the conservative
phase-field LBM of Fakhari et al. 2017 and Mitchell et al. 2018, as lbmpy
generates it (Holzer et al., arXiv:2012.06144).  Each time step runs a D3Q15
Allen-Cahn lattice for the interface (:mod:`repro.kernels.lbm_d3q15`), then a
D3Q27 velocity-based hydrodynamic lattice that feels the forces built from
the new phase field.  Lattice units, c_s^2 = 1/3.  Per cell p, from the
pdfs g, the new phase phi and the velocity u of the previous step:

1. pull-stream: gh_a(p) = g_a(p - c_a);
2. isotropic derivatives of phi on the D3Q27 weights:
   grad phi = 3 sum_a w_a c_a phi(p + c_a),
   lap phi = 6 sum_a w_a (phi(p + c_a) - phi(p));
3. rho = rho_L + phi (rho_H - rho_L), so grad rho = (rho_H - rho_L) grad phi;
   tau = tau_L + phi (tau_H - tau_L);
   mu = 4 beta phi (phi - 1)(phi - 1/2) - kappa lap phi, with
   beta = 12 sigma / xi and kappa = 3 sigma xi / 2;
4. p* = sum_a gh_a; with Gamma_a(u) = w_a [1 + 3 c.u + 4.5 (c.u)^2 - 1.5 u.u],
   geq_a = p* w_a + Gamma_a(u) - w_a at the carried u;
5. F_s = mu grad phi; F_p = -(p*/3) grad rho;
   F_mu,i = -tau sum_j [sum_a c_ai c_aj (gh_a - geq_a)] d_j rho;
   F = F_s + F_p + F_mu;
6. u' = sum_a c_a gh_a + F / (2 rho);
7. F_a = 3 w_a (c_a.F) / rho, gbar_a = p* w_a + Gamma_a(u') - w_a - F_a / 2,
   g'_a = gh_a - (gh_a - gbar_a) / (tau + 1/2) + F_a.

The D3Q15 step (:func:`repro.kernels.lbm_d3q15.ref.lbm_step_ref`) adds its
sharpening term F^phi_a = w_a 4 phi (1 - phi) / width (c_a.n) in full and
relaxes towards an unshifted equilibrium.  The sources relax towards
h^eq_a - F^phi_a / 2 and add F^phi_a with width = xi, which is the same step
with the term scaled by 1 - 1/(2 tau_phi): the coupled step passes the D3Q15
step ``width`` = xi / (1 - 1/(2 tau_phi)) (:attr:`TwoPhaseParams.sharpening_width`).
Unscaled, the term sharpens the interface to below half of xi and phi
leaves [0, 1] by about 1 %, so rho_L + phi (rho_H - rho_L) falls below 0 at
a density ratio of 1000.

Departures from the sources, each deliberate:

* step 4 takes the viscous force's equilibrium at the velocity carried from
  the previous step, not at u', so the step stays explicit (the sources'
  u' itself depends on F_mu);
* p* w_a + Gamma_a(u) - w_a is evaluated as
  w_a (p* + 3 c.u + 4.5 (c.u)^2 - 1.5 u.u), the same quantity with the
  constant terms cancelled;
* no gravity or body force; the field is periodic in all three axes (the
  Pallas kernel clamps its z/y halo at the domain edge instead, so
  comparisons leave out a shell).

The oracle sums every quantity term by term in :data:`DIRS` order, under
``jax.default_matmul_precision("highest")``.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..lbm_d3q15.ref import lbm_step_ref

# (cx, cy, cz) of the 27 components, grouped by (cz, cy) into nine classes of
# three (class k = 3 iz + iy holds components 3k, 3k + 1, 3k + 2), each class
# ordered cx = 0, 1, -1; component 0 is the rest velocity
STEPS = (0, 1, -1)
DIRS: tuple[tuple[int, int, int], ...] = tuple(
    (cx, cy, cz) for cz in STEPS for cy in STEPS for cx in STEPS
)
# the D3Q27 weights by |c|^2: 8/27, 2/27, 1/54, 1/216
WEIGHTS: tuple[float, ...] = tuple(
    {0: 8.0 / 27.0, 1: 2.0 / 27.0, 2: 1.0 / 54.0, 3: 1.0 / 216.0}[cx * cx + cy * cy + cz * cz]
    for cx, cy, cz in DIRS
)


@dataclass(frozen=True)
class TwoPhaseParams:
    """Physical parameters of the two-phase step, in lattice units.

    ``tau_phase`` and ``width`` (the interface width xi) drive the D3Q15
    Allen-Cahn lattice; the rest the D3Q27 hydrodynamic lattice.  The
    defaults are the chip benchmark's ``lbm-twophase-d3q27-f32``: the
    sources' high density ratio, 1000.
    """

    tau_phase: float = 0.8
    width: float = 4.0
    rho_heavy: float = 1.0
    rho_light: float = 0.001
    tau_heavy: float = 0.5
    tau_light: float = 0.8
    sigma: float = 1e-4

    @property
    def sharpening_width(self) -> float:
        """The D3Q15 step's ``width`` that gives its sharpening term the
        sources' strength at interface width xi: xi / (1 - 1/(2 tau_phi))."""
        return self.width / (1.0 - 0.5 / self.tau_phase)

    @property
    def beta(self) -> float:
        return 12.0 * self.sigma / self.width

    @property
    def kappa(self) -> float:
        return 1.5 * self.sigma * self.width


def hydro_step_ref(g: jnp.ndarray, phase: jnp.ndarray, vel: jnp.ndarray,
                   params: TwoPhaseParams = TwoPhaseParams()):
    """One D3Q27 hydrodynamic step over g (27, nz, ny, nx), the new phase
    (nz, ny, nx) and the carried velocity (3, nz, ny, nx) (ux, uy, uz);
    returns (g', u')."""
    p = params
    with jax.default_matmul_precision("highest"):
        pulled = [jnp.roll(g[a], (cz, cy, cx), axis=(0, 1, 2)) for a, (cx, cy, cz) in enumerate(DIRS)]
        # phi(p + c): a roll by -c brings the value at p + c to p
        near = [jnp.roll(phase, (-cz, -cy, -cx), axis=(0, 1, 2)) for cx, cy, cz in DIRS]
        grad = []
        for axis in range(3):
            acc = jnp.zeros_like(phase)
            for a, c in enumerate(DIRS):
                acc = acc + WEIGHTS[a] * c[axis] * near[a]
            grad.append(3.0 * acc)
        lap = jnp.zeros_like(phase)
        for a in range(27):
            lap = lap + WEIGHTS[a] * (near[a] - phase)
        lap = 6.0 * lap

        drho_dphi = p.rho_heavy - p.rho_light
        rho = p.rho_light + phase * drho_dphi
        tau = p.tau_light + phase * (p.tau_heavy - p.tau_light)
        mu = 4.0 * p.beta * phase * (phase - 1.0) * (phase - 0.5) - p.kappa * lap
        drho = [drho_dphi * d for d in grad]

        pstar = jnp.zeros_like(phase)
        for a in range(27):
            pstar = pstar + pulled[a]
        u = [vel[0], vel[1], vel[2]]

        def eq(a, v):
            c = DIRS[a]
            cu = c[0] * v[0] + c[1] * v[1] + c[2] * v[2]
            uu = v[0] * v[0] + v[1] * v[1] + v[2] * v[2]
            return WEIGHTS[a] * (pstar + 3.0 * cu + 4.5 * cu * cu - 1.5 * uu)

        neq = [pulled[a] - eq(a, u) for a in range(27)]
        force = []
        for i in range(3):
            visc = jnp.zeros_like(phase)
            for j in range(3):
                moment = jnp.zeros_like(phase)
                for a, c in enumerate(DIRS):
                    moment = moment + c[i] * c[j] * neq[a]
                visc = visc + moment * drho[j]
            force.append(mu * grad[i] - pstar / 3.0 * drho[i] - tau * visc)

        new_u = []
        for i in range(3):
            mom = jnp.zeros_like(phase)
            for a, c in enumerate(DIRS):
                mom = mom + c[i] * pulled[a]
            new_u.append(mom + force[i] / (2.0 * rho))

        omega = 1.0 / (tau + 0.5)
        out = []
        for a, c in enumerate(DIRS):
            cf = c[0] * force[0] + c[1] * force[1] + c[2] * force[2]
            fa = 3.0 * WEIGHTS[a] * cf / rho
            gbar = eq(a, new_u) - 0.5 * fa
            out.append(pulled[a] - omega * (pulled[a] - gbar) + fa)
        return jnp.stack(out), jnp.stack(new_u)


def twophase_step_ref(f, g, phase, vel, params: TwoPhaseParams = TwoPhaseParams()):
    """One coupled two-phase step: the D3Q15 Allen-Cahn step on (f, phase)
    at the carried velocity, its sharpening at the sources' strength, then
    the D3Q27 hydrodynamic step on (g, phi') at that velocity; returns
    (f', g', phi', u')."""
    f_new, phase_new = lbm_step_ref(f, phase, vel, tau=params.tau_phase,
                                    width=params.sharpening_width)
    g_new, vel_new = hydro_step_ref(g, phase_new, vel, params)
    return f_new, g_new, phase_new, vel_new


def equilibrium(vel: jnp.ndarray, pstar: float = 0.0) -> jnp.ndarray:
    """g at equilibrium for pressure ``pstar`` at velocity ``vel`` (3, ...)."""
    ux, uy, uz = vel[0], vel[1], vel[2]
    uu = ux * ux + uy * uy + uz * uz
    out = []
    for a, (cx, cy, cz) in enumerate(DIRS):
        cu = cx * ux + cy * uy + cz * uz
        out.append(WEIGHTS[a] * (pstar + 3.0 * cu + 4.5 * cu * cu - 1.5 * uu))
    return jnp.stack(out)
