"""Pallas TPU kernel: D3Q27 velocity-based hydrodynamic LB step of the
two-phase solver (paper app 2, the lattice that feels the interface forces).

TPU adaptation: tiles over (z, y); x is the lane dimension and every block
holds whole x rows, so the periodic x neighbours are lane rotations of the
row (no ghost-padded copy of the field).
The 27 pdf components are grouped by their (cz, cy) stream direction into
nine classes of three (``ref.DIRS`` order), so a class is one
(3, bz, by, nx) block.  Pull streaming reads a
class's pdfs at (z - cz, y - cy), so each class needs only the halo on the
side it streams from: its centre tile, a z strip where cz != 0, a y strip
where cy != 0 and a (z, y) corner piece where both are.  The new phase field
feeds the 27-point gradient and Laplacian, so it reads its centre, four
strips and four corner pieces; the carried velocity only its centre tile.
Strip heights come from :func:`repro.kernels.stencil25.kernel.strip_heights`
at range 1: one z plane, and y rows on the dtype's sublane tile.  The D3Q15
kernel reads the same layout (:mod:`repro.kernels.tiling`).
:func:`input_blocks` and :func:`output_blocks` are the one description of
these blocks, which :mod:`repro.kernels.tiling` turns into the BlockSpecs and
the estimator's candidates.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..stencil25.kernel import strip_heights
from ..tiling import (SIDE_READ_BY, SIDES, Block, _plane, _ystrip, _yshift, edges, ghost_blocks,
                      halo_block, pallas_specs)
from .ref import DIRS, STEPS, WEIGHTS, TwoPhaseParams

CLASSES = tuple((cz, cy) for cz in STEPS for cy in STEPS)  # class k = components 3k..3k+2


def input_blocks(block: tuple[int, int], nx: int, dtype_bits: int):
    """The kernel's inputs (:class:`~repro.kernels.tiling.Block`), in the
    kernel's order: for each class of g its centre, z strip, y strip and
    corner piece (those it streams from), then phase's centre, four strips
    and four corners, then vel's centre.  ``operand`` is ``g`` (27, nz, ny, nx),
    ``phase`` (nz, ny, nx) or ``vel`` (3, nz, ny, nx).  The index maps are
    the interior ones."""
    heights = strip_heights(1, block, dtype_bits)
    out = []
    for k, (cz, cy) in enumerate(CLASSES):
        zs, ys = SIDE_READ_BY.get(cz), SIDE_READ_BY.get(cy)
        for suffix, zside, yside in (("", None, None), ("_z", zs, None), ("_y", None, ys),
                                     ("_zy", zs, ys)):
            if ("z" in suffix and zs is None) or ("y" in suffix and ys is None):
                continue  # a side this class does not stream from
            out.append(halo_block(f"g{k}{suffix}", "g", ((3, k),), block, heights, nx,
                                  zside, yside))
    for zside in (None, *SIDES):
        for yside in (None, *SIDES):
            name = "phase" + (f"_z{zside}" if zside else "") + (f"_y{yside}" if yside else "")
            out.append(halo_block(name, "phase", (), block, heights, nx, zside, yside))
    out.append(halo_block("vel", "vel", ((3, 0),), block, heights, nx))
    return tuple(out)


def output_blocks(block: tuple[int, int], nx: int):
    """The output tiles of g' and u'."""
    bz, by = block
    return (Block("g_out", (27, bz, by, nx), lambda i, j: (0, i, j, 0), is_output=True),
            Block("vel_out", (3, bz, by, nx), lambda i, j: (0, i, j, 0), is_output=True))


def _hydro_kernel(*refs, names, hy: int, nx: int, p: TwoPhaseParams):
    """One (bz, by, nx) output tile of g' and u' from the blocks named in
    ``names`` (:func:`input_blocks` order), then the two output refs.  The
    body walks the tile's z planes, so its code and its values are those of
    one (by, nx) plane whatever bz is."""
    ins = dict(zip(names, refs))
    g_out_ref, vel_out_ref = refs[len(names)], refs[len(names) + 1]
    bz, by = ins["phase"].shape[0], ins["phase"].shape[1]
    at_edge = edges(names)

    def phase_rows(z, dz):
        """Plane z + dz of the new phase over y rows [-hy, by + hy) of the
        tile: the y strips' planes around the centre's."""
        zside = {-1: "_zlo", 0: "", 1: "_zhi"}[dz]
        corner = (lambda part: "phase" + zside + part) if dz else (lambda part: None)
        return jnp.concatenate([
            _ystrip(ins, "phase_ylo", corner("_ylo"), z, dz, at_edge["lo"]),
            _plane(ins["phase"], ins["phase" + zside] if dz else None, z, dz),
            _ystrip(ins, "phase_yhi", corner("_yhi"), z, dz, at_edge["hi"]),
        ], axis=0)

    def xshift(a, cx: int):
        """a(x - cx) at x, periodic: a lane rotation of the whole row."""
        return pltpu.roll(a, cx % nx, 1) if cx else a

    def ysmooth(a):
        return (a[hy - 1 : hy - 1 + by] + 4.0 * a[hy : hy + by] + a[hy + 1 : hy + 1 + by]) / 6.0

    def xsmooth(a):
        return (xshift(a, 1) + 4.0 * a + xshift(a, -1)) / 6.0

    def signed_sum(terms):
        """sum of (sign, value) pairs, the zero-weight terms left out."""
        acc = None
        for sign, v in terms:
            if sign == 0:
                continue
            acc = (v if sign > 0 else -v) if acc is None else (acc + v if sign > 0 else acc - v)
        return acc

    def plane_step(z, carry):
        # pull streaming, class by class: gh_a(p) = g_a(p - c_a)
        pulled = []
        for k, (cz, cy) in enumerate(CLASSES):
            cls = _plane(ins[f"g{k}"], ins.get(f"g{k}_z"), z, -cz)
            if cy:
                strip = _ystrip(ins, f"g{k}_y", f"g{k}_zy" if cz else None, z, -cz,
                                at_edge[SIDE_READ_BY[cy]])
                cls = _yshift(cls, strip, cy, hy)
            for m in range(3):
                pulled.append(xshift(cls[m], DIRS[3 * k + m][0]))

        # the D3Q27 weights factor as w(c) = s(cx) s(cy) s(cz), s = (1/6, 2/3, 1/6):
        # the 27-point sums are one smoothing or difference per axis
        below, mid, above = phase_rows(z, -1), phase_rows(z, 0), phase_rows(z, 1)
        zs = (below + 4.0 * mid + above) / 6.0
        zd = (above - below) / 6.0
        yss = ysmooth(zs)
        yds = (zs[hy + 1 : hy + 1 + by] - zs[hy - 1 : hy - 1 + by]) / 6.0
        phi = mid[hy : hy + by]
        grad = (3.0 * (xshift(yss, -1) - xshift(yss, 1)) / 6.0,
                3.0 * xsmooth(yds),
                3.0 * xsmooth(ysmooth(zd)))
        lap = 6.0 * (xsmooth(yss) - phi)

        drho_dphi = p.rho_heavy - p.rho_light
        rho = p.rho_light + phi * drho_dphi
        tau = p.tau_light + phi * (p.tau_heavy - p.tau_light)
        mu = 4.0 * p.beta * phi * (phi - 1.0) * (phi - 0.5) - p.kappa * lap
        drho = [drho_dphi * d for d in grad]

        pstar = pulled[0]
        for a in range(1, 27):
            pstar = pstar + pulled[a]
        vel = ins["vel"][:, z]
        u = (vel[0], vel[1], vel[2])

        def eq(a, v, uu):
            cx, cy, cz = DIRS[a]
            cu = signed_sum(((cx, v[0]), (cy, v[1]), (cz, v[2])))
            if cu is None:
                return WEIGHTS[a] * (pstar - 1.5 * uu)
            return WEIGHTS[a] * (pstar + 3.0 * cu + 4.5 * cu * cu - 1.5 * uu)

        uu_old = u[0] * u[0] + u[1] * u[1] + u[2] * u[2]
        neq = [pulled[a] - eq(a, u, uu_old) for a in range(27)]
        force = []
        for i in range(3):
            visc = None
            for j in range(3):
                moment = signed_sum((DIRS[a][i] * DIRS[a][j], neq[a]) for a in range(27))
                visc = moment * drho[j] if visc is None else visc + moment * drho[j]
            force.append(mu * grad[i] - pstar / 3.0 * drho[i] - tau * visc)

        new_u = [signed_sum((DIRS[a][i], pulled[a]) for a in range(27)) + force[i] / (2.0 * rho)
                 for i in range(3)]
        uu_new = new_u[0] * new_u[0] + new_u[1] * new_u[1] + new_u[2] * new_u[2]
        omega = 1.0 / (tau + 0.5)
        inv_rho = 1.0 / rho
        for a, (cx, cy, cz) in enumerate(DIRS):
            cf = signed_sum(((cx, force[0]), (cy, force[1]), (cz, force[2])))
            gbar = eq(a, new_u, uu_new)
            if cf is None:
                g_out_ref[a, z] = pulled[a] - omega * (pulled[a] - gbar)
            else:
                fa = 3.0 * WEIGHTS[a] * cf * inv_rho
                g_out_ref[a, z] = pulled[a] - omega * (pulled[a] - (gbar - 0.5 * fa)) + fa
        for i in range(3):
            vel_out_ref[i, z] = new_u[i]
        return carry

    jax.lax.fori_loop(0, bz, plane_step, 0)


def hydro_step_pallas(
    g: jnp.ndarray,
    phase: jnp.ndarray,
    vel: jnp.ndarray,
    params: TwoPhaseParams = TwoPhaseParams(),
    block: tuple[int, int] = (8, 8),
    vmem_limit_bytes: int | None = None,
    interpret: bool = False,
    ghosts: dict | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """One D3Q27 hydrodynamic step -> (g', u'); valid outside the one-cell
    z/y shell, where the clamped halo stands in for the neighbours.
    ``ghosts`` as in :func:`repro.kernels.lbm_d3q15.kernel.lbm_step_pallas`."""
    _, nz, ny, nx = g.shape
    bz, by = block
    if nz % bz or ny % by:
        raise ValueError(f"grid {(nz, ny, nx)} not divisible by block {block}")
    bits = g.dtype.itemsize * 8
    fields = {"g": g, "phase": phase, "vel": vel, **(ghosts or {})}
    inputs = input_blocks(block, nx, bits)
    if ghosts:
        inputs = (*inputs, *ghost_blocks(inputs))
    in_specs, out_specs = pallas_specs((*inputs, *output_blocks(block, nx)),
                                       {op: a.shape for op, a in fields.items()})
    kernel = functools.partial(_hydro_kernel, names=tuple(b.name for b in inputs),
                               hy=strip_heights(1, block, bits)[1], nx=nx, p=params)
    return pl.pallas_call(
        kernel,
        grid=(nz // bz, ny // by),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=(jax.ShapeDtypeStruct(g.shape, g.dtype),
                   jax.ShapeDtypeStruct(vel.shape, vel.dtype)),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="lbm_d3q27",
    )(*(fields[b.operand] for b in inputs))
