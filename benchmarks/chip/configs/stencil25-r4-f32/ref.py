"""Plain reference of the range-four 3D25pt star stencil, written from its
definition (arXiv:2107.01143) and sharing no code with the program.

dst[z, y, x] = sum_k w_k src[(z, y, x) + o_k], with the 25 offsets o_k of the
star (centre, then for each distance d = 1..4 the neighbours at +x, -x, +y,
-y, +z, -z) and weights w_k = k / 325.  Cells outside the field take the value
of the nearest edge cell.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

R = 4


def offsets(r: int = R) -> list[tuple[int, int, int]]:
    out = [(0, 0, 0)]
    for d in range(1, r + 1):
        out += [(0, 0, d), (0, 0, -d), (0, d, 0), (0, -d, 0), (d, 0, 0), (-d, 0, 0)]
    return out


def weights(r: int = R) -> np.ndarray:
    n = 6 * r + 1
    return np.arange(1, n + 1, dtype=np.float64) / (n * (n + 1) / 2)


def step(domain: dict, dtype=jnp.float32) -> dict:
    """One stencil step over ``domain["x"]`` (nz, ny, nx), computed and
    returned in ``dtype``."""
    x = domain["x"].astype(dtype)
    nz, ny, nx = x.shape
    padded = jnp.pad(x, R, mode="edge")
    acc = jnp.zeros(x.shape, dtype)
    for w, (dz, dy, dx) in zip(weights(), offsets()):
        acc = acc + jnp.asarray(w, dtype) * padded[
            R + dz:R + dz + nz, R + dy:R + dy + ny, R + dx:R + dx + nx]
    return {"x": acc}
