"""jit'd wrapper + estimator-guided chunk selection for the WKV kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import tpu_estimator as te
from ...core.machine import TPUMachine

# GPU-space entry: the AccessIR builder that pushes this kernel through the
# paper §III analytic pipeline (registry kernel "wkv", backend "gpu").
from ...frontend.builders import wkv_gpu_ir
from ..entry import chip
from ..tiling import pick
from .kernel import wkv_pallas
from .ref import wkv_ref

CANDIDATE_CHUNKS = (16, 32, 64, 128, 256)


def config_space(BH: int, S: int, K: int, dtype_bits: int = 32):
    """Candidate chunk lengths L: per-step flops grow ~L^2*K (intra matmuls) while
    the sequential grid and per-token HBM traffic shrink ~1/L — the estimator
    finds the knee analytically."""
    out = []
    for L in CANDIDATE_CHUNKS:
        if S % L:
            continue
        accesses = tuple(
            te.BlockAccess(nm, (1, L, K), lambda b, c: (b, c, 0), dtype_bits)
            for nm in ("r", "k", "v", "w")
        ) + (
            te.BlockAccess("o", (1, L, K), lambda b, c: (b, c, 0), dtype_bits, True),
        )
        out.append(
            te.PallasConfig(
                name=f"wkv_L{L}",
                grid=(BH, S // L),
                accesses=accesses,
                # intra: A (L^2 K) + A@v (L^2 K) + inter/inject (2 L K^2)
                flops_per_step=2.0 * (2 * L * L * K + 2 * L * K * K),
                is_matmul=True,
                scratch_bytes=4 * K * K,
                meta={"chunk": L},
            )
        )
    return out


def select_chunk(
    BH: int, S: int, K: int, *, machine: TPUMachine
) -> tuple[int, te.TPUEstimate]:
    cfg, est = pick(config_space(BH, S, K), machine,
                    f"no candidate chunk {CANDIDATE_CHUNKS} divides sequence length {S}")
    return cfg.meta["chunk"], est


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv(r, k, v, wlog, u, chunk: int | None = None, interpret: bool = False):
    """Chunked WKV; chunk and VMEM limit as in :func:`stencil25.ops.stencil25`."""
    BH, S, K = r.shape
    machine, vmem_limit = chip(interpret)
    if chunk is None:
        chunk, _ = select_chunk(BH, S, K, machine=machine)
    return wkv_pallas(r, k, v, wlog, u, chunk=chunk, interpret=interpret,
                      vmem_limit_bytes=vmem_limit)


__all__ = ["wkv", "wkv_ref", "select_chunk", "config_space", "wkv_gpu_ir"]
