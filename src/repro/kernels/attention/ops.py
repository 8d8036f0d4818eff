"""jit'd wrapper + estimator-guided block selection for flash attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import tpu_estimator as te
from ...core.machine import TPUMachine

# GPU-space entry: the AccessIR builder that pushes this kernel through the
# paper §III analytic pipeline (registry kernel "attention", backend "gpu").
from ...frontend.builders import attention_gpu_ir
from ..entry import chip
from ..tiling import pick
from .kernel import flash_attention_pallas
from .ref import mha_ref

CANDIDATE_BLOCKS = (128, 256, 512, 1024)


def config_space(
    b: int, hq: int, hkv: int, s: int, d: int, dtype_bits: int, causal: bool = True
):
    """Candidate (block_q, block_kv) configs.

    The kv refetch across the q-block loop is the V_red analogue: k/v blocks are
    refetched for every q block of the same head.  Larger kv blocks reduce grid
    overhead but raise VMEM; the estimator trades these off analytically.

    The grid splits the batch*head loop into (batch, kv_head, group) dims so
    every ``index_map`` is *affine* in the grid coordinates — the fused-``bh``
    form indexed kv heads through an integer division, which the AccessIR
    tracer rightly rejects (and which the old probe-based store keys silently
    mis-fingerprinted).  The enumeration order, and therefore the Pallas
    revisit/fetch schedule, is unchanged: ``bh == batch*hq + kv_head*g + grp``
    iterates exactly as the old fused dimension did.
    """
    group = max(1, hq // max(hkv, 1))
    out = []
    for bq in CANDIDATE_BLOCKS:
        for bkv in CANDIDATE_BLOCKS:
            if s % bq or s % bkv:
                continue
            nq, nkv = s // bq, s // bkv
            accesses = (
                te.BlockAccess(
                    "q",
                    (1, bq, d),
                    lambda bb, hk, gg, i, j, g=group, hq=hq: (
                        bb * hq + hk * g + gg,
                        i,
                        0,
                    ),
                    dtype_bits,
                ),
                te.BlockAccess(
                    "k",
                    (1, bkv, d),
                    lambda bb, hk, gg, i, j, hkv=hkv: (bb * hkv + hk, j, 0),
                    dtype_bits,
                ),
                te.BlockAccess(
                    "v",
                    (1, bkv, d),
                    lambda bb, hk, gg, i, j, hkv=hkv: (bb * hkv + hk, j, 0),
                    dtype_bits,
                ),
                te.BlockAccess(
                    "o",
                    (1, bq, d),
                    lambda bb, hk, gg, i, j, g=group, hq=hq: (
                        bb * hq + hk * g + gg,
                        i,
                        0,
                    ),
                    dtype_bits,
                    True,
                ),
            )
            # causal: ~half the kv blocks do useful work; flops halve but the
            # fetch schedule (grid) is unchanged
            useful = 0.5 if causal else 1.0
            out.append(
                te.PallasConfig(
                    name=f"flash_bq{bq}_bkv{bkv}",
                    grid=(b, hkv, group, nq, nkv),
                    accesses=accesses,
                    flops_per_step=useful * (4.0 * bq * bkv * d),
                    is_matmul=True,
                    scratch_bytes=4 * (bq * d + 2 * bq),
                    meta={"block_q": bq, "block_kv": bkv},
                )
            )
    return out


def select_blocks(
    b: int,
    hq: int,
    hkv: int,
    s: int,
    d: int,
    dtype=jnp.bfloat16,
    causal: bool = True,
    *,
    machine: TPUMachine,
) -> tuple[tuple[int, int], te.TPUEstimate]:
    cands = config_space(b, hq, hkv, s, d, jnp.dtype(dtype).itemsize * 8, causal)
    cfg, est = pick(cands, machine,
                    f"no candidate blocks {CANDIDATE_BLOCKS} divide sequence length {s}")
    return (cfg.meta["block_q"], cfg.meta["block_kv"]), est


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_kv", "interpret")
)
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """GQA flash attention; blocks and VMEM limit as in
    :func:`stencil25.ops.stencil25` (either block left ``None`` is picked)."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    machine, vmem_limit = chip(interpret)
    if block_q is None or block_kv is None:
        (bq, bkv), _ = select_blocks(
            b, hq, hkv, s, d, q.dtype, causal, machine=machine
        )
        block_q = block_q or bq
        block_kv = block_kv or bkv
    return flash_attention_pallas(
        q, k, v, causal=causal, block_q=block_q, block_kv=block_kv,
        interpret=interpret, vmem_limit_bytes=vmem_limit,
    )


__all__ = [
    "attention_gpu_ir",
    "config_space",
    "flash_attention",
    "mha_ref",
    "select_blocks",
]
