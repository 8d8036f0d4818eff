"""Pallas TPU kernel: GQA flash-attention forward (online softmax).

Grid: (batch*q_heads, q_blocks, kv_blocks) — kv innermost so the f32 accumulators
in VMEM scratch persist across the kv sweep of one (head, q-block).  BlockSpecs:
q/out blocks (bq, d); k/v blocks (bkv, d), with the GQA head mapping folded into
the k/v index maps.  Block sizes are selected by `ops.select_blocks` via
`core.tpu_estimator` (the paper's configuration-selection loop).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (1, bq, d)
    k_ref,  # (1, bkv, d)
    v_ref,  # (1, bkv, d)
    o_ref,  # (1, bq, d)
    m_scr,  # (bq, 1) f32
    l_scr,  # (bq, 1) f32
    acc_scr,  # (bq, d) f32
    *,
    bq: int,
    bkv: int,
    causal: bool,
    scale: float,
    n_kv_blocks: int,
):
    j = pl.program_id(2)
    i = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (bq, bkv)
    if causal:
        q_pos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        k_pos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = q_pos >= k_pos
        s = jnp.where(mask, s, NEG_INF)
    m_prev = m_scr[...]  # (bq, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    if causal:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
    l_new = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    v = v_ref[0].astype(jnp.float32)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_new = acc_scr[...] * alpha + pv
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(j == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,  # (B, Hq, S, D)
    k: jnp.ndarray,  # (B, Hkv, S, D)
    v: jnp.ndarray,  # (B, Hkv, S, D)
    causal: bool = True,
    block_q: int = 128,
    block_kv: int = 128,
    vmem_limit_bytes: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    group = hq // hkv
    if sq % block_q or skv % block_kv:
        raise ValueError(f"seq {sq}/{skv} not divisible by blocks {block_q}/{block_kv}")
    nq, nkv = sq // block_q, skv // block_kv
    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    def kv_head(bh):  # flat q-head id -> flat kv-head id (GQA)
        batch = bh // hq
        head = bh % hq
        return batch * hkv + head // group

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    k_spec = pl.BlockSpec((1, block_kv, d), lambda bh, i, j: (kv_head(bh), j, 0))
    v_spec = pl.BlockSpec((1, block_kv, d), lambda bh, i, j: (kv_head(bh), j, 0))
    o_spec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    kernel = functools.partial(
        _flash_kernel,
        bq=block_q,
        bkv=block_kv,
        causal=causal,
        scale=1.0 / (d**0.5),
        n_kv_blocks=nkv,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nkv),
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)
