"""Pallas TPU kernel: chunked RWKV6 WKV (the §Perf rwkv6 hot spot).

Grid: (BH, n_chunks) with chunks innermost — the (K, V) recurrent state lives in
VMEM scratch across the chunk sweep of one (batch, head), so HBM traffic is one
read of r/k/v/w and one write of out per token (the naive scan round-trips the
state per TOKEN; this kernel is the TPU-native form of the 1128x §Perf win).

Within a chunk of L steps everything is dense (L,L[,K]) math on the MXU/VPU:
  out_t = Σ_{s<t} (r_t · exp(Λ_{t-1}-Λ_s) ⊙ k_s) v_s     (strict lower tri)
        + (r_t · (u ⊙ k_t)) v_t                           (diagonal bonus)
        + (r_t ⊙ exp(Λ_{t-1})) · S_chunk_start
  S_end = exp(Λ_L) ⊙ S_start + Σ_s (exp(Λ_L - Λ_s) ⊙ k_s) v_s^T
All exponents are <= 0, so there is no factorization overflow (DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state, *, L: int, K: int, n_chunks: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    r = r_ref[0].astype(jnp.float32)  # (L, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    wlog = w_ref[0].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)  # (1, K)

    # Every product is an MXU matmul at HIGHEST precision, so the f32
    # log-decays are not rounded to bf16.  Prefix sums over the chunk are
    # lower-triangular ones matmuls: Mosaic has no cumsum lowering.
    def mm(a, b, contract=((1,), (0,))):
        return jax.lax.dot_general(
            a, b, (contract, ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tri = row > col
    lam = mm((row >= col).astype(jnp.float32), wlog)  # (L, K) inclusive
    lam_prev = mm(tri.astype(jnp.float32), wlog)  # exclusive
    seg = lam_prev[:, None, :] - lam[None, :, :]  # (Lt, Ls, K)
    tri3 = jax.lax.broadcasted_iota(jnp.int32, (L, L, K), 0) > (
        jax.lax.broadcasted_iota(jnp.int32, (L, L, K), 1)
    )
    seg = jnp.where(tri3, seg, -60.0)
    # A[t,s] = sum_k r[t,k] decay[t,s,k] k[s,k]
    a = jnp.sum(r[:, None, :] * jnp.exp(seg) * k[None, :, :], axis=2)
    out = mm(a, v)
    bonus = jnp.sum(r * (u * k), axis=1, keepdims=True)  # (L, 1)
    out = out + bonus * v
    s0 = state[...]
    out = out + mm(r * jnp.exp(lam_prev), s0)
    lam_end = lam[L - 1 :, :]  # (1, K)
    inj = mm(k * jnp.exp(lam_end - lam), v, ((0,), (0,)))  # (K, V)
    # diag(exp(lam_end)) @ S: scales row i of the state by its decay
    eye = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0) == (
        jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
    )
    state[...] = mm(jnp.where(eye, jnp.exp(lam_end), 0.0), s0) + inj
    o_ref[0] = out.astype(o_ref.dtype)


def wkv_pallas(
    r, k, v, wlog, u, chunk: int = 64, vmem_limit_bytes: int | None = None,
    interpret: bool = False,
):
    """r,k,v,wlog: (BH, S, K); u: (K,). Returns out (BH, S, K)."""
    BH, S, K = r.shape
    if S % chunk:
        raise ValueError(f"seq {S} not divisible by chunk {chunk}")
    nc = S // chunk
    spec = pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0))
    u2 = u.reshape(1, K)
    kernel = functools.partial(_wkv_kernel, L=chunk, K=K, n_chunks=nc)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[spec, spec, spec, spec, pl.BlockSpec((1, K), lambda b, c: (0, 0))],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((BH, S, K), r.dtype),
        scratch_shapes=[pltpu.VMEM((K, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="wkv",
    )(r, k, v, wlog, u2)
