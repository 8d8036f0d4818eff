"""Unified LM assembly for all assigned architectures.

One :class:`LM` covers the four families:
  * dense / audio / vlm : pre-norm GQA transformer (scan-over-layers)
  * moe                 : same skeleton with a routed-MoE MLP
  * ssm                 : RWKV6 Finch stack (attention-free)
  * hybrid              : Zamba2 — Mamba2 blocks with one *shared* attention+MLP
                          block applied after every ``shared_attn_period`` blocks

Everything is scan-over-layers with stacked parameters (compact HLO — essential
for 512-device dry-run compiles) and optional per-layer remat.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from .layers import (
    apply_norm,
    attention_block,
    attention_defs,
    mlp,
    mlp_defs,
    moe_block,
    moe_defs,
    norm_defs,
)
from .mamba2 import mamba2_block, mamba2_defs
from .params import ParamDef, stack_blueprint
from .rwkv6 import rwkv6_block, rwkv6_defs
from .shardctx import constrain


def _dtype(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16}[name]


@dataclass
class LM:
    cfg: ArchConfig

    # ------------------------------------------------------------------ #
    # Blueprint
    # ------------------------------------------------------------------ #
    def blueprint(self) -> dict:
        cfg = self.cfg
        d, V = cfg.d_model, cfg.vocab
        bp: dict[str, Any] = {
            "embed": ParamDef((V, d), ("tp", "fsdp"), scale=1.0),
            "final_norm": norm_defs(cfg),
        }
        if not cfg.tie_embeddings:
            bp["unembed"] = ParamDef((d, V), ("fsdp", "tp"))
        if cfg.frontend != "none":
            bp["frontend_proj"] = ParamDef((cfg.frontend_dim, d), (None, "tp"))
        if cfg.family == "ssm":
            bp["blocks"] = stack_blueprint(rwkv6_defs(cfg), cfg.n_layers)
        elif cfg.family == "hybrid":
            block = {"ln": norm_defs(cfg), "mamba": mamba2_defs(cfg)}
            bp["blocks"] = stack_blueprint(block, cfg.n_layers)
            bp["shared_attn"] = {
                "ln1": norm_defs(cfg),
                "attn": attention_defs(cfg),
                "ln2": norm_defs(cfg),
                "mlp": mlp_defs(cfg),
            }
        else:
            block = {
                "ln1": norm_defs(cfg),
                "attn": attention_defs(cfg),
                "ln2": norm_defs(cfg),
            }
            if cfg.moe is not None:
                block["moe"] = moe_defs(cfg)
            else:
                block["mlp"] = mlp_defs(cfg)
            bp["blocks"] = stack_blueprint(block, cfg.n_layers)
        return bp

    # ------------------------------------------------------------------ #
    # Embedding / head
    # ------------------------------------------------------------------ #
    def _embed(self, params, tokens, frontend_embeds=None):
        cfg = self.cfg
        cdt = _dtype(cfg.compute_dtype)
        h = constrain(params["embed"].astype(cdt)[tokens], ("dp", None, None))
        if cfg.frontend != "none" and frontend_embeds is not None:
            proj = frontend_embeds.astype(cdt) @ params["frontend_proj"].astype(cdt)
            h = jax.lax.dynamic_update_slice(h, proj, (0, 0, 0))
        return h

    def _head(self, params, h):
        cfg = self.cfg
        w = (
            params["embed"].T if cfg.tie_embeddings else params["unembed"]
        )
        return (h.astype(jnp.float32) @ w.astype(jnp.float32))  # fp32 logits

    # ------------------------------------------------------------------ #
    # Block stacks (shared by forward and decode)
    # ------------------------------------------------------------------ #
    def _dense_body(self, params_l, x, positions, cache_l=None):
        cfg = self.cfg
        h = apply_norm(cfg, params_l.get("ln1", {}), x)
        a, new_cache = attention_block(cfg, params_l["attn"], h, positions, cache_l)
        x = x + a
        h2 = apply_norm(cfg, params_l.get("ln2", {}), x)
        if cfg.moe is not None:
            m, aux = moe_block(cfg, params_l["moe"], h2)
        else:
            m, aux = mlp(cfg, params_l["mlp"], h2), jnp.zeros((), jnp.float32)
        return x + m, aux, new_cache

    def _run_blocks(self, params, h, positions, caches=None):
        """caches: None (train/prefill without cache) or stacked per-layer trees.

        Returns (h, aux_loss, new_caches)."""
        cfg = self.cfg
        remat = cfg.remat

        if cfg.family == "ssm":

            def body(x, inp):
                p_l, st_l = inp
                x = constrain(x, ("dp", None, None))
                out, new_st = rwkv6_block(cfg, p_l, x, st_l)
                return constrain(x + out, ("dp", None, None)), new_st

            body_fn = jax.checkpoint(body) if remat else body
            xs = (params["blocks"], caches)
            h, new_states = jax.lax.scan(body_fn, h, xs)
            return h, jnp.zeros((), jnp.float32), new_states

        if cfg.family == "hybrid":
            g = cfg.shared_attn_period
            L = cfg.n_layers
            n_groups = L // g
            shared = params["shared_attn"]
            grouped = jax.tree.map(
                lambda a: a.reshape(n_groups, g, *a.shape[1:]), params["blocks"]
            )
            mamba_caches, attn_caches = (
                caches if caches is not None else (None, None)
            )
            if mamba_caches is not None:
                mamba_caches = jax.tree.map(
                    lambda a: a.reshape(n_groups, g, *a.shape[1:]), mamba_caches
                )

            def group_body(x, inp):
                gp, g_mamba_cache, g_attn_cache = inp

                def mamba_body(xx, inner):
                    p_l, st_l = inner
                    xx = constrain(xx, ("dp", None, None))
                    hh = apply_norm(cfg, p_l["ln"], xx)
                    out, new_st = mamba2_block(cfg, p_l["mamba"], hh, st_l)
                    return constrain(xx + out, ("dp", None, None)), new_st

                mb = jax.checkpoint(mamba_body) if remat else mamba_body
                x, new_mstates = jax.lax.scan(mb, x, (gp, g_mamba_cache))
                hh = apply_norm(cfg, shared["ln1"], x)
                a, new_attn_cache = attention_block(
                    cfg, shared["attn"], hh, positions, g_attn_cache
                )
                x = x + a
                hh2 = apply_norm(cfg, shared["ln2"], x)
                x = x + mlp(cfg, shared["mlp"], hh2)
                return x, (new_mstates, new_attn_cache)

            gb = jax.checkpoint(group_body) if remat else group_body
            h, (new_m, new_a) = jax.lax.scan(
                gb, h, (grouped, mamba_caches, attn_caches)
            )
            new_m = jax.tree.map(
                lambda a: a.reshape(L, *a.shape[2:]), new_m
            )
            return h, jnp.zeros((), jnp.float32), (new_m, new_a)

        # dense / moe / audio / vlm
        def body(x, inp):
            p_l, c_l = inp
            x = constrain(x, ("dp", None, None))
            x, aux, new_c = self._dense_body(p_l, x, positions, c_l)
            return constrain(x, ("dp", None, None)), (aux, new_c)

        body_fn = jax.checkpoint(body) if remat else body
        h, (auxs, new_caches) = jax.lax.scan(body_fn, h, (params["blocks"], caches))
        return h, auxs.mean(), new_caches

    # ------------------------------------------------------------------ #
    # Public API
    # ------------------------------------------------------------------ #
    def forward(self, params, tokens, frontend_embeds=None):
        """Train/prefill forward: tokens (B, S) -> logits (B, S, V) fp32."""
        B, S = tokens.shape
        h = self._embed(params, tokens, frontend_embeds)
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        h, aux, _ = self._run_blocks(params, h, positions, caches=None)
        h = apply_norm(self.cfg, params.get("final_norm", {}), h)
        return self._head(params, h), aux

    def loss(self, params, batch):
        logits, aux = self.forward(
            params, batch["tokens"], batch.get("frontend_embeds")
        )
        labels = batch["labels"]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        # label log-prob via masked reduction, NOT take_along_axis: a gather over
        # the vocab dim would force an all-gather of tp-sharded logits
        vidx = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
        picked = jnp.sum(
            jnp.where(vidx == labels[..., None], logits, 0.0), axis=-1
        )
        ll = picked - lse
        ce = -ll.mean()
        z = jnp.square(lse).mean()
        total = ce + 1e-4 * z + 1e-2 * aux
        return total, {"ce": ce, "aux": aux, "zloss": z}

    # -------------------------- decoding ------------------------------ #
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        cdt = _dtype(cfg.compute_dtype)
        L, Hkv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
        if cfg.family == "ssm":
            d = cfg.d_model
            K = cfg.rwkv_head_dim
            H = d // K
            return {
                "shift_tm": jnp.zeros((L, batch, 1, d), cdt),
                "shift_cm": jnp.zeros((L, batch, 1, d), cdt),
                "s": jnp.zeros((L, batch, H, K, K), jnp.float32),
            }
        if cfg.family == "hybrid":
            d_in = 2 * cfg.d_model
            H = d_in // cfg.ssm_head_dim
            n_groups = cfg.n_layers // cfg.shared_attn_period
            mamba = {
                "h": jnp.zeros((L, batch, H, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32),
                "conv": jnp.zeros((L, batch, 3, d_in + 2 * cfg.ssm_state), cdt),
            }
            attn = {
                "k": jnp.zeros((n_groups, batch, max_len, Hkv, hd), cdt),
                "v": jnp.zeros((n_groups, batch, max_len, Hkv, hd), cdt),
                "len": jnp.zeros((n_groups,), jnp.int32),
            }
            return (mamba, attn)
        return {
            "k": jnp.zeros((L, batch, max_len, Hkv, hd), cdt),
            "v": jnp.zeros((L, batch, max_len, Hkv, hd), cdt),
            "len": jnp.zeros((L,), jnp.int32),
        }

    def decode_step(self, params, cache, tokens):
        """tokens (B, S) -> (logits (B, S, V), new_cache): one decode step
        (S = 1) or a prefill of S tokens appended to the cache."""
        cfg = self.cfg
        B, S = tokens.shape
        h = self._embed(params, tokens)
        positions = None
        if cfg.family != "ssm":
            # a prefill of S tokens occupies positions len .. len + S - 1
            pos0 = (cache[1] if cfg.family == "hybrid" else cache)["len"][0]
            positions = jnp.broadcast_to(pos0 + jnp.arange(S)[None, :], (B, S))
        h, _, new_cache = self._run_blocks(params, h, positions, caches=cache)
        h = apply_norm(cfg, params.get("final_norm", {}), h)
        return self._head(params, h), new_cache


def build_model(cfg: ArchConfig) -> LM:
    return LM(cfg)
