"""Batched serving engine: prefill + greedy/temperature decode over a KV cache."""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.registry import LM


@dataclass
class ServeEngine:
    model: LM
    params: Any
    max_len: int = 512

    def __post_init__(self):
        self._prefill = jax.jit(self.model.decode_step)

        @jax.jit
        def _decode(params, cache, tok, key, temperature):
            logits, cache = self.model.decode_step(params, cache, tok)
            logits = logits[:, -1, :]
            greedy = jnp.argmax(logits, axis=-1)
            sampled = jax.random.categorical(key, logits / jnp.maximum(temperature, 1e-4))
            next_tok = jnp.where(temperature <= 0.0, greedy, sampled)
            return next_tok[:, None].astype(jnp.int32), logits, cache

        self._decode = _decode

    def generate(
        self,
        prompts: np.ndarray,  # (B, S0) int32
        n_steps: int = 32,
        temperature: float = 0.0,
        seed: int = 0,
        return_logits: bool = False,
    ):
        """Generate ``n_steps`` tokens per prompt -> (B, n_steps) int32.

        With ``return_logits`` also returns the fp32 logits of every position
        fed through the cache, (B, S0 + n_steps - 1, V): the prefill's S0, then
        one per decode step.  They are what ``model.forward`` gives on
        ``concat(prompts, tokens[:, :-1])``.
        """
        B, S0 = prompts.shape
        cache = self.model.init_cache(B, self.max_len)
        # prefill: feed the prompt through the cached path (updates cache)
        logits, cache = self._prefill(
            self.params, cache, jnp.asarray(prompts, jnp.int32)
        )
        seen = [logits]
        tok = jnp.argmax(logits[:, -1:, :], axis=-1).astype(jnp.int32)
        out = [np.asarray(tok)]
        key = jax.random.PRNGKey(seed)
        for i in range(n_steps - 1):
            key, sub = jax.random.split(key)
            tok, logits, cache = self._decode(self.params, cache, tok, sub, temperature)
            seen.append(logits[:, None, :])
            out.append(np.asarray(tok))
        tokens = np.concatenate(out, axis=1)
        if return_logits:
            return tokens, np.asarray(jnp.concatenate(seen, axis=1))
        return tokens
