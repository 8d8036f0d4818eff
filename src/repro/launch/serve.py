"""Serving launcher CLI (batched prefill + decode).

  python -m repro.launch.serve --arch olmo-1b --smoke --requests 4 --steps 16
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from ..configs import get_arch
from .compile_cache import enable_compile_cache
from ..models.params import init_params
from ..models.registry import build_model
from ..serve.engine import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    model = build_model(cfg)
    params = init_params(model.blueprint(), jax.random.PRNGKey(0))
    engine = ServeEngine(model, params, max_len=args.prompt_len + args.steps + 8)
    prompts = (
        np.random.default_rng(0)
        .integers(0, cfg.vocab, size=(args.requests, args.prompt_len))
        .astype(np.int32)
    )
    t0 = time.time()
    out = engine.generate(prompts, n_steps=args.steps, temperature=args.temperature)
    dt = time.time() - t0
    print(f"{cfg.name}: {args.requests} requests x {args.steps} tokens in {dt:.2f}s")
    print(out[:, :10])


if __name__ == "__main__":
    main()
