"""Training launcher CLI.

On a real TPU fleet this process runs per-host under the standard multi-host
bootstrap (jax.distributed.initialize from TPU env vars) against the production
mesh; on this CPU box it runs the same code on a 1-device mesh with reduced
presets (see examples/train_100m.py for the preset definitions).

  python -m repro.launch.train --arch olmo-1b --steps 100 --smoke
  python -m repro.launch.train --arch qwen2.5-14b --shape train_4k   # TPU fleet
"""
from __future__ import annotations

import argparse

import jax

from ..configs import get_arch
from ..configs.base import SHAPES, ShapeConfig
from ..data.pipeline import SyntheticTokenDataset
from ..models.registry import build_model
from ..optim.optimizers import make_optimizer
from ..train.trainer import Trainer, TrainerConfig
from .compile_cache import enable_compile_cache
from .mesh import make_production_mesh, make_test_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true", help="reduced config, test mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default="results/train_run")
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
        mesh = make_test_mesh(1, 1)
        shape = ShapeConfig("smoke", seq_len=128, global_batch=4, kind="train")
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        shape = SHAPES[args.shape]
    model = build_model(cfg)
    opt = make_optimizer("adafactor" if cfg.moe is not None else "adamw")
    tcfg = TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=50, peak_lr=args.lr)
    trainer = Trainer(model, opt, mesh, shape, tcfg)
    ds = SyntheticTokenDataset(
        cfg.vocab,
        shape.seq_len,
        shape.global_batch,
        seed=0,
        n_frontend_tokens=cfg.n_frontend_tokens,
        frontend_dim=cfg.frontend_dim,
    )
    trainer.fit(jax.random.PRNGKey(0), ds, n_steps=args.steps)
    steps = [e for e in trainer.log if e["event"] == "step"]
    print(
        f"{cfg.name}: {len(steps)} steps, final loss {steps[-1]['loss']:.3f}, "
        f"restarts={trainer.restarts} stragglers={trainer.stragglers}"
    )


if __name__ == "__main__":
    main()
