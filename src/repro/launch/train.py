"""Training launcher.

Runs the jitted train step on one device: by default the reduced smoke
config, with ``--full`` the config as published. No mesh is built here; the
sharding rules in sharding.py are exercised only by the dry-run compile
(launch/dryrun.py).

  PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b --steps 50 \
      --batch 8 --seq 64 [--smoke/--full] [--n-micro 2]
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_variant
from repro.data import make_batch_iter
from repro.models import init_params
from repro.train import OptConfig, train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--full", action="store_true",
                    help="full-size config (TPU only)")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = smoke_variant(cfg)
    print(f"training {cfg.name}: {cfg.param_count()/1e6:.1f}M params "
          f"({cfg.param_count(True)/1e6:.1f}M active)")
    params = init_params(jax.random.PRNGKey(0), cfg)
    batches = make_batch_iter(cfg.vocab_size, args.seq, args.batch)
    opt = OptConfig(lr=args.lr, total_steps=args.steps,
                    warmup_steps=max(1, args.steps // 10))
    params, history = train(params, cfg, batches, args.steps, opt,
                            n_micro=args.n_micro)
    if args.ckpt:
        from repro.checkpoint import save_checkpoint
        save_checkpoint(args.ckpt, params, args.steps,
                        meta={"arch": cfg.name})
        print(f"checkpoint saved to {args.ckpt}")


if __name__ == "__main__":
    main()
