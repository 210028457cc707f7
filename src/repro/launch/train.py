"""Training driver.

Examples:
  # CPU-runnable reduced config, few hundred steps, FDB checkpoints:
  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --smoke --steps 200 --batch 8 --seq 128 --backend daos

  # full config on real hardware (mesh picked up from the runtime):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --steps 1000
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.core import FDBConfig
from repro.data import SyntheticTokens
from repro.launch.cache import enable_compile_cache
from repro.train.checkpoint import FDBCheckpointer
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import Trainer, run_with_restarts


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true",
                   help="reduced config (CPU-runnable)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--backend", default="daos",
                   choices=["daos", "rados", "posix", "s3"])
    p.add_argument("--run", default="run0")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--async-ckpt", action="store_true")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    data = SyntheticTokens(cfg.vocab_size, args.seq, seed=args.seed)
    ck = FDBCheckpointer(args.run, FDBConfig(backend=args.backend),
                         asynchronous=args.async_ckpt)

    def batch_fn(step: int):
        b = data.batch(step, args.batch)
        out = {"tokens": jnp.asarray(b["tokens"]),
               "labels": jnp.asarray(b["labels"])}
        if cfg.family == "audio":
            out["frames"] = jax.random.normal(
                jax.random.PRNGKey(step), (args.batch, args.seq // 2,
                                           cfg.d_model)) * 0.02
        if cfg.family == "vlm":
            out["patches"] = jax.random.normal(
                jax.random.PRNGKey(step), (args.batch, cfg.n_patches,
                                           cfg.d_model)) * 0.02
        return out

    def make():
        return Trainer(cfg, None, AdamWConfig(lr=args.lr), checkpointer=ck,
                       ckpt_every=args.ckpt_every, batch_fn=batch_fn,
                       seed=args.seed)

    trainer = run_with_restarts(make, args.steps)
    last = trainer.metrics[-1] if trainer.metrics else {}
    print(f"done: step={trainer.step} loss={last.get('loss'):.4f} "
          f"ckpts={ck.available_steps()}")
    ck.close()


if __name__ == "__main__":
    main()
