"""Serving driver: batched decode over FDB-checkpointed weights.

  PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b --smoke \
      --requests 8 --new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core import FDBConfig
from repro.launch.cache import enable_compile_cache
from repro.models import lm
from repro.serve import Request, ServeEngine
from repro.train.checkpoint import FDBCheckpointer


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--new-tokens", type=int, default=16)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-len", type=int, default=128)
    p.add_argument("--run", default=None,
                   help="restore weights from this FDB checkpoint run")
    p.add_argument("--backend", default="daos")
    args = p.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    if args.run:
        ck = FDBCheckpointer(args.run, FDBConfig(backend=args.backend))
        step, params = ck.restore_latest(params)
        print(f"restored weights from run {args.run} step {step}")

    eng = ServeEngine(cfg, params, batch_slots=args.slots,
                      max_len=args.max_len)
    rng = np.random.default_rng(0)
    for rid in range(args.requests):
        plen = int(rng.integers(4, 16))
        eng.submit(Request(rid=rid,
                           prompt=rng.integers(0, cfg.vocab_size, plen,
                                               dtype=np.int32),
                           max_new_tokens=args.new_tokens))
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"served {len(done)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s) stats={eng.stats}")


if __name__ == "__main__":
    main()
