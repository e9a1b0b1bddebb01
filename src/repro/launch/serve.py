"""Batched serving driver (continuous batching demo).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
        --requests 8 --max-new 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from repro.configs.base import get_config, get_smoke_config
    from repro.models import model as model_lib
    from repro.serve.engine import ServeEngine

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    rng = jax.random.PRNGKey(args.seed)
    # bf16 weights and decode state: a full-width model fits one chip
    params = model_lib.init_params(cfg, rng, dtype=jnp.bfloat16)
    engine = ServeEngine(cfg, params, n_slots=args.slots,
                         max_seq=args.max_seq, dtype=jnp.bfloat16)

    rs = np.random.default_rng(args.seed)
    t0 = time.time()
    for _ in range(args.requests):
        prompt = rs.integers(0, cfg.vocab, size=args.prompt_len)
        engine.submit(prompt, max_new=args.max_new)
    results = engine.run_until_drained()
    dt = time.time() - t0
    total_new = sum(len(v) for v in results.values())
    print(f"served {len(results)} requests, {total_new} tokens "
          f"in {dt:.2f}s ({total_new / dt:.1f} tok/s)")
    for uid, toks in sorted(results.items())[:4]:
        print(f"  req {uid}: {toks[:8]}{'...' if len(toks) > 8 else ''}")


if __name__ == "__main__":
    main()
