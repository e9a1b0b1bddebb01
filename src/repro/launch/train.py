"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

``--smoke`` selects the reduced config of the same family (CPU-runnable);
omit it on real hardware for the full published dims.  The trainer provides
auto-resume, atomic keep-k checkpoints, and the step-time watchdog
(straggler mitigation hook) — see ``train.trainer``.
"""
from __future__ import annotations

import argparse
import dataclasses

import jax


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--remat", default="none",
                    choices=["none", "dots", "full"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--model-shards", type=int, default=1,
                    help="TP degree over available devices")
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from repro.configs.base import ShapeConfig, get_config, get_smoke_config
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.partition import make_rules
    from repro.train.optimizer import AdamWConfig
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    shape = ShapeConfig(name="cli", kind="train", seq_len=args.seq,
                        global_batch=args.batch, n_micro=args.n_micro,
                        remat=args.remat, loss_chunk=min(128, args.seq),
                        attn_chunk=min(128, args.seq))

    mesh = rules = None
    if args.model_shards > 1 or len(jax.devices()) > 1:
        mesh = make_host_mesh(model=args.model_shards)
        rules = make_rules(mesh, kind="train", n_heads=cfg.n_heads,
                           n_kv_heads=cfg.n_kv_heads)

    pipeline = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                        global_batch=args.batch,
                                        seed=args.seed))
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                      total_steps=args.steps)
    tcfg = TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every,
                         log_every=args.log_every, seed=args.seed)
    trainer = Trainer(cfg, shape, opt, tcfg, mesh=mesh, rules=rules,
                      pipeline=pipeline)
    log = trainer.run()
    print(f"done: {len(log)} steps, "
          f"final loss {log[-1]['loss']:.4f}" if log else "no steps run")


if __name__ == "__main__":
    main()
