#!/usr/bin/env python3
"""Bring-up smoke: the serving path on one TPU chip at StableLM-2-1.6B width.

    python3 chip_smoke.py [--seed N]          # one chip: phases A and B
    python3 chip_smoke.py --four-chips        # four chips: the mesh trainer

Phase A serves 8 seeded requests through the dense engine at the model's
full width and depth (24 layers, bf16 weights and decode state), through
the entry points ``launch/serve.py`` drives (``ServeEngine.submit`` then
``run_until_drained``), and checks the fused streams of the first two
requests token for token against the per-token oracle (``fused=False``).

Phase B magnitude-prunes the same weights to 50 %, compiles the weight
plan (``decode_exec_config``) and serves the same requests through the
block-sparse Pallas kernels.  It checks that the decode executable holds
the kernels, and compares prefill and first-decode logits with an engine
on the same plan that takes the masked XLA path (``use_pallas=False``).

``--four-chips`` runs only the trainer's mesh path: 3 steps at
StableLM-2-1.6B width cut to 4 layers on a (data 2, model 2) mesh over
four chips, compared with the same steps on a (1, 1) mesh over one chip.

The script exits non-zero, printing no result, where JAX finds no TPU or
any check fails.  Times printed on the way are the device's host-clock
seconds, bring-up observations rather than benchmark figures.  The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MODEL = "stablelm-1.6b"
N_SLOTS, MAX_SEQ, PREFILL_CHUNK = 8, 1024, 128
N_REQUESTS, MAX_NEW, N_ORACLE = 8, 32, 2
PROMPT_LENS = (32, 512)
SPARSITY = 0.5
# Pallas against XLA on the same plan: the two differ only in f32
# accumulation order, whose bf16 roundings compound over 24 layers.
# Allowed: 8 bf16 ulps (2^-8 each) of the largest reference logit.
LOGIT_RTOL = 2.0 ** -5
# mesh against one chip, bf16 weights: the same steps with reductions
# split across devices
LOSS_RTOL = 1e-2
MESH_LAYERS, MESH_STEPS, MESH_SEQ, MESH_BATCH = 4, 3, 128, 8


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


def make_prompts(vocab: int, seed: int, n: int = N_REQUESTS,
                 lens=PROMPT_LENS) -> list:
    """``n`` seeded prompts whose lengths span ``lens`` evenly."""
    rs = np.random.default_rng(seed)
    lengths = rs.permutation(np.linspace(*lens, n).astype(int))
    return [rs.integers(0, vocab, size=int(n_tok)).astype(np.int32)
            for n_tok in lengths]


def serve(engine, prompts, max_new: int):
    """Submit ``prompts``, drain, and check every request finished with
    exactly ``max_new`` tokens.  Returns (streams, drain seconds)."""
    import jax
    uids = [engine.submit(p, max_new=max_new) for p in prompts]
    t0 = time.perf_counter()
    results = engine.run_until_drained()
    jax.block_until_ready(engine.state)
    seconds = time.perf_counter() - t0
    streams = []
    for uid in uids:
        status, toks = engine.status(uid), results.get(uid, [])
        check(status == "done" and len(toks) == max_new,
              f"request {uid}: status {status}, {len(toks)} of {max_new} "
              f"tokens")
        streams.append(list(toks))
    return streams, seconds


def phase_dense(cfg, params, prompts, *, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                prefill_chunk=PREFILL_CHUNK, max_new=MAX_NEW,
                n_oracle=N_ORACLE):
    """Phase A: dense serving, fused streams against the per-token oracle."""
    import jax.numpy as jnp
    from repro.serve.engine import ServeEngine

    kw = dict(n_slots=n_slots, max_seq=max_seq, prefill_chunk=prefill_chunk,
              dtype=jnp.bfloat16)
    t0 = time.perf_counter()
    engine = ServeEngine(cfg, params, **kw)
    engine.warmup()
    setup = time.perf_counter() - t0
    streams, drain = serve(engine, prompts, max_new)
    n_tok = sum(map(len, streams))
    log(f"phase A (dense): set-up and compile {setup:.3f} s on the device; "
        f"drained {len(prompts)} requests, {n_tok} tokens in {drain:.3f} s")
    del engine
    gc.collect()

    oracle = ServeEngine(cfg, params, fused=False, **kw)
    expected, seconds = serve(oracle, prompts[:n_oracle], max_new)
    del oracle
    gc.collect()
    for i, (got, want) in enumerate(zip(streams, expected)):
        check(got == want, f"request {i}: fused stream {got} differs from "
              f"the per-token oracle {want}")
    log(f"phase A: fused streams equal the per-token oracle for "
        f"{n_oracle} of {len(prompts)} requests (oracle drain {seconds:.3f} "
        f"s, compile included)")
    return streams


def prune_params(params, sparsity: float = SPARSITY):
    """Magnitude-prune every stacked matmul weight of ``params``."""
    import jax
    from repro.core.sparsity import prune_stacked_magnitude
    stack = jax.tree.map(lambda leaf: prune_stacked_magnitude(leaf, sparsity),
                         params["stack"])
    return {**params, "stack": stack}


def teacher_forced_fn(cfg, exec_cfg):
    """Jitted ``(params, seqs (B, L), lengths (B,), want (B, W)) -> logits
    (B, W, V)``: the logits at positions ``want`` of each row of ``seqs``,
    fed one token per step through ``masked_decode_step`` (the step the
    engine's prefill and decode both run) under ``exec_cfg``, into a
    cache of L positions."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models import model as model_lib

    def run(p, seqs, lengths, want):
        b, n = seqs.shape
        with ops.exec_config(exec_cfg):
            state = model_lib.init_decode_state(cfg, b, n,
                                                dtype=jnp.bfloat16)
            out = jnp.zeros((b, want.shape[1], cfg.vocab), jnp.float32)

            def step(carry, t):
                st, out = carry
                active = t < lengths
                tok = jnp.where(active, seqs[:, t], 0)[:, None]
                logits, st = model_lib.masked_decode_step(
                    p, cfg, tok, st, jnp.full((b,), t, jnp.int32), active)
                lg = logits[:, 0, :].astype(jnp.float32)
                out = jnp.where((want == t)[:, :, None], lg[:, None, :], out)
                return (st, out), None

            (_, out), _ = jax.lax.scan(step, (state, out),
                                       jnp.arange(n, dtype=jnp.int32))
        return out

    return jax.jit(run)


def phase_flexnn(cfg, pruned, prompts, *, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                 prefill_chunk=PREFILL_CHUNK, max_new=MAX_NEW,
                 interpret: bool = False):
    """Phase B: planned block-sparse Pallas serving against the same plan
    on the XLA path.  ``interpret`` runs the kernels in interpret mode, for
    a rehearsal on the CPU at a small size; the chip run never sets it."""
    import jax.numpy as jnp
    from repro.configs.base import SparsityConfig
    from repro.serve.engine import ServeEngine, decode_exec_config

    cfg = dataclasses.replace(cfg, sparsity=SparsityConfig(
        weight_sparsity=SPARSITY))
    kw = dict(n_slots=n_slots, max_seq=max_seq, prefill_chunk=prefill_chunk,
              dtype=jnp.bfloat16)
    t0 = time.perf_counter()
    exec_cfg = decode_exec_config(cfg, n_slots, params=pruned,
                                  use_pallas=True, interpret=interpret)
    plan_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    engine = ServeEngine(cfg, pruned, exec_cfg=exec_cfg, **kw)
    engine.warmup()
    setup = time.perf_counter() - t0
    if not interpret:
        zero = np.zeros((n_slots,), np.int32)
        text = engine._decode_many.lower(
            engine._exec_params, engine.state, zero, zero,
            np.zeros((n_slots,), bool), zero, None, None, None, 1
        ).compile().as_text()
        n_kernels = text.count("tpu_custom_call")
        check(n_kernels > 0, "the planned decode executable holds no Pallas "
              "kernel (no tpu_custom_call)")
        log(f"phase B: decode executable holds {n_kernels} tpu_custom_call "
            f"sites")
    got, drain = serve(engine, prompts, max_new)
    log(f"phase B (planned, Pallas): plan compile {plan_s:.3f} s on the "
        f"host; set-up and compile {setup:.3f} s on the device; drained "
        f"{len(prompts)} requests, {sum(map(len, got))} tokens in "
        f"{drain:.3f} s")
    del engine
    gc.collect()

    ref_cfg = dataclasses.replace(exec_cfg, use_pallas=False,
                                  interpret=False)
    ref_engine = ServeEngine(cfg, pruned, exec_cfg=ref_cfg,
                             verify_plan=False, **kw)
    ref, ref_drain = serve(ref_engine, prompts, max_new)
    del ref_engine
    gc.collect()
    log(f"phase B (planned, XLA reference): drained in {ref_drain:.3f} s, "
        f"compile included")

    # logits along each reference stream: the prefill logits (position
    # P-1), the first decode logits (P) and, where the Pallas stream
    # leaves the reference, the logits at that step
    rows, want, flips = [], [], []
    for prompt, r, g in zip(prompts, ref, got):
        p = len(prompt)
        j = next((k for k in range(max_new) if r[k] != g[k]), None)
        flips.append(j)
        rows.append(list(prompt) + r)
        want.append([p - 1, p, p - 1 + (j if j is not None else 0)])
    want = np.asarray(want, np.int32)
    lengths = want.max(axis=1) + 1
    seqs = np.zeros((len(rows), int(lengths.max())), np.int32)
    for i, row in enumerate(rows):
        seqs[i, :lengths[i]] = row[:lengths[i]]
    attached = exec_cfg.plan.attach(pruned, verify=False)
    lg, lg_ref = (np.asarray(teacher_forced_fn(cfg, c)(
        attached, seqs, lengths, want)) for c in (exec_cfg, ref_cfg))
    check(bool(np.isfinite(lg).all() and np.isfinite(lg_ref).all()),
          "non-finite logits")
    scale = float(np.abs(lg_ref[:, :2]).max())
    tol = LOGIT_RTOL * scale
    diff = float(np.abs(lg[:, :2] - lg_ref[:, :2]).max())
    log(f"phase B: prefill and first-decode logits, Pallas against XLA: "
        f"max |diff| {diff:.6g}, tolerance {tol:.6g} "
        f"({LOGIT_RTOL:g} x max |logit| {scale:.6g})")
    check(diff <= tol, f"Pallas logits differ from XLA by {diff:.6g} > "
          f"{tol:.6g}")
    for i, j in enumerate(flips):
        if j is None:
            continue
        gap = float(lg_ref[i, 2, ref[i][j]] - lg_ref[i, 2, got[i][j]])
        log(f"phase B: request {i} leaves the reference at token {j} "
            f"(reference logit gap {gap:.6g})")
        check(abs(gap) <= tol, f"request {i}: token {j} flipped across a "
              f"logit gap {gap:.6g} > {tol:.6g}")
    agree = sum(max_new if j is None else j for j in flips)
    log(f"phase B: greedy streams agree on {agree} of {max_new * len(ref)} "
        f"tokens; {flips.count(None)} of {len(ref)} requests identical")


def phase_mesh_train(cfg, devices, *, steps=MESH_STEPS, seq=MESH_SEQ,
                     batch=MESH_BATCH, seed=0):
    """The trainer's mesh path (``launch/train.py``): ``steps`` steps on a
    (data, model 2) mesh over ``devices`` against a (1, 1) mesh over the
    first device.  Returns the two loss lists."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import DataConfig, TokenPipeline
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.partition import make_rules
    from repro.train.optimizer import AdamWConfig
    from repro.train.trainer import Trainer, TrainerConfig

    shape = ShapeConfig(name="smoke", kind="train", seq_len=seq,
                        global_batch=batch, n_micro=1, remat="none",
                        loss_chunk=min(128, seq), attn_chunk=min(128, seq))

    def train(devs, model):
        mesh = make_host_mesh(model=model, devices=devs)
        rules = make_rules(mesh, kind="train", n_heads=cfg.n_heads,
                           n_kv_heads=cfg.n_kv_heads)
        pipeline = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                            global_batch=batch, seed=seed))
        opt = AdamWConfig(lr=3e-4, warmup_steps=max(steps // 10, 1),
                          total_steps=steps)
        trainer = Trainer(cfg, shape, opt,
                          TrainerConfig(steps=steps, log_every=1, seed=seed),
                          mesh=mesh, rules=rules, pipeline=pipeline,
                          dtype=jnp.bfloat16)
        t0 = time.perf_counter()
        records = trainer.run()
        seconds = time.perf_counter() - t0
        return trainer, [r["loss"] for r in records], seconds

    trainer, losses, seconds = train(devices, 2)
    leaves = jax.tree.leaves(trainer.params)
    spans = {len(leaf.sharding.device_set) for leaf in leaves}
    n_split = sum(not leaf.sharding.is_fully_replicated for leaf in leaves)
    check(spans == {len(devices)} and n_split > 0,
          f"mesh parameters span {spans} devices, {n_split} leaves split")
    log(f"mesh ({len(devices) // 2}, 2): losses {losses} in {seconds:.3f} s, "
        f"compile included; {n_split} of {len(leaves)} parameter leaves "
        f"split over {len(devices)} devices")
    del trainer, leaves
    gc.collect()

    trainer, ref, seconds = train(devices[:1], 1)
    del trainer
    gc.collect()
    log(f"mesh (1, 1): losses {ref} in {seconds:.3f} s, compile included")
    check(len(losses) == len(ref) == steps
          and all(np.isfinite(losses + ref)), "missing or non-finite loss")
    worst = max(abs(a - b) / abs(b) for a, b in zip(losses, ref))
    log(f"mesh against one chip: worst relative loss difference {worst:.6g}, "
        f"tolerance {LOSS_RTOL:g}")
    check(worst <= LOSS_RTOL, f"mesh losses {losses} differ from one-chip "
          f"losses {ref} by {worst:.6g} > {LOSS_RTOL:g}")
    return losses, ref


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the trainer's mesh path, on four chips")
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache
    cache_dir = setup_compile_cache()
    import jax
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.models import model as model_lib

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(devices)}; compile cache {cache_dir}")
    cfg = get_config(MODEL)

    if args.four_chips:
        check(len(devices) == 4, f"--four-chips needs 4 devices, JAX found "
              f"{len(devices)}")
        phase_mesh_train(dataclasses.replace(cfg, n_layers=MESH_LAYERS),
                         devices, seed=args.seed)
    else:
        t0 = time.perf_counter()
        params = model_lib.init_params(cfg, jax.random.PRNGKey(args.seed),
                                       dtype=jnp.bfloat16)
        jax.block_until_ready(params)
        log(f"{MODEL}: {cfg.n_layers} layers, d_model {cfg.d_model}, vocab "
            f"{cfg.vocab}; bf16 weights made in "
            f"{time.perf_counter() - t0:.3f} s")
        prompts = make_prompts(cfg.vocab, args.seed)
        phase_dense(cfg, params, prompts)
        t0 = time.perf_counter()
        pruned = prune_params(params)
        del params
        gc.collect()
        log(f"phase B: pruned to {SPARSITY:g} in "
            f"{time.perf_counter() - t0:.3f} s on the host")
        phase_flexnn(cfg, pruned, prompts)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
