"""Batched serving example (deliverable (b)): continuous batching with slot
reuse over a reduced gemma-2b — requests arrive mid-flight, finished slots
are re-admitted from the queue, greedy tokens stream back per request.

The engine's hot loop is fused on-device (``decode_many`` blocks with
on-device argmax, batched per-request prefill, donated decode state): host
work is O(1) per block of tokens, and block k+1 is dispatched from
device-resident carries before block k's token sync, so even that O(1)
accounting overlaps device compute.  The example drains the same queue
through the per-token oracle loop and the fused loop and checks that
their token streams are identical.  A
sampling wave mixes a temperature/top-k request (``SamplingParams``) with
a greedy neighbor in the same batch: sampling is reproducible per seed
and never perturbs greedy rows.  A final wave swaps in
``AdaptiveAdmission`` (occupancy-scaled prefill chunks,
shortest-prompt-first under burst) and checks streams are
policy-invariant.

See docs/serving.md for the engine lifecycle these demos exercise.

Run:  PYTHONPATH=src python examples/serve_batched.py
"""
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import get_smoke_config
from repro.models import model as model_lib
from repro.serve.engine import (AdaptiveAdmission, SamplingParams,
                                ServeEngine)


def serve_wave(engine: ServeEngine, prompts, max_new: int = 12):
    t0 = time.time()
    for p in prompts[:4]:
        engine.submit(p, max_new=max_new)
    # stream the first few blocks (fused) / steps (oracle)
    for step in range(3):
        out = (engine.decode_block_step(4) if engine.fused
               else engine.step())
        print(f"  burst {step}: {len(out)} slots emitted "
              f"{dict(list(out.items())[:2])}")

    # second wave arrives while the first is decoding
    for p in prompts[4:]:
        engine.submit(p, max_new=max_new)
    results = engine.run_until_drained()
    dt = time.time() - t0
    total = sum(len(v) for v in results.values())
    return results, total, dt


def warm_wave(engine: ServeEngine, prompts, max_new: int = 12):
    """A second identical wave on the now-warm engine: the steady-state
    serving throughput (the first wave's time is compile-dominated).
    Counts only this wave's requests — the drain also returns earlier
    finished requests still sitting in un-recycled slots."""
    uids = [engine.submit(p, max_new=max_new) for p in prompts]
    jax.block_until_ready(engine.state)
    t0 = time.time()
    results = engine.run_until_drained()
    jax.block_until_ready(engine.state)
    dt = time.time() - t0
    return sum(len(results[u]) for u in uids) / dt


def main() -> None:
    cfg = get_smoke_config("gemma-2b")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=8) for _ in range(8)]

    print("per-token oracle loop:")
    oracle = ServeEngine(cfg, params, n_slots=4, max_seq=96, fused=False)
    res_o, total_o, dt_o = serve_wave(oracle, prompts)
    tps_o = warm_wave(oracle, prompts)
    print(f"  {len(res_o)} requests / {total_o} tokens in {dt_o:.2f}s "
          f"(warm: {tps_o:.0f} tok/s)")

    print("fused double-buffered block loop (block k+1 before block k's "
          "sync):")
    fused = ServeEngine(cfg, params, n_slots=4, max_seq=96, fused=True,
                        decode_block=8)
    res_f, total_f, dt_f = serve_wave(fused, prompts)
    tps_f = warm_wave(fused, prompts)
    print(f"  {len(res_f)} requests / {total_f} tokens in {dt_f:.2f}s "
          f"(warm: {tps_f:.0f} tok/s)")

    assert res_o == res_f, "fused loop diverged from the per-token oracle"
    for uid, toks in sorted(res_f.items()):
        print(f"  req {uid}: {len(toks)} tokens, first 6 = {toks[:6]}")
    assert len(res_f) == 8 and all(len(v) == 12 for v in res_f.values())

    # per-request sampling: temperature/top-k ride alongside greedy
    # neighbors in the same fused block — the position-keyed PRNG makes a
    # sampled stream a pure function of (seed, position), so a re-run with
    # the same seed reproduces it exactly, at any decode_block size
    print("mixed sampling (per-request SamplingParams):")
    sp = SamplingParams(temperature=0.8, top_k=16, seed=7)
    streams = []
    for _ in range(2):
        uid_s = fused.submit(prompts[0], max_new=12, sampling=sp)
        uid_g = fused.submit(prompts[1], max_new=12)
        res = fused.run_until_drained()
        streams.append((res[uid_s], res[uid_g]))
    (samp_a, greedy_a), (samp_b, greedy_b) = streams
    assert samp_a == samp_b, "sampling must be reproducible per seed"
    # baseline: prompts[1]'s greedy stream from the first wave (second
    # submit), where every neighbor was greedy
    baseline = res_f[sorted(res_f)[1]]
    assert greedy_a == greedy_b == baseline, \
        "greedy rows must be unaffected by sampled neighbors"
    print(f"  sampled (T=0.8, top_k=16, seed=7): first 6 = {samp_a[:6]}")
    print(f"  greedy neighbor unchanged:          first 6 = {greedy_a[:6]}")

    # adaptive admission: occupancy-scaled prefill chunks + shortest-
    # prompt-first under burst — a scheduling policy, so every request's
    # stream is identical to the FIFO engine's
    print("adaptive admission (policy-invariant streams):")
    adaptive = ServeEngine(cfg, params, n_slots=4, max_seq=96, fused=True,
                           decode_block=8, prefill_chunk=8,
                           admission=AdaptiveAdmission(min_chunk=4,
                                                       max_chunk=16,
                                                       burst_depth=2))
    uids_a = [adaptive.submit(p, max_new=12) for p in prompts]
    res_a = adaptive.run_until_drained()
    # same prompts, same greedy math: the policy only reorders scheduling,
    # so every stream matches the oracle wave's (uids align by submit order)
    assert [res_a[u] for u in uids_a] == [res_o[u] for u in sorted(res_o)]
    print(f"  {len(uids_a)} requests drained under AdaptiveAdmission, "
          f"streams unchanged")


if __name__ == "__main__":
    main()
