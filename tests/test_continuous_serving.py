"""Continuous batching under real traffic (ISSUE 6).

Admission edge cases (empty / length-1 / over-long prompts), chunked
prefill interleaved with decode blocks, on-device EOS + per-slot block
truncation, per-request temperature/top-k sampling, popcount row masking,
and the staggered-traffic equivalence property: however arrivals land,
the chunked fused engine emits exactly the per-token oracle's streams.
"""
import dataclasses
import functools

from hypothesis import given, settings, strategies as st
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ArchConfig, SparsityConfig, get_smoke_config
from repro.models import model as model_lib
from repro.serve.engine import (SamplingParams, ServeEngine,
                                decode_exec_config)


def _tiny_cfg() -> ArchConfig:
    """1-layer edge-class dense config — fast enough for property loops."""
    return ArchConfig(name="serve-tiny", family="dense", n_layers=1,
                      d_model=32, n_heads=4, n_kv_heads=4, d_ff=64,
                      vocab=128, norm="rmsnorm")


@functools.lru_cache(maxsize=None)
def _tiny():
    cfg = _tiny_cfg()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    return cfg, params


@functools.lru_cache(maxsize=None)
def _family(arch: str):
    cfg = get_smoke_config(arch)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    return cfg, params


def _prompt(rng, n, vocab=128):
    return rng.integers(0, vocab, size=n).astype(np.int32)


# ---------------------------------------------------------------------------
# admission edge cases
# ---------------------------------------------------------------------------

def test_submit_rejects_empty_prompt():
    cfg, params = _tiny()
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=32)
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit(np.asarray([], np.int32))
    with pytest.raises(ValueError, match="1-D"):
        eng.submit(np.asarray([[3, 5]], np.int32))
    assert not eng.queue                 # nothing half-enqueued


def test_submit_rejects_prompt_overflowing_max_seq():
    cfg, params = _tiny()
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=16)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(np.arange(16, dtype=np.int32))   # needs 17 positions
    # the boundary fits: 15 prompt tokens + 1 generated = 16 positions
    uid = eng.submit(np.arange(15, dtype=np.int32), max_new=8)
    res = eng.run_until_drained()
    assert len(res[uid]) == 1            # one token, then the wall → done
    assert all(s.req is None or s.req.done for s in eng.slots)


def test_length1_prompt_is_prefill_free_admit():
    """A 1-token prompt has an empty feed: the admit only zero-resets the
    slot row, and decode starts from the prompt token itself — identical
    across the fused and oracle paths, including into a recycled slot."""
    cfg, params = _tiny()
    streams = {}
    for fused in (True, False):
        eng = ServeEngine(cfg, params, n_slots=1, max_seq=32, fused=fused)
        # dirty the slot with a long request first, then recycle it
        eng.submit(_prompt(np.random.default_rng(1), 9), max_new=4)
        u = eng.submit(np.asarray([5], np.int32), max_new=6)
        res = eng.run_until_drained()
        streams[fused] = res[u]
    assert streams[True] == streams[False]
    # a fresh engine serving only the length-1 prompt emits the same stream
    # — the recycled slot leaked nothing into it
    fresh = ServeEngine(cfg, params, n_slots=1, max_seq=32)
    u = fresh.submit(np.asarray([5], np.int32), max_new=6)
    assert fresh.run_until_drained()[u] == streams[True]


def test_max_seq_wall_marks_done_mid_block():
    """A request whose budget exceeds the sequence room stops at the
    ``max_seq - 1`` wall, is marked done (never silently truncated into a
    live slot), and the fused path credits exactly the oracle's tokens."""
    cfg, params = _tiny()
    prompt = _prompt(np.random.default_rng(2), 6)
    streams = {}
    for fused in (True, False):
        eng = ServeEngine(cfg, params, n_slots=2, max_seq=16,
                          decode_block=16, fused=fused)
        u = eng.submit(prompt, max_new=64)       # budget >> sequence room
        res = eng.run_until_drained()
        streams[fused] = res[u]
        assert all(s.req is None or s.req.done for s in eng.slots)
    # feed = 5 positions, wall at pos 15 → exactly 10 generated tokens
    assert len(streams[True]) == (16 - 1) - (len(prompt) - 1)
    assert streams[True] == streams[False]


# ---------------------------------------------------------------------------
# chunked prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["tiny", "mamba2-1.3b"])
def test_chunked_prefill_matches_whole_prompt(arch):
    """Feeding a prompt in chunks lands bit-identical state: the decoded
    stream matches the whole-prompt prefill on both an attention (KV
    scatter) and a recurrent (SSM running-state) family."""
    cfg, params = _tiny() if arch == "tiny" else _family(arch)
    prompt = _prompt(np.random.default_rng(3), 21, vocab=cfg.vocab)
    streams = {}
    for chunk in (None, 4, 8):
        eng = ServeEngine(cfg, params, n_slots=2, max_seq=64,
                          prefill_chunk=chunk)
        u = eng.submit(prompt, max_new=6)
        streams[chunk] = eng.run_until_drained()[u]
    assert streams[4] == streams[None]
    assert streams[8] == streams[None]


def test_chunked_prefill_interleaves_with_live_decode():
    """While a long prompt is mid-prefill, live slots keep decoding: each
    ``decode_block_step`` tick feeds one chunk AND runs a block, so the
    live request makes progress before the long admit completes — and the
    mid-prefill slot's state survives those interleaved blocks (its stream
    matches an engine that served it alone)."""
    cfg, params = _tiny()
    rng = np.random.default_rng(4)
    short, long = _prompt(rng, 3), _prompt(rng, 40)

    eng = ServeEngine(cfg, params, n_slots=2, max_seq=64, prefill_chunk=4,
                      decode_block=4)
    u_short = eng.submit(short, max_new=16)
    eng.decode_block_step()              # admit short, decode one block
    u_long = eng.submit(long, max_new=6)
    eng.decode_block_step()              # admit long: first chunk only
    i_long = next(i for i, s in enumerate(eng.slots)
                  if s.req is not None and s.req.uid == u_long)
    assert 0 < eng.slots[i_long].prefill_cursor < len(long) - 1
    short_progress = len(eng.slots[0 if i_long else 1].req.out)
    assert short_progress > 0            # live decode advanced mid-prefill
    res = eng.run_until_drained()

    for u, prompt, max_new in ((u_short, short, 16), (u_long, long, 6)):
        solo = ServeEngine(cfg, params, n_slots=2, max_seq=64)
        su = solo.submit(prompt, max_new=max_new)
        assert solo.run_until_drained()[su] == res[u]


# ---------------------------------------------------------------------------
# on-device EOS
# ---------------------------------------------------------------------------

def _greedy_stream(cfg, params, prompt, max_new):
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=64)
    u = eng.submit(prompt, max_new=max_new)
    return eng.run_until_drained()[u]


def test_eos_truncates_on_device_fused_equals_oracle():
    cfg, params = _tiny()
    prompt = _prompt(np.random.default_rng(5), 7)
    ref = _greedy_stream(cfg, params, prompt, 12)
    eos = ref[4]                          # appears mid-stream
    cut = ref.index(eos) + 1              # first occurrence ends the stream
    streams = {}
    for fused in (True, False):
        eng = ServeEngine(cfg, params, n_slots=2, max_seq=64,
                          eos_id=int(eos), decode_block=16, fused=fused)
        u = eng.submit(prompt, max_new=12)
        res = eng.run_until_drained()
        streams[fused] = res[u]
        assert all(s.req is None or s.req.done for s in eng.slots)
    assert streams[True] == ref[:cut]     # truncated at (and including) EOS
    assert streams[True] == streams[False]


def test_eos_does_not_shrink_other_slots_block():
    """One early-stopping request no longer drags the block length down:
    ``_block_len`` sizes by the max remaining budget and the stopped row
    rides the rest of the block as inactive filler — the long request
    still gets its full greedy stream."""
    cfg, params = _tiny()
    rng = np.random.default_rng(6)
    p_short, p_long = _prompt(rng, 5), _prompt(rng, 4)
    ref_long = _greedy_stream(cfg, params, p_long, 24)
    ref_short = _greedy_stream(cfg, params, p_short, 24)
    eos = ref_short[1]                    # short stops early
    cut = ref_short.index(eos) + 1
    assert eos not in ref_long            # long must not be cut by it
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=64, eos_id=int(eos),
                      decode_block=16)
    u_s = eng.submit(p_short, max_new=24)
    u_l = eng.submit(p_long, max_new=24)
    eng._admit()
    # both slots live in the same first block: max-based sizing runs the
    # full 16 steps even though the short request stops after `cut`
    assert eng._block_len([0, 1], 16) == 16
    res = eng.run_until_drained()
    assert res[u_s] == ref_short[:cut]
    assert res[u_l] == ref_long


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def test_sampling_reproducible_and_block_invariant():
    """Position-keyed PRNG: a sampled stream is a pure function of (seed,
    position) — identical across runs, across fused block sizes, and
    between the fused and per-token paths."""
    cfg, params = _tiny()
    prompt = _prompt(np.random.default_rng(7), 6)
    sp = SamplingParams(temperature=0.9, top_k=12, seed=123)
    streams = []
    for fused, block in ((True, 16), (True, 4), (False, 16), (True, 16)):
        eng = ServeEngine(cfg, params, n_slots=2, max_seq=64, fused=fused,
                          decode_block=block)
        u = eng.submit(prompt, max_new=10, sampling=sp)
        streams.append(eng.run_until_drained()[u])
    assert all(s == streams[0] for s in streams[1:])
    # a different seed draws a different stream (vocab 128, 10 steps —
    # collision odds are negligible)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=64)
    u = eng.submit(prompt, max_new=10,
                   sampling=dataclasses.replace(sp, seed=124))
    assert eng.run_until_drained()[u] != streams[0]


def test_greedy_rows_unaffected_by_sampled_neighbours():
    """A mixed batch — one greedy slot, one sampled slot — leaves the
    greedy stream exactly the all-greedy engine's, and explicit
    ``temperature=0`` is the same as the default ``sampling=None``."""
    cfg, params = _tiny()
    rng = np.random.default_rng(8)
    p_greedy, p_sampled = _prompt(rng, 5), _prompt(rng, 5)
    ref = _greedy_stream(cfg, params, p_greedy, 10)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=64)
    u_g = eng.submit(p_greedy, max_new=10,
                     sampling=SamplingParams(temperature=0.0))
    u_s = eng.submit(p_sampled, max_new=10,
                     sampling=SamplingParams(temperature=1.2, seed=7))
    res = eng.run_until_drained()
    assert res[u_g] == ref


def test_top_k_one_is_greedy():
    """top_k=1 collapses the sampled distribution to argmax regardless of
    temperature — a direct check on the threshold masking."""
    cfg, params = _tiny()
    prompt = _prompt(np.random.default_rng(9), 6)
    ref = _greedy_stream(cfg, params, prompt, 8)
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=64)
    u = eng.submit(prompt, max_new=8,
                   sampling=SamplingParams(temperature=2.0, top_k=1,
                                           seed=99))
    assert eng.run_until_drained()[u] == ref


# ---------------------------------------------------------------------------
# popcount row masking + cache hygiene
# ---------------------------------------------------------------------------

def _density_run(cfg, params, n_slots):
    sp_cfg = dataclasses.replace(cfg, sparsity=SparsityConfig(
        weight_sparsity=0.5, activation_threshold=0.1))
    ec = decode_exec_config(sp_cfg, n_slots=n_slots, collect_stats=True)
    eng = ServeEngine(cfg, params, n_slots=n_slots, max_seq=32, exec_cfg=ec)
    eng.submit(np.asarray([3, 5, 7], np.int32), max_new=6)
    eng.run_until_drained()
    return eng.activation_densities()


@pytest.mark.slow
def test_popcounts_mask_dead_slot_filler_rows():
    """1 live slot of 4 measures the same per-site activation densities as
    a 1-slot engine: dead slots' token-0 filler rows no longer skew the
    recalibration signal at low occupancy."""
    cfg, params = _family("stablelm-1.6b")
    d1 = _density_run(cfg, params, n_slots=1)
    d4 = _density_run(cfg, params, n_slots=4)
    assert d1 and set(d1) == set(d4)
    for site in d1:
        assert d4[site] == pytest.approx(d1[site], rel=1e-5), site


@pytest.mark.slow
def test_recalibrate_clears_mask_cache():
    """The rebuild path drops every per-engine cache: ``_mask_cache``
    entries are device arrays handed to the retired executables, and the
    recompiled engine must not reuse them."""
    cfg, params = _family("stablelm-1.6b")
    sp_cfg = dataclasses.replace(cfg, sparsity=SparsityConfig(
        weight_sparsity=0.5, activation_threshold=0.1))
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params,
                            collect_stats=True)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=32, exec_cfg=ec)
    eng.submit(np.asarray([3, 5, 7], np.int32), max_new=6)
    eng.run_until_drained()
    assert eng._mask_cache                   # populated by the fused run
    assert eng.maybe_recalibrate(drift_threshold=0.0) is not None
    assert not eng._mask_cache               # cleared with the rebuild
    uid = eng.submit(np.asarray([2, 4, 6], np.int32), max_new=4)
    assert len(eng.run_until_drained()[uid]) == 4


# ---------------------------------------------------------------------------
# staggered-traffic equivalence (property)
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_staggered_arrivals_match_oracle(seed):
    """However requests arrive — random lengths, random budgets, random
    submission ticks — the chunked-prefill fused engine with on-device EOS
    emits exactly the per-token oracle's streams.  Masked state commits
    keep slots independent, so arrival timing reorders the schedule but
    never the math."""
    cfg, params = _tiny()
    rng = np.random.default_rng(seed)
    n_req = int(rng.integers(3, 7))
    reqs = [(_prompt(rng, int(rng.integers(1, 24))),
             int(rng.integers(1, 13))) for _ in range(n_req)]
    arrival_tick = sorted(int(rng.integers(0, 6)) for _ in range(n_req))

    eng = ServeEngine(cfg, params, n_slots=2, max_seq=64, eos_id=7,
                      prefill_chunk=4, decode_block=4)
    uids, k = [], 0
    # a request can finish inside a tick and have its slot recycled before
    # the final drain — hold the Request objects so no stream is lost
    req_by_uid = {}
    for tick in range(max(arrival_tick) + 1):
        while k < n_req and arrival_tick[k] <= tick:
            p, mn = reqs[k]
            uids.append(eng.submit(p, max_new=mn))
            k += 1
        eng.decode_block_step()
        for s in eng.slots:
            if s.req is not None:
                req_by_uid[s.req.uid] = s.req
    res = eng.run_until_drained()
    assert all(r.done for r in req_by_uid.values())
    streams = [req_by_uid[u].out if u in req_by_uid else res[u]
               for u in uids]

    oracle = ServeEngine(cfg, params, n_slots=2, max_seq=64, eos_id=7,
                         fused=False)
    ouids = [oracle.submit(p, max_new=mn) for p, mn in reqs]
    ores = oracle.run_until_drained()
    assert streams == [ores[u] for u in ouids]
