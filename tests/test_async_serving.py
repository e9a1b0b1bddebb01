"""Double-buffered dispatch + admission policies.

The engine dispatches block k+1 from device-resident carries *before*
syncing block k's token array (deferring host accounting by one block),
and admission is a pluggable policy.  These tests pin the contract:
async ≡ per-token oracle token-for-token — across state families, greedy
and sampled, under randomized staggered arrivals — plus the
occupancy-change rule, EOS inside a deferred block, ``flush()``
semantics, the device-carry launch fast path, the drain running the same
dispatches as the serving tick, and the ``AdaptiveAdmission`` policy
surface.
"""
import dataclasses
import functools
from types import SimpleNamespace

from hypothesis import given, settings, strategies as st
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ArchConfig, SparsityConfig, get_smoke_config
from repro.models import model as model_lib
from repro.serve.engine import (AdaptiveAdmission, AdmissionPolicy,
                                FIFOAdmission, SamplingParams, ServeEngine,
                                decode_exec_config)


def _tiny_cfg() -> ArchConfig:
    return ArchConfig(name="async-tiny", family="dense", n_layers=1,
                      d_model=32, n_heads=4, n_kv_heads=4, d_ff=64,
                      vocab=128, norm="rmsnorm")


@functools.lru_cache(maxsize=None)
def _tiny():
    cfg = _tiny_cfg()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    return cfg, params


def _prompt(rng, n, vocab=128):
    return rng.integers(0, vocab, size=n).astype(np.int32)


_PROMPTS = [np.array([3, 5, 7], np.int32), np.array([2, 4], np.int32),
            np.array([9, 1, 8], np.int32), np.array([6], np.int32)]


def _drain(cfg, params, *, fused=True, exec_cfg=None,
           prompts=_PROMPTS, max_new=6, n_slots=2, decode_block=4,
           sampling=None, **kw):
    eng = ServeEngine(cfg, params, n_slots=n_slots, max_seq=48,
                      exec_cfg=exec_cfg, fused=fused,
                      decode_block=decode_block, **kw)
    for p in prompts:
        eng.submit(p, max_new=max_new, sampling=sampling)
    res = eng.run_until_drained()
    assert not eng._inflight              # drain leaves nothing pending
    return res


# ---------------------------------------------------------------------------
# async ≡ oracle across families
# ---------------------------------------------------------------------------

def test_async_matches_sync_and_oracle_dense():
    cfg, params = _tiny()
    oracle = _drain(cfg, params, fused=False)
    assert _drain(cfg, params) == oracle


def test_async_matches_sync_planned_sparse():
    cfg, params = _tiny()
    sp_cfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(weight_sparsity=0.5,
                                     activation_threshold=0.1))
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params)
    assert ec.plan is not None and ec.plan.entries
    async_ = _drain(cfg, params, exec_cfg=ec)
    assert async_ == _drain(cfg, params, exec_cfg=ec, fused=False)


@pytest.mark.slow
def test_async_matches_sync_moe():
    cfg, params, _ = _moe_setup()
    async_ = _drain(cfg, params, max_new=4)
    assert async_ == _drain(cfg, params, fused=False, max_new=4)


def test_async_sampled_streams_match_sync():
    """Sampling is position-keyed, so deferred accounting cannot perturb
    it: async and per-token oracle sampled streams are identical per
    seed."""
    cfg, params = _tiny()
    sp = SamplingParams(temperature=0.9, top_k=12, seed=11)
    async_ = _drain(cfg, params, sampling=sp)
    assert async_ == _drain(cfg, params, fused=False, sampling=sp)
    # and reproducible: a second async run emits the same streams
    assert async_ == _drain(cfg, params, sampling=sp)


# ---------------------------------------------------------------------------
# staggered arrivals (property): the async engine under tick-driven
# traffic still emits the oracle's streams
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_async_staggered_arrivals_match_oracle(seed):
    cfg, params = _tiny()
    rng = np.random.default_rng(seed)
    n_req = int(rng.integers(3, 7))
    reqs = [(_prompt(rng, int(rng.integers(1, 20))),
             int(rng.integers(1, 11))) for _ in range(n_req)]
    ticks = sorted(int(rng.integers(0, 6)) for _ in range(n_req))

    eng = ServeEngine(cfg, params, n_slots=2, max_seq=64, eos_id=7,
                      prefill_chunk=4, decode_block=4)
    uids, k, req_by_uid = [], 0, {}
    for tick in range(max(ticks) + 1):
        while k < n_req and ticks[k] <= tick:
            p, mn = reqs[k]
            uids.append(eng.submit(p, max_new=mn))
            k += 1
        eng.decode_block_step()
        for s in eng.slots:
            if s.req is not None:
                req_by_uid[s.req.uid] = s.req
    res = eng.run_until_drained()
    for s in eng.slots:                   # catch slots filled by the drain
        if s.req is not None:
            req_by_uid[s.req.uid] = s.req
    assert all(r.done for r in req_by_uid.values())
    streams = [req_by_uid[u].out if u in req_by_uid else res[u]
               for u in uids]

    oracle = ServeEngine(cfg, params, n_slots=2, max_seq=64, eos_id=7,
                         fused=False)
    ouids = [oracle.submit(p, max_new=mn) for p, mn in reqs]
    ores = oracle.run_until_drained()
    assert streams == [ores[u] for u in ouids]


# ---------------------------------------------------------------------------
# deferred-accounting edge cases
# ---------------------------------------------------------------------------

def test_occupancy_change_mid_speculation():
    """A request finishing inside block k invalidates the speculatively
    dispatched block k+1's live set: the engine drains the speculative
    block cleanly (its tokens are still exact) and the queued request
    admits on the next tick — streams stay oracle-exact throughout."""
    cfg, params = _tiny()
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=48, decode_block=4)
    # A finishes after 2 tokens (inside the first 4-step block) while B
    # runs long; C waits in the queue for A's slot
    reqs = [(np.array([3, 5], np.int32), 2),
            (np.array([2, 4, 6], np.int32), 14),
            (np.array([9, 1], np.int32), 5)]
    uids = [eng.submit(p, max_new=mn) for p, mn in reqs]
    req_by_uid = {}
    for _ in range(12):
        eng.decode_block_step()
        for s in eng.slots:               # hold refs before slot recycling
            if s.req is not None:
                req_by_uid[s.req.uid] = s.req
    eng.run_until_drained()
    for s in eng.slots:
        if s.req is not None:
            req_by_uid[s.req.uid] = s.req
    assert all(req_by_uid[u].done for u in uids)

    oracle = ServeEngine(cfg, params, n_slots=2, max_seq=48, fused=False)
    ouids = [oracle.submit(p, max_new=mn) for p, mn in reqs]
    ores = oracle.run_until_drained()
    for uid, ouid in zip(uids, ouids):
        assert req_by_uid[uid].out == ores[ouid]


def _oracle_streams(reqs):
    cfg, params = _tiny()
    oracle = ServeEngine(cfg, params, n_slots=2, max_seq=48, fused=False)
    uids = [oracle.submit(p, max_new=mn) for p, mn in reqs]
    out = oracle.run_until_drained()
    return [out[u] for u in uids]


def _collect(streams, out):
    for uid, toks in out.items():
        streams.setdefault(uid, []).extend(toks)
    return out


def test_tick_returns_credited_tokens_before_a_first_token_block():
    """A tick that has credited tokens returns them at once: the last
    token of a finishing request is not held while a newly admitted
    request's first-token block runs.  The next tick launches that
    block's successor, then syncs it."""
    cfg, params = _tiny()
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=48, decode_block=4)
    reqs = [(np.array([3, 5, 7], np.int32), 5),
            (np.array([2, 4], np.int32), 6)]
    got = {}
    a = eng.submit(reqs[0][0], max_new=reqs[0][1])
    assert len(_collect(got, eng.decode_block_step())[a]) == 4  # urgency
    assert _collect(got, eng.decode_block_step()) == {}  # a's last step
    b = eng.submit(reqs[1][0], max_new=reqs[1][1])
    out = _collect(got, eng.decode_block_step())  # a accounted, b admitted
    assert len(out[a]) == 1 and b not in out
    assert len(eng._inflight) == 1        # b's first block, not synced
    out = _collect(got, eng.decode_block_step())
    assert len(out[b]) == 4 and len(eng._inflight) == 1  # successor out
    _collect(got, eng.flush())
    assert [got[a], got[b]] == _oracle_streams(reqs)


def test_occupancy_change_returns_without_draining():
    """A request finishing inside block k while block k+1 was launched
    speculatively: the tick returns block k's tokens at once, the finished
    request's last ones among them, and leaves block k+1 in flight; the
    next tick accounts it.  Streams stay oracle-exact."""
    cfg, params = _tiny()
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=48, decode_block=4)
    reqs = [(np.array([3, 5, 7], np.int32), 6),
            (np.array([2, 4], np.int32), 14)]
    a, b = [eng.submit(p, max_new=mn) for p, mn in reqs]
    got = {}
    out = _collect(got, eng.decode_block_step())     # first block, synced
    assert len(out[a]) == len(out[b]) == 4
    assert _collect(got, eng.decode_block_step()) == {}   # deferred
    out = _collect(got, eng.decode_block_step())  # speculate, account: a done
    assert len(out[a]) == 2 and len(got[a]) == 6
    assert len(eng._inflight) == 1        # the speculative block, undrained
    out = _collect(got, eng.decode_block_step())
    assert len(out[b]) == 4 and a not in out
    _collect(got, eng.flush())
    assert [got[a], got[b]] == _oracle_streams(reqs)


def test_eos_in_deferred_block():
    """EOS fires on device inside a block whose host accounting is
    deferred: the stream still truncates at (and including) the EOS
    token, exactly like the per-token oracle."""
    cfg, params = _tiny()
    prompt = _prompt(np.random.default_rng(5), 7)
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=64)
    u = eng.submit(prompt, max_new=12)
    ref = eng.run_until_drained()[u]
    eos = ref[4]
    cut = ref.index(eos) + 1
    streams = {}
    for fused in (True, False):
        e = ServeEngine(cfg, params, n_slots=2, max_seq=64,
                        eos_id=int(eos), decode_block=4, fused=fused)
        uu = e.submit(prompt, max_new=12)
        streams[fused] = e.run_until_drained()[uu]
        assert all(s.req is None or s.req.done for s in e.slots)
    assert streams[True] == ref[:cut] == streams[False]


def test_decode_block_step_defers_by_one_block():
    """Async tick semantics: a block carrying a request's *first* token is
    synced in its own tick (first-token urgency — TTFT never pays the
    deferral); after that the engine double-buffers: the next tick
    launches and returns nothing, ``flush()`` returns the deferred tail.
    The total equals the per-token oracle's stream."""
    cfg, params = _tiny()
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=48, decode_block=4)
    u = eng.submit(np.array([3, 5, 7], np.int32), max_new=8)
    first = eng.decode_block_step()
    assert len(first.get(u, [])) == 4 and not eng._inflight
    second = eng.decode_block_step()
    assert second == {} and len(eng._inflight) == 1
    tail = eng.flush()
    toks = first[u] + tail.get(u, [])
    assert not eng._inflight

    assert [toks] == _oracle_streams([(np.array([3, 5, 7], np.int32), 8)])


def _sparse_setup():
    from repro.core.sparsity import prune_stacked_magnitude
    cfg = dataclasses.replace(_tiny_cfg(),
                              sparsity=SparsityConfig(weight_sparsity=0.5))
    params = jax.tree.map(
        lambda x: (prune_stacked_magnitude(x, 0.5, block=(16, 16))
                   .astype(x.dtype)
                   if x.ndim >= 2 and min(x.shape[-2:]) >= 16 else x),
        _tiny()[1])
    ec = decode_exec_config(cfg, n_slots=2, params=params)
    assert ec.plan is not None and ec.plan.entries
    return cfg, params, ec


def _moe_setup():
    cfg = get_smoke_config("deepseek-moe-16b")
    return cfg, model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                      dtype=jnp.float32), None


_DRAIN_SETUPS = {"dense": lambda: (*_tiny(), None), "weight": _sparse_setup,
                 "moe": _moe_setup}


@pytest.mark.parametrize("setup", list(_DRAIN_SETUPS))
def test_drain_dispatches_like_the_tick(setup):
    """``run_until_drained`` is serving ticks until drained: on a schedule
    where a queued request waits for the slot a short request frees, the
    drain dispatches the same prefill segments and decode blocks, in the
    same order, as ``decode_block_step`` driven until ``health()`` shows
    nothing queued, live or in flight, and both emit the oracle's
    streams."""
    cfg, params, ec = _DRAIN_SETUPS[setup]()
    rng = np.random.default_rng(7)
    reqs = [(_prompt(rng, 3, cfg.vocab), 3),      # short: frees its slot
            (_prompt(rng, 6, cfg.vocab), 10),
            (_prompt(rng, 9, cfg.vocab), 6)]      # waits for a slot

    def run(drain):
        eng = ServeEngine(cfg, params, n_slots=2, max_seq=48, exec_cfg=ec,
                          prefill_chunk=4, decode_block=4)
        seen, prefill, decode_many = [], eng._prefill, eng._decode_many

        def rec_prefill(p, s, toks, valid, *rest):
            seen.append(("prefill", len(toks), int(np.sum(valid))))
            return prefill(p, s, toks, valid, *rest)

        def rec_decode(*args):
            seen.append(("decode", int(args[9]),
                         tuple(np.asarray(args[4]).tolist())))
            return decode_many(*args)

        eng._prefill, eng._decode_many = rec_prefill, rec_decode
        uids = [eng.submit(p, max_new=mn) for p, mn in reqs]
        if drain:
            out = eng.run_until_drained()
        else:
            for _ in range(200):
                h = eng.health()
                if not (h["queue_depth"] or h["decoding"]
                        or h["prefilling"] or h["inflight_blocks"]):
                    break
                eng.decode_block_step()
            eng.flush()
            out = eng.results()
        return seen, [out[u] for u in uids]

    drained, ticked = run(True), run(False)
    assert drained[0] == ticked[0]
    assert drained[1] == ticked[1]
    oracle = ServeEngine(cfg, params, n_slots=2, max_seq=48, exec_cfg=ec,
                         fused=False)
    ouids = [oracle.submit(p, max_new=mn) for p, mn in reqs]
    ores = oracle.run_until_drained()
    assert drained[1] == [ores[u] for u in ouids]


def test_flush_is_idempotent_and_credits_requests():
    cfg, params = _tiny()
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=48, decode_block=4)
    u = eng.submit(np.array([3, 5, 7], np.int32), max_new=8)
    first = eng.decode_block_step()       # first block syncs (urgency)
    eng.decode_block_step()               # steady state: launch, deferred
    req = next(s.req for s in eng.slots if s.req is not None)
    out = eng.flush()
    assert out[u] and first[u] + out[u] == req.out and req.done
    assert eng.flush() == {}              # nothing pending → no-op


def test_async_launch_uses_device_carries():
    """White-box: while a block is in flight, the speculative launch must
    feed ``decode_many`` the device-resident carries (jax arrays), not
    host-rebuilt numpy inputs — that round-trip is the host sync the
    tentpole removes."""
    cfg, params = _tiny()
    eng = ServeEngine(cfg, params, n_slots=1, max_seq=48, decode_block=4)
    inner = eng._decode_many
    seen = []

    def spy(p, state, toks, pos, live, rem, temp, topk, seeds, t):
        seen.append((bool(eng._inflight),
                     isinstance(toks, jax.Array)
                     and not isinstance(toks, np.ndarray)))
        return inner(p, state, toks, pos, live, rem, temp, topk, seeds, t)

    eng._decode_many = spy
    u = eng.submit(np.array([3, 5, 7], np.int32), max_new=12)
    for _ in range(3):
        eng.decode_block_step()
    eng.flush()
    # first launch: host inputs, nothing in flight
    assert seen[0] == (False, False)
    # speculative launches: dispatched over a pending block, from carries
    spec = [dev for inflight, dev in seen[1:] if inflight]
    assert spec and all(spec)


# ---------------------------------------------------------------------------
# admission policies
# ---------------------------------------------------------------------------

def _stub_engine(n_live, n_slots, prefill_chunk=64):
    return SimpleNamespace(n_slots=n_slots, prefill_chunk=prefill_chunk,
                           _live=lambda: list(range(n_live)))


def test_adaptive_chunk_monotone_in_occupancy():
    """Idle slots → big chunks (fast admits); hot decode → small chunks
    (short stalls).  Chunk size is pow2 and monotone non-increasing in
    occupancy, hitting both endpoints."""
    pol = AdaptiveAdmission(min_chunk=32, max_chunk=256)
    chunks = [pol.chunk(_stub_engine(k, 8)) for k in range(9)]
    assert chunks[0] == 256 and chunks[-1] == 32
    assert all(a >= b for a, b in zip(chunks, chunks[1:]))
    assert all(c & (c - 1) == 0 for c in chunks)
    assert pol.chunk_cap(_stub_engine(0, 8)) == 256


def test_adaptive_shortest_prompt_first_under_burst():
    pol = AdaptiveAdmission(burst_depth=3)
    mk = lambda *lens: [SimpleNamespace(prompt=np.zeros(n)) for n in lens]
    eng = _stub_engine(0, 4)
    # at or below the threshold: FIFO order
    assert pol.pick(mk(9, 2, 5), eng) == 0
    # burst: the shortest prompt jumps the queue
    assert pol.pick(mk(9, 2, 5, 7), eng) == 1
    assert pol.pick(mk(4, 4, 1, 8, 1), eng) == 2   # ties → earliest


def test_adaptive_rejects_bad_chunk_bounds():
    with pytest.raises(ValueError):
        AdaptiveAdmission(min_chunk=48, max_chunk=256)   # not pow2
    with pytest.raises(ValueError):
        AdaptiveAdmission(min_chunk=256, max_chunk=64)   # min > max


def test_engine_rejects_non_policy_admission():
    cfg, params = _tiny()
    with pytest.raises(TypeError, match="AdmissionPolicy"):
        ServeEngine(cfg, params, n_slots=1, max_seq=32,
                    admission=object())


def test_adaptive_streams_match_fifo_per_request():
    """Policies reorder the *schedule*, never the *math*: every request's
    stream under AdaptiveAdmission equals its FIFO stream."""
    cfg, params = _tiny()
    rng = np.random.default_rng(3)
    reqs = [(_prompt(rng, int(rng.integers(1, 20))),
             int(rng.integers(2, 9))) for _ in range(6)]
    outs = []
    for adm in (FIFOAdmission(),
                AdaptiveAdmission(min_chunk=4, max_chunk=16,
                                  burst_depth=2)):
        eng = ServeEngine(cfg, params, n_slots=2, max_seq=64,
                          prefill_chunk=4, decode_block=4, admission=adm)
        uids = [eng.submit(p, max_new=mn) for p, mn in reqs]
        res = eng.run_until_drained()
        outs.append([res[u] for u in uids])
    assert outs[0] == outs[1]


def test_base_policy_is_fifo_with_configured_chunk():
    pol = AdmissionPolicy()
    eng = _stub_engine(0, 4, prefill_chunk=16)
    assert pol.pick([1, 2, 3], eng) == 0
    assert pol.chunk(eng) == 16 and pol.chunk_cap(eng) == 16
    assert isinstance(FIFOAdmission(), AdmissionPolicy)
