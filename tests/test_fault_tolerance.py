"""Fault-tolerant serving (ISSUE 10): lifecycle, quarantine, shedding,
demotion, and the deterministic chaos property suite.

Unit layer: ``cancel()`` at every lifecycle stage (queued / prefill /
decode / mid-speculation), ``submit(deadline=...)`` expiry on a virtual
clock, NaN quarantine isolating one slot (the on-device ``-2`` sentinel),
bounded-queue shedding (reject-new default vs ``ShedLowestPriority``),
deadline-pressure tier demotion, ``health()``, and the satellite
regressions (idempotent ``flush()`` on a fresh engine, zero-sample
``activation_densities()``).

Chaos layer: seeded ``FaultInjector`` schedules over staggered arrivals —
async dense, planned + self-speculative, and two-sided + forced
recalibration engines.  Invariants asserted per schedule: the drive
terminates (no hang — backstopped by the conftest SIGALRM shim), every
request reaches a terminal status, every applied targeted fault maps to
exactly one ``failed`` / ``cancelled`` / ``deadline_missed`` request,
survivors stream token-for-token equal to the fault-free per-token
oracle, and non-survivors stream an exact oracle *prefix* (a fault never
corrupts what was already credited, and a cancelled slot never leaks a
speculative block's tokens into a successor).
"""
import dataclasses
import functools

from hypothesis import given, settings, strategies as st
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ArchConfig, SparsityConfig
from repro.core.sparsity import prune_stacked_magnitude
from repro.kernels import ops
from repro.models import model as model_lib
from repro.serve import (TERMINAL_STATES, Fault, FaultInjector,
                         PriorityAdmission, ServeEngine, ShedLowestPriority,
                         VirtualClock, decode_exec_config, drive)


def _tiny_cfg(**over) -> ArchConfig:
    return ArchConfig(name="ft-tiny", family="dense", n_layers=1,
                      d_model=32, n_heads=4, n_kv_heads=4, d_ff=64,
                      vocab=128, norm="rmsnorm", **over)


@functools.lru_cache(maxsize=None)
def _tiny():
    cfg = _tiny_cfg()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    return cfg, params


_PLANNED_CACHE = {}


def _planned(two_sided=False):
    """Tiny planned setup: 0.5 block-pruned weights + compiled plan
    (optionally two-sided with runtime stats collection)."""
    key = bool(two_sided)
    if key not in _PLANNED_CACHE:
        thr = 0.05 if two_sided else 0.0
        cfg = _tiny_cfg(sparsity=SparsityConfig(weight_sparsity=0.5,
                                                activation_threshold=thr))
        params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                       dtype=jnp.float32)
        params = jax.tree.map(
            lambda x: (prune_stacked_magnitude(x, 0.5, block=(16, 16))
                       .astype(x.dtype)
                       if x.ndim >= 2 and x.shape[-1] >= 16
                       and x.shape[-2] >= 16 else x),
            params)
        ec = decode_exec_config(cfg, 2, params=params,
                                collect_stats=two_sided)
        assert ec.plan is not None and ec.plan.entries
        _PLANNED_CACHE[key] = (cfg, params, ec)
    return _PLANNED_CACHE[key]


def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("decode_block", 4)
    kw.setdefault("prefill_chunk", 4)
    return ServeEngine(cfg, params, **kw)


# ---------------------------------------------------------------------------
# lifecycle: cancel at every stage
# ---------------------------------------------------------------------------

def test_cancel_queued_request():
    cfg, params = _tiny()
    eng = _engine(cfg, params, n_slots=1)
    a = eng.submit([1, 2, 3], max_new=4)
    b = eng.submit([4, 5], max_new=4)          # stuck behind a in the queue
    assert eng.status(b) == "queued"
    assert eng.cancel(b)
    assert eng.status(b) == "cancelled" and eng.counters["cancelled"] == 1
    assert not eng.cancel(b)                   # idempotent: already terminal
    assert not eng.cancel(999)                 # unknown uid
    out = eng.run_until_drained()
    assert eng.status(a) == "done" and b not in out
    assert eng.results()[b] == []


def test_cancel_mid_prefill_and_mid_decode():
    cfg, params = _tiny()
    eng = _engine(cfg, params, prefill_chunk=2)
    a = eng.submit(list(range(1, 9)), max_new=40)   # 8-token prompt, 4 chunks
    b = eng.submit([4, 5, 6], max_new=8)
    eng.decode_block_step()
    assert eng.status(a) == "prefill"
    assert eng.cancel(a)                            # mid-prefill
    assert eng.status(a) == "cancelled"
    for _ in range(3):
        eng.decode_block_step()
    assert eng.status(b) in ("prefill", "decode", "done")
    ticks = drive(eng)
    assert ticks >= 1 and eng.status(b) == "done"
    # survivor is oracle-exact despite the mid-prefill cancellation
    orc = _engine(cfg, params, fused=False)
    orc.submit(list(range(1, 9)), max_new=40)
    ob = orc.submit([4, 5, 6], max_new=8)
    assert eng.results()[b] == orc.run_until_drained()[ob]

    # mid-decode: let the request stream a few tokens first
    eng2 = _engine(cfg, params)
    c = eng2.submit([1, 2, 3], max_new=40)
    for _ in range(4):
        eng2.decode_block_step()
    assert eng2.status(c) == "decode" and eng2.results() == {}
    assert eng2.cancel(c)
    drive(eng2)
    got = eng2.results()[c]
    orc2 = _engine(cfg, params, fused=False)
    oc = orc2.submit([1, 2, 3], max_new=40)
    want = orc2.run_until_drained()[oc]
    assert 0 < len(got) < 40 and got == want[:len(got)]


def test_cancel_mid_speculation_never_leaks_into_successor():
    """The PR 7 clean-drain rule, exercised through cancellation: cancel a
    slot while a block is in flight for it, admit a successor into the
    same slot, and require the successor's stream to be oracle-exact (no
    token from the cancelled request's in-flight block leaks)."""
    cfg, params = _tiny()
    eng = _engine(cfg, params, n_slots=1)
    a = eng.submit([1, 2, 3], max_new=40)
    b = eng.submit([7, 8], max_new=6)               # waits for the slot
    for _ in range(3):
        eng.decode_block_step()
    assert eng.status(a) == "decode" and eng._inflight
    assert eng.cancel(a)                            # in-flight block pending
    drive(eng)
    assert eng.status(a) == "cancelled" and eng.status(b) == "done"
    orc = _engine(cfg, params, n_slots=1, fused=False)
    oa = orc.submit([1, 2, 3], max_new=40)
    ob = orc.submit([7, 8], max_new=6)
    want = orc.run_until_drained()
    res = eng.results()
    assert res[b] == want[ob]
    assert res[a] == want[oa][:len(res[a])]


def test_cancel_mid_speculation_planned_tiers():
    cfg, params, ec = _planned()
    eng = _engine(cfg, params, n_slots=1, exec_cfg=ec,
                  plan_tiers=(0.0, 0.5), speculate_k=3)
    a = eng.submit([1, 2, 3], max_new=40)
    b = eng.submit([7, 8, 9], max_new=8)
    for _ in range(3):
        eng.decode_block_step()
    assert eng.cancel(a)
    drive(eng)
    assert eng.status(a) == "cancelled" and eng.status(b) == "done"
    orc = _engine(cfg, params, n_slots=1, exec_cfg=ec, fused=False)
    oa = orc.submit([1, 2, 3], max_new=40)
    ob = orc.submit([7, 8, 9], max_new=8)
    want = orc.run_until_drained()
    res = eng.results()
    assert res[b] == want[ob]
    assert res[a] == want[oa][:len(res[a])]


# ---------------------------------------------------------------------------
# deadlines + demotion
# ---------------------------------------------------------------------------

def test_deadline_expiry_queued_and_decoding():
    cfg, params = _tiny()
    clk = VirtualClock()
    eng = _engine(cfg, params, n_slots=1, clock=clk)
    a = eng.submit([1, 2, 3], max_new=40, deadline=10.0)   # will be decoding
    b = eng.submit([4, 5], max_new=4, deadline=10.0)       # expires queued
    c = eng.submit([6, 7], max_new=4)                      # no deadline
    for _ in range(3):
        eng.decode_block_step()
    clk.advance(100.0)
    drive(eng)
    assert eng.status(a) == "deadline_missed"
    assert eng.status(b) == "deadline_missed"
    assert eng.status(c) == "done"
    assert eng.counters["deadline_missed"] == 2
    # partial stream of the expired decoder is still an oracle prefix
    orc = _engine(cfg, params, n_slots=1, fused=False)
    oa = orc.submit([1, 2, 3], max_new=40)
    orc.submit([4, 5], max_new=4)
    orc.submit([6, 7], max_new=4)
    want = orc.run_until_drained()
    got = eng.results()[a]
    assert got and got == want[oa][:len(got)]


def test_submit_validates_deadline():
    cfg, params = _tiny()
    eng = _engine(cfg, params)
    with pytest.raises(ValueError):
        eng.submit([1, 2], max_new=2, deadline=0.0)
    with pytest.raises(ValueError):
        eng.submit([1, 2], max_new=2, deadline=-1.0)


def test_deadline_pressure_demotes_to_cheaper_tier():
    cfg, params, ec = _planned()
    clk = VirtualClock()
    eng = _engine(cfg, params, exec_cfg=ec, clock=clk,
                  plan_tiers=(0.0, 0.5))
    a = eng.submit([1, 2, 3], max_new=30, deadline=5.0)
    eng.decode_block_step()                 # admit + start decoding
    eng.flush()
    eng._tok_ema = 1.0                      # 1 s/token measured service rate
    eng._maybe_demote()                     # 30 tokens needed, 5 s budget
    req = next(s.req for s in eng.slots if s.req is not None
               and s.req.uid == a)
    assert req.latency_class == 1 and req.demotions == 1
    assert eng.counters["demotions"] == 1
    eng._maybe_demote()                     # already at the cheapest tier
    assert req.latency_class == 1 and eng.counters["demotions"] == 1
    drive(eng)
    assert eng.status(a) in ("done", "deadline_missed")


def test_no_demotion_without_deadline_or_single_tier():
    cfg, params, ec = _planned()
    eng = _engine(cfg, params, exec_cfg=ec, plan_tiers=(0.0, 0.5),
                  clock=VirtualClock())
    a = eng.submit([1, 2, 3], max_new=30)          # no deadline
    eng.decode_block_step()
    eng._tok_ema = 100.0
    eng._maybe_demote()
    assert eng.counters["demotions"] == 0
    assert eng.status(a) in ("prefill", "decode")


# ---------------------------------------------------------------------------
# NaN quarantine
# ---------------------------------------------------------------------------

def test_nan_quarantine_isolates_one_slot():
    from repro.serve.faults import poison_slot_state
    cfg, params = _tiny()
    eng = _engine(cfg, params)
    a = eng.submit([1, 2, 3, 4], max_new=40)
    b = eng.submit([5, 6, 7], max_new=8)
    for _ in range(4):                      # both prefilled and decoding
        eng.decode_block_step()
    eng.flush()
    slot_a = next(i for i, s in enumerate(eng.slots)
                  if s.req is not None and s.req.uid == a)
    poison_slot_state(eng, slot_a)
    drive(eng)
    assert eng.status(a) == "failed" and eng.counters["failed"] == 1
    assert eng.status(b) == "done"          # the batch survives
    res = eng.results()
    orc = _engine(cfg, params, fused=False)
    oa = orc.submit([1, 2, 3, 4], max_new=40)
    ob = orc.submit([5, 6, 7], max_new=8)
    want = orc.run_until_drained()
    assert res[b] == want[ob]
    assert res[a] == want[oa][:len(res[a])]     # clean prefix, then fail
    assert len(res[a]) < 40


def test_quarantine_sentinel_is_distinct_from_eos():
    assert model_lib.QUARANTINE_SENTINEL == -2
    # both sentinels are negative: one `tok < 0` test stops host crediting
    assert model_lib.QUARANTINE_SENTINEL < 0


# ---------------------------------------------------------------------------
# bounded queue + shedding
# ---------------------------------------------------------------------------

def test_bounded_queue_reject_new_default():
    cfg, params = _tiny()
    eng = _engine(cfg, params, max_queue=2)
    u = [eng.submit([1, 2], max_new=2) for _ in range(4)]
    assert [eng.status(x) for x in u] == ["queued", "queued", "shed", "shed"]
    assert eng.counters["shed"] == 2
    eng.run_until_drained()
    assert eng.status(u[0]) == "done" and eng.status(u[1]) == "done"
    assert eng.results()[u[2]] == []


def test_shed_lowest_priority_evicts_for_vip():
    cfg, params = _tiny()
    eng = _engine(cfg, params, max_queue=1, admission=ShedLowestPriority())
    low = eng.submit([1, 2], max_new=2, priority=5)
    vip = eng.submit([3, 4], max_new=2, priority=0)    # evicts `low`
    assert eng.status(low) == "shed" and eng.status(vip) == "queued"
    peer = eng.submit([5, 6], max_new=2, priority=0)   # equal prio: reject new
    assert eng.status(peer) == "shed"
    assert eng.counters["shed"] == 2
    eng.run_until_drained()
    assert eng.status(vip) == "done"


def test_priority_admission_sheds_like_shed_lowest_priority():
    cfg, params = _tiny()
    eng = _engine(cfg, params, max_queue=1, admission=PriorityAdmission())
    low = eng.submit([1, 2], max_new=2, priority=9)
    vip = eng.submit([3, 4], max_new=2, priority=1)
    assert eng.status(low) == "shed" and eng.status(vip) == "queued"


def test_max_queue_validation():
    cfg, params = _tiny()
    with pytest.raises(ValueError):
        _engine(cfg, params, max_queue=0)


# ---------------------------------------------------------------------------
# satellites: flush idempotency, zero-sample densities, health
# ---------------------------------------------------------------------------

def test_flush_safe_and_idempotent_on_fresh_engine():
    cfg, params = _tiny()
    for fused in (False, True):
        eng = _engine(cfg, params, fused=fused)
        eng.flush()                         # never dispatched: must be a no-op
        eng.flush()
        assert eng._inflight == [] and eng.results() == {}
        u = eng.submit([1, 2, 3], max_new=4)
        out = eng.run_until_drained()
        eng.flush()                         # drained engine: still a no-op
        eng.flush()
        assert eng.status(u) == "done" and out[u] == eng.results()[u]


def test_activation_densities_zero_sample_guard():
    # collector-level: zero-total sites are skipped, not divided by zero
    c = ops.SparsityStatsCollector()
    assert c.densities() == {}
    c.record("site_a", 0, 0)                # a tick with zero live rows
    assert c.densities() == {}
    c._total["site_b"] = 64                 # total without a live record
    assert c.densities() == {"site_b": 0.0}
    c.record("site_a", 8, 64)
    assert c.densities()["site_a"] == pytest.approx(8 / 64)

    # engine-level: query before any two-sided dispatch
    cfg, params, ec = _planned(two_sided=True)
    eng = _engine(cfg, params, exec_cfg=ec)
    assert eng.activation_densities() == {}


def test_health_snapshot():
    cfg, params = _tiny()
    eng = _engine(cfg, params, max_queue=8)
    h0 = eng.health()
    assert h0["queue_depth"] == 0 and h0["inflight_blocks"] == 0
    assert h0["max_queue"] == 8 and h0["requests"] == {}
    a = eng.submit([1, 2, 3], max_new=12)
    b = eng.submit([4, 5], max_new=4)
    eng.decode_block_step()
    eng.decode_block_step()
    h1 = eng.health()
    assert set(h1) == {"queue_depth", "max_queue", "free_slots", "decoding",
                       "prefilling", "inflight_blocks",
                       "inflight_speculative", "requests", "counters",
                       "spec", "tok_ema_s"}
    assert h1["requests"][a] in ("queued", "prefill", "decode")
    assert h1["inflight_blocks"] >= 1       # async: a block is in flight
    eng.cancel(a)
    drive(eng)
    h2 = eng.health()
    assert h2["counters"]["cancelled"] == 1 and h2["counters"]["done"] == 1
    assert eng.status(b) == "done"


# ---------------------------------------------------------------------------
# chaos property suite: seeded fault schedules vs the per-token oracle
# ---------------------------------------------------------------------------

_TARGETED = ("nan", "cancel")


def _chaos_schedule(seed, *, kinds=_TARGETED, with_deadline=True,
                    with_recal=False, n_req_lo=4, n_req_hi=8):
    """Deterministic (requests, faults) pair for one chaos run.

    Targeted faults hit distinct requests whose budgets are raised to 40
    tokens so the fault always lands before natural completion (fault
    ticks sit within 5 ticks of arrival; a 40-token budget cannot drain
    that fast at decode_block=4) — this is what makes the fault ->
    terminal-request mapping exactly one-to-one, assertable per run."""
    rng = np.random.default_rng(seed)
    n_req = int(rng.integers(n_req_lo, n_req_hi + 1))
    reqs = []
    for _ in range(n_req):
        reqs.append({
            # len >= 2: a nan target needs a cached prefix position
            "prompt": rng.integers(1, 127,
                                   size=int(rng.integers(2, 13))).astype(
                                       np.int32),
            "arrive": int(rng.integers(0, 6)),
            "max_new": int(rng.integers(3, 13)),
            "deadline": None,
        })
    reqs.sort(key=lambda r: r["arrive"])

    n_targets = int(rng.integers(1, min(3, n_req - 1) + 1))
    order = rng.permutation(n_req)
    faults, expect = [], {}
    for j in order[:n_targets]:
        kind = str(kinds[int(rng.integers(len(kinds)))])
        reqs[j]["max_new"] = 40
        tick = reqs[j]["arrive"] + 1 + int(rng.integers(0, 4))
        uid = int(j) + 1                    # engine uids are 1-based FIFO
        faults.append(Fault(tick=tick, kind=kind, uid=uid))
        expect[uid] = "failed" if kind == "nan" else "cancelled"
    if with_deadline and n_req - n_targets >= 2:
        d = int(order[n_targets])
        reqs[d]["deadline"] = 1000.0
        reqs[d]["max_new"] = 40
        faults.append(Fault(tick=reqs[d]["arrive"] + 1, kind="delay",
                            dt=5000.0))
        expect[d + 1] = "deadline_missed"
    if with_recal:
        # within the arrival window, which drive() is guaranteed to reach
        last = max(r["arrive"] for r in reqs)
        faults.append(Fault(tick=int(rng.integers(1, max(last, 1) + 1)),
                            kind="recalibrate"))
    return reqs, faults, expect


def _run_chaos(seed, cfg, params, *, exec_cfg=None, kinds=_TARGETED,
               with_recal=False, **engine_kw):
    reqs, faults, expect = _chaos_schedule(seed, kinds=kinds,
                                           with_recal=with_recal)
    clk = VirtualClock()
    eng = _engine(cfg, params, exec_cfg=exec_cfg, clock=clk, **engine_kw)
    uids = []

    def on_tick(t):
        while len(uids) < len(reqs) and reqs[len(uids)]["arrive"] <= t:
            r = reqs[len(uids)]
            uids.append(eng.submit(r["prompt"], max_new=r["max_new"],
                                   deadline=r["deadline"]))
        return len(uids) < len(reqs)        # truthy while arrivals pending

    inj = FaultInjector(faults, clock=clk)
    drive(eng, inj, on_tick=on_tick)        # no crash, no hang (SIGALRM shim)
    assert uids == list(range(1, len(reqs) + 1))

    # every request terminal; every applied fault -> exactly one casualty
    assert not inj.pending and not inj.dropped
    statuses = {u: eng.status(u) for u in uids}
    assert all(s in TERMINAL_STATES for s in statuses.values()), statuses
    for uid, want in expect.items():
        assert statuses[uid] == want, (uid, want, statuses)
    for uid, s in statuses.items():
        if uid not in expect:
            assert s == "done", (uid, s)
    n_kind = {k: sum(1 for f in faults if f.kind == k)
              for k in ("nan", "cancel", "delay")}
    assert eng.counters["failed"] == n_kind["nan"]
    assert eng.counters["cancelled"] == n_kind["cancel"]
    assert eng.counters["deadline_missed"] == n_kind["delay"]
    assert eng.counters["done"] == len(reqs) - len(expect)

    # survivors oracle-exact; casualties stream an exact oracle prefix
    orc = _engine(cfg, params, exec_cfg=exec_cfg, fused=False)
    for r in reqs:
        orc.submit(r["prompt"], max_new=r["max_new"])
    oracle = orc.run_until_drained()
    res = eng.results()
    for uid in uids:
        if statuses[uid] == "done":
            assert res[uid] == oracle[uid], (uid, seed)
        else:
            assert res[uid] == oracle[uid][:len(res[uid])], (uid, seed)
    return eng


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_chaos_dense_async(seed):
    cfg, params = _tiny()
    _run_chaos(seed, cfg, params)


@pytest.mark.slow
@settings(max_examples=8, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_chaos_planned_speculative(seed):
    cfg, params, ec = _planned()
    eng = _run_chaos(seed, cfg, params, exec_cfg=ec,
                     plan_tiers=(0.0, 0.5), speculate_k=3)
    assert eng._spec_windowed               # speculation was actually on


@pytest.mark.slow
@settings(max_examples=6, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_chaos_two_sided_with_recalibration(seed):
    """Two-sided engines launder NaN through the activation bitmap
    (|x| > thr is False for NaN), so the quarantine path can't see the
    poison — chaos here sticks to cancel/delay/recalibrate faults and
    additionally forces a mid-traffic recalibration."""
    cfg, params, ec = _planned(two_sided=True)
    eng = _run_chaos(seed, cfg, params, exec_cfg=ec,
                     kinds=("cancel",), with_recal=True)
    assert eng._stats is not None
