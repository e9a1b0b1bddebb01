"""Elastic plan tiers + self-speculative decoding.

Three layers of guarantees:

* ``compile_plan_tiers`` — tier monotonicity properties: the ratio-0 tier
  is bitwise the unpruned plan, a higher ratio keeps a *subset* of every
  lower ratio's live K-blocks with a no-looser ``max_nnz``, and all
  attached tiers share the same weight leaves (no copies).
* ``model.verify_block`` — the draft/score/accept contract against the
  ``decode_many`` full-plan oracle (greedy and sampled).
* ``ServeEngine(plan_tiers=..., speculate_k=...)`` — speculative streams
  are token-for-token the plain-engine / per-token-oracle streams across
  dense, quantized, tied-head and MoE families under randomized staggered
  arrivals; the clean-drain-on-occupancy-change rule holds for in-flight
  *verify* blocks; ``PriorityAdmission`` is schedule-invariant.
"""
import dataclasses

import jax
import jax.numpy as jnp
from hypothesis import given, settings, strategies as st
import numpy as np
import pytest

from repro.configs.base import get_smoke_config, SparsityConfig
from repro.core.sparsity import (compile_plan_tiers, compile_weight_plan,
                                 prune_stacked_magnitude, tier_max_live)
from repro.models import model as model_lib
from repro.serve.engine import (FIFOAdmission, PriorityAdmission,
                                SamplingParams, ServeEngine,
                                decode_exec_config)


def _sparse_cfg(name="stablelm-1.6b", **over):
    """Weight-only sparsity: the planned family speculation serves exactly.

    Deliberately NOT two_sided (``activation_threshold=0``) — the
    activation-bitmap masked dot is not bitwise-stable across the verify
    window's row count on XLA:CPU, so the engine auto-disables speculation
    there (see ``test_two_sided_config_disables_speculation``)."""
    cfg = dataclasses.replace(get_smoke_config(name), **over)
    return dataclasses.replace(cfg, sparsity=SparsityConfig(
        weight_sparsity=0.5, activation_threshold=0.0))


def _pruned_params(cfg, seed=0):
    params = model_lib.init_params(cfg, jax.random.PRNGKey(seed),
                                   dtype=jnp.float32)
    return jax.tree.map(
        lambda x: (prune_stacked_magnitude(x, 0.5, block=(16, 16))
                   .astype(x.dtype)
                   if x.ndim >= 2 and x.shape[-1] >= 16
                   and x.shape[-2] >= 16 else x),
        params)


_SETUP_CACHE = {}


def _get_setup():
    """Module-cached (cfg, params, exec_cfg) — plain function rather than
    a fixture so the hypothesis ``@given`` tests can use it too."""
    if "v" not in _SETUP_CACHE:
        cfg = _sparse_cfg(d_ff=256)
        params = _pruned_params(cfg)
        ec = decode_exec_config(cfg, 3, params=params)
        assert ec.plan is not None
        _SETUP_CACHE["v"] = (cfg, params, ec)
    return _SETUP_CACHE["v"]


@pytest.fixture(scope="module")
def tier_setup():
    return _get_setup()


# ---------------------------------------------------------------------------
# tier compilation properties
# ---------------------------------------------------------------------------

def test_tier_max_live_monotone():
    for tk in (1, 2, 3, 7, 16):
        prev = tk
        for r in (0.0, 0.1, 0.25, 0.5, 0.75, 0.99):
            ml = tier_max_live(tk, r)
            assert 1 <= ml <= tk
            assert ml <= prev          # non-increasing in ratio
            prev = ml
        assert tier_max_live(tk, 0.0) == tk


def test_tier_zero_is_bitwise_the_unpruned_plan(tier_setup):
    cfg, params, ec = tier_setup
    tiers = compile_plan_tiers(params, ec.schedules, ratios=(0.0, 0.5))
    base = compile_weight_plan(params, ec.schedules)
    assert set(tiers[0].entries) == set(base.entries)
    for key, e in base.entries.items():
        t = tiers[0].entries[key]
        assert t.max_nnz == e.max_nnz
        assert t.wt_density == e.wt_density
        np.testing.assert_array_equal(t.b_bitmap, e.b_bitmap)
        np.testing.assert_array_equal(t.wkidx, e.wkidx)
        np.testing.assert_array_equal(t.wkcnt, e.wkcnt)


@settings(max_examples=3, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_tiers_monotone_live_subsets(seed):
    cfg, _, ec = _get_setup()
    params = _pruned_params(cfg, seed=seed % 97)
    ratios = (0.0, 0.25, 0.5, 0.75)
    tiers = compile_plan_tiers(params, ec.schedules, ratios=ratios)
    for lo, hi in zip(tiers, tiers[1:]):
        for key in lo.entries:
            a, b = lo.entries[key], hi.entries[key]
            # higher ratio keeps a subset of the lower tier's live blocks
            assert np.all(~b.b_bitmap | a.b_bitmap), key
            assert b.max_nnz <= a.max_nnz
            assert b.wt_density <= a.wt_density
    # every tier's dispatch metadata stays within the raw live blocks
    for t, r in zip(tiers, ratios):
        assert t.prune_ratio == r
        for key, e in t.entries.items():
            assert e.prune_ratio == r


def test_attached_tiers_share_weight_leaves(tier_setup):
    cfg, params, ec = tier_setup
    tiers = compile_plan_tiers(params, ec.schedules, ratios=(0.0, 0.5))
    p0 = tiers[0].attach(params, verify=True)
    p1 = tiers[1].attach(params, verify=True)   # subset check passes
    w0 = [l.w for l in jax.tree.leaves(
        p0, is_leaf=lambda x: hasattr(x, "wkidx")) if hasattr(l, "wkidx")]
    w1 = [l.w for l in jax.tree.leaves(
        p1, is_leaf=lambda x: hasattr(x, "wkidx")) if hasattr(l, "wkidx")]
    assert w0 and len(w0) == len(w1)
    for a, b in zip(w0, w1):
        assert a is b                  # one HBM weight set, N plans


def test_pruned_tiers_carry_compact_gather_payload(tier_setup):
    """Ratio-0 tier keeps the bit-exact masked path (no gather flag, no
    payload); pruned tiers are gather-marked and carry the attach-time
    compacted payload sized (tn, max_nnz, bk, bn) — the draft's
    max_nnz-proportional weight stream."""
    cfg, params, ec = tier_setup
    tiers = compile_plan_tiers(params, ec.schedules, ratios=(0.0, 0.5))
    p0, p1 = tiers[0].attach(params), tiers[1].attach(params)
    is_pw = lambda x: hasattr(x, "wkidx")
    for pw in jax.tree.leaves(p0, is_leaf=is_pw):
        if is_pw(pw):
            assert not pw.gather and pw.wgather is None
    seen = 0
    for pw in jax.tree.leaves(p1, is_leaf=is_pw):
        if not is_pw(pw):
            continue
        seen += 1
        assert pw.gather and pw.wgather is not None
        tn = pw.wkcnt.shape[-1]
        assert pw.wgather.shape[-4:] == (tn, pw.max_nnz, pw.bk, pw.bn)
        assert pw.wgather.dtype == pw.w.dtype
    assert seen


def test_gather_dispatch_matches_masked_dense(tier_setup):
    """The pruned-tier gather dispatch equals x @ (masked dense weight) up
    to f32 block-sum reassociation, for every planned site (stacked layer
    leaves sliced like ``lax.scan`` does)."""
    from repro.kernels.ops import _gathered_planned_matmul
    cfg, params, ec = tier_setup
    tiers = compile_plan_tiers(params, ec.schedules, ratios=(0.0, 0.5))
    p1 = tiers[1].attach(params)
    rng = np.random.default_rng(0)
    checked = 0
    for pw in jax.tree.leaves(p1, is_leaf=lambda x: hasattr(x, "wkidx")):
        if not hasattr(pw, "wkidx"):
            continue
        if pw.w.ndim > 2:                    # scan-style layer slice
            pw = jax.tree.map(lambda a: a[0], pw)
        k, n = pw.w_kn.shape
        x = jnp.asarray(rng.standard_normal((3, k)), jnp.float32)
        mask = np.repeat(np.repeat(np.asarray(pw.b_bitmap), pw.bk, 0),
                         pw.bn, 1)[:k, :n]
        want = x @ (pw.w_kn * mask)
        got = _gathered_planned_matmul(x, pw)
        # and the inline-gather fallback (no precompacted payload)
        got2 = _gathered_planned_matmul(
            x, dataclasses.replace(pw, wgather=None))
        scale = float(jnp.max(jnp.abs(want))) + 1e-9
        assert float(jnp.max(jnp.abs(got - want))) / scale < 1e-5
        assert float(jnp.max(jnp.abs(got2 - want))) / scale < 1e-5
        checked += 1
    assert checked


def test_compile_plan_tiers_validates_ratios(tier_setup):
    cfg, params, ec = tier_setup
    with pytest.raises(ValueError):
        compile_plan_tiers(params, ec.schedules, ratios=())
    with pytest.raises(ValueError):
        compile_plan_tiers(params, ec.schedules, ratios=(0.5, 0.25))
    with pytest.raises(ValueError):
        compile_weight_plan(params, ec.schedules, prune_ratio=1.0)


# ---------------------------------------------------------------------------
# verify_block vs the decode_many oracle (model level)
# ---------------------------------------------------------------------------

def _oracle_prefix_check(emitted, oracle):
    """Each row's non-sentinel emitted prefix must equal the oracle's
    stream prefix, and sentinels must be a suffix."""
    k1, b = emitted.shape
    for r in range(b):
        col = emitted[:, r]
        n = int((col >= 0).sum())
        assert np.all(col[:n] >= 0), f"row {r}: sentinel not a suffix"
        np.testing.assert_array_equal(col[:n], oracle[:n, r])


@settings(max_examples=2, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_verify_block_prefix_matches_oracle(seed):
    cfg, params, ec = _get_setup()
    rng = np.random.default_rng(seed)
    tiers = compile_plan_tiers(params, ec.schedules, ratios=(0.0, 0.5))
    p_full = tiers[0].attach(params)
    p_draft = tiers[1].attach(params)
    b, k = 3, 4
    state = model_lib.init_decode_state(cfg, b, 32, dtype=jnp.float32)
    toks = jnp.asarray(rng.integers(1, cfg.vocab - 1, b), jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)
    live = jnp.asarray([True, True, False])
    rem = jnp.asarray(rng.integers(1, k + 2, b), jnp.int32)
    with jax.disable_jit(False):
        emitted, *_ = model_lib.verify_block(
            p_full, p_draft, cfg, toks, state, pos, live, k,
            rem=rem, eos_id=5)
        oracle, *_ = model_lib.decode_many(
            p_full, cfg, toks, state, pos, live, k + 1,
            rem=rem, eos_id=5)
    _oracle_prefix_check(np.asarray(emitted), np.asarray(oracle))


def test_verify_block_self_draft_accepts_everything(tier_setup):
    cfg, params, ec = tier_setup
    p_full = ec.plan.attach(params)
    b, k = 2, 3
    state = model_lib.init_decode_state(cfg, b, 32, dtype=jnp.float32)
    toks = jnp.asarray([3, 9], jnp.int32)
    pos = jnp.zeros((b,), jnp.int32)
    live = jnp.asarray([True, True])
    emitted, _, tok, ps, rm = model_lib.verify_block(
        p_full, p_full, cfg, toks, state, pos, live, k)
    oracle, _, otok, ops_, orm = model_lib.decode_many(
        p_full, cfg, toks, state, pos, live, k + 1)
    np.testing.assert_array_equal(np.asarray(emitted), np.asarray(oracle))
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(otok))
    np.testing.assert_array_equal(np.asarray(ps), np.asarray(ops_))


# ---------------------------------------------------------------------------
# engine: speculative streams are exact across families
# ---------------------------------------------------------------------------

def _serve(cfg, params, ec, prompts, *, stagger_rng=None, quantize=False,
           **kw):
    eng = ServeEngine(cfg, params, n_slots=3, max_seq=48, exec_cfg=ec,
                      decode_block=8, eos_id=5, quantize=quantize, **kw)
    results = {}
    if stagger_rng is None:
        for p in prompts:
            eng.submit(p, max_new=10)
        results = eng.run_until_drained()
    else:
        # randomized staggered arrivals: interleave submits with serving
        # ticks so requests join mid-traffic with verify blocks in flight
        pending = list(prompts)
        while pending or not eng._drained() or eng._inflight:
            if pending and stagger_rng.random() < 0.6:
                eng.submit(pending.pop(0), max_new=10)
            for uid, toks in eng.decode_block_step().items():
                results.setdefault(uid, []).extend(toks)
            if stagger_rng.random() < 0.2:
                for uid, toks in eng.flush().items():
                    results.setdefault(uid, []).extend(toks)
        for uid, toks in eng.flush().items():
            results.setdefault(uid, []).extend(toks)
        for s in eng.slots:
            if s.req is not None:
                results[s.req.uid] = s.req.out
    return eng, results


FAMILIES = {
    "dense": dict(name="stablelm-1.6b", quantize=False),
    "quant": dict(name="stablelm-1.6b", quantize=True),
    "tied": dict(name="stablelm-1.6b", quantize=False, tied=True),
    "moe": dict(name="deepseek-moe-16b", quantize=False),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_speculative_streams_exact(family):
    spec = FAMILIES[family]
    cfg = _sparse_cfg(spec["name"])
    if spec.get("tied"):
        cfg = dataclasses.replace(cfg, tie_embeddings=True)
    params = _pruned_params(cfg)
    ec = decode_exec_config(cfg, 3, params=params,
                            quantize=spec["quantize"])
    rng = np.random.default_rng(hash(family) % 2**32)
    prompts = [rng.integers(1, cfg.vocab - 1, size=rng.integers(1, 7))
               .astype(np.int32) for _ in range(5)]
    q = spec["quantize"]
    es, spec_out = _serve(cfg, params, ec, prompts, quantize=q,
                          plan_tiers=(0.0, 0.5), speculate_k=3)
    _, oracle = _serve(cfg, params, ec, prompts, quantize=q, fused=False)
    assert spec_out == oracle
    if family == "moe":
        # the windowed scorer has no expert layer: speculation must be
        # gated off, not approximated
        assert not es._spec_windowed
        assert es.spec_stats["verify_blocks"] == 0
    else:
        assert es.spec_stats["verify_blocks"] > 0


def test_two_sided_config_disables_speculation():
    """Two-sided dispatch is not bitwise-stable across the verify window's
    row count on XLA:CPU (the activation-masked dot fuses m-dependently,
    last-ulp drift flips near-tied argmaxes — observed as stream divergence
    from the per-token oracle at real prompt mixes).  The engine must gate
    speculation OFF for these configs and serve exact plain blocks."""
    cfg = dataclasses.replace(
        get_smoke_config("stablelm-1.6b"),
        sparsity=SparsityConfig(weight_sparsity=0.5,
                                activation_threshold=0.05))
    params = _pruned_params(cfg)
    ec = decode_exec_config(cfg, 3, params=params)
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab - 1, size=rng.integers(3, 9))
               .astype(np.int32) for _ in range(6)]
    es, spec_out = _serve(cfg, params, ec, prompts,
                          plan_tiers=(0.0, 0.5), speculate_k=3)
    _, oracle = _serve(cfg, params, ec, prompts, fused=False)
    assert not es._spec_windowed
    assert es.spec_stats["verify_blocks"] == 0
    assert spec_out == oracle


@settings(max_examples=2, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_speculative_staggered_arrivals_exact(seed):
    cfg, params, ec = _get_setup()
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab - 1, size=rng.integers(1, 9))
               .astype(np.int32) for _ in range(6)]
    _, oracle = _serve(cfg, params, ec, prompts, fused=False)
    _, spec_out = _serve(cfg, params, ec, prompts,
                         stagger_rng=np.random.default_rng(seed + 1),
                         plan_tiers=(0.0, 0.5), speculate_k=3)
    assert {u: t for u, t in spec_out.items()} == oracle


def test_self_draft_engine_accepts_everything(tier_setup):
    """Single-tier engine drafting under the full plan: every draft must
    be accepted.  ``eos_id=None`` and max_new a multiple of k+1 keep any
    row from stopping mid-window — a stop truncates the emit count, which
    the host-side accounting cannot distinguish from a rejection."""
    cfg, params, ec = tier_setup
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab - 1, size=4).astype(np.int32)
               for _ in range(4)]

    def run(**kw):
        eng = ServeEngine(cfg, params, n_slots=4, max_seq=48, exec_cfg=ec,
                          decode_block=8, eos_id=None, **kw)
        for p in prompts:
            eng.submit(p, max_new=8)       # 8 = 2 windows of k+1 = 4
        return eng, eng.run_until_drained()

    eng, out = run(speculate_k=3)
    _, oracle = run(fused=False)
    assert out == oracle
    assert eng.spec_stats["drafted"] > 0
    assert eng.speculative_acceptance() == 1.0


def test_sampled_speculative_streams_exact(tier_setup):
    cfg, params, ec = tier_setup
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab - 1, size=3).astype(np.int32)
               for _ in range(4)]

    def run(**kw):
        eng = ServeEngine(cfg, params, n_slots=3, max_seq=48, exec_cfg=ec,
                          decode_block=8, eos_id=5, **kw)
        for j, p in enumerate(prompts):
            s = (SamplingParams(temperature=0.8, top_k=20, seed=j)
                 if j % 2 else None)
            eng.submit(p, max_new=8, sampling=s)
        return eng.run_until_drained()

    assert run(plan_tiers=(0.0, 0.5), speculate_k=3) == run()


# ---------------------------------------------------------------------------
# engine: drain / routing / admission satellites
# ---------------------------------------------------------------------------

def test_verify_blocks_drain_on_occupancy_change(tier_setup):
    """Regression: the clean-drain rule must cover in-flight *verify*
    blocks.  Uneven budgets force finishes while speculated verify blocks
    are in flight; every drained token must still be oracle-exact and no
    block may be stranded."""
    cfg, params, ec = tier_setup
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, cfg.vocab - 1, size=2).astype(np.int32)
               for _ in range(5)]

    def run(**kw):
        eng = ServeEngine(cfg, params, n_slots=2, max_seq=48, exec_cfg=ec,
                          decode_block=8, eos_id=None, **kw)
        for j, p in enumerate(prompts):
            eng.submit(p, max_new=3 + 4 * j)    # staggered finish times
        out = eng.run_until_drained()
        assert not eng._inflight               # nothing stranded
        return eng, out

    eng, out = run(plan_tiers=(0.0, 0.5), speculate_k=3,
                   async_dispatch=True)
    _, oracle = run(fused=False)
    assert out == oracle
    assert eng.spec_stats["verify_blocks"] > 0


def test_latency_class_routes_to_pruned_tier(tier_setup):
    """A class-1 request decodes under tier 1: its stream equals a plain
    engine whose *only* plan is the pruned tier (length-1 prompts so no
    prefill forward runs — prefill always uses the full plan)."""
    cfg, params, ec = tier_setup
    tier1 = compile_weight_plan(params, ec.schedules, prune_ratio=0.5)
    prompt = np.asarray([11], np.int32)

    eng = ServeEngine(cfg, params, n_slots=2, max_seq=48, exec_cfg=ec,
                      plan_tiers=(0.0, 0.5))
    eng.submit(prompt, max_new=8, latency_class=1)
    routed = list(eng.run_until_drained().values())

    ec1 = dataclasses.replace(ec, plan=tier1)
    ref = ServeEngine(cfg, params, n_slots=2, max_seq=48, exec_cfg=ec1,
                      verify_plan=False)
    ref.submit(prompt, max_new=8)
    expect = list(ref.run_until_drained().values())
    assert routed == expect

    # class 0 must stay on the full plan
    eng2 = ServeEngine(cfg, params, n_slots=2, max_seq=48, exec_cfg=ec,
                      plan_tiers=(0.0, 0.5))
    eng2.submit(prompt, max_new=8, latency_class=0)
    full = ServeEngine(cfg, params, n_slots=2, max_seq=48, exec_cfg=ec)
    full.submit(prompt, max_new=8)
    assert (list(eng2.run_until_drained().values())
            == list(full.run_until_drained().values()))


def test_priority_admission_schedule_invariant(tier_setup):
    cfg, params, ec = tier_setup
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab - 1, size=rng.integers(1, 6))
               .astype(np.int32) for _ in range(6)]

    def run(pol):
        eng = ServeEngine(cfg, params, n_slots=2, max_seq=48, exec_cfg=ec,
                          decode_block=8, eos_id=5, admission=pol)
        for j, p in enumerate(prompts):
            eng.submit(p, max_new=8, priority=(len(prompts) - j))
        return eng.run_until_drained()

    assert run(FIFOAdmission()) == run(PriorityAdmission())


def test_maybe_recalibrate_rebuilds_tiers():
    # recalibration is fed by two_sided popcounts, so this test needs an
    # activation threshold (speculation is then auto-gated off — the tier
    # rebuild it exercises is independent of drafting)
    cfg = dataclasses.replace(
        _sparse_cfg(d_ff=256), sparsity=SparsityConfig(
            weight_sparsity=0.5, activation_threshold=0.05))
    params = _pruned_params(cfg)
    ec = decode_exec_config(cfg, 3, params=params, collect_stats=True)
    eng = ServeEngine(cfg, params, n_slots=3, max_seq=48, exec_cfg=ec,
                      decode_block=8, plan_tiers=(0.0, 0.5), speculate_k=2)
    eng.submit(np.asarray([3, 7, 11], np.int32), max_new=4)
    eng.run_until_drained()
    measured = eng.maybe_recalibrate(drift_threshold=-1.0)
    assert measured is not None           # forced trip
    assert len(eng.plan_tiers) == 2
    assert eng.plan_tiers[1].prune_ratio == 0.5
    assert len(eng._tier_params) == 2
    # engine still serves exactly after the rebuild (drain re-collects the
    # first finished request too — compare the new uid's stream only)
    uid = eng.submit(np.asarray([5, 9], np.int32), max_new=6)
    out = eng.run_until_drained()
    ref = ServeEngine(cfg, params, n_slots=3, max_seq=48,
                      exec_cfg=eng.exec_cfg, fused=False)
    ref.submit(np.asarray([5, 9], np.int32), max_new=6)
    assert out[uid] == list(ref.run_until_drained().values())[0]


def test_warmup_precompiles_spec_shapes(tier_setup):
    """Warmup must cover every dispatchable executable with tiers and
    speculation on (per-tier block lengths + the greedy verify shape) —
    exercised on a tiny engine so the compile bill stays bounded."""
    cfg, params, ec = tier_setup
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=16, exec_cfg=ec,
                      decode_block=4, plan_tiers=(0.0, 0.5), speculate_k=2)
    eng.warmup()
    eng.submit(np.asarray([3], np.int32), max_new=4)
    assert eng.run_until_drained()


def test_engine_validates_tier_args(tier_setup):
    cfg, params, ec = tier_setup
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, exec_cfg=ec, plan_tiers=(0.5, 0.0))
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, exec_cfg=ec, plan_tiers=(0.25,))
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, exec_cfg=ec, speculate_k=-1)
    with pytest.raises(ValueError):
        ServeEngine(cfg, params, plan_tiers=(0.0, 0.5))   # unplanned
    eng = ServeEngine(cfg, params, exec_cfg=ec)
    with pytest.raises(ValueError):
        eng.submit(np.asarray([3], np.int32), latency_class=-1)
