"""Precompiled weight-sparsity plans (engine bring-up hoist).

Coverage for the plan subsystem: the plan-based ``flex_matmul`` path must be
bitwise-identical to the trace-time path (same bitmaps → same masked
product) and match dense within float tolerance; ``ServeEngine`` under a
plan must emit exactly the tokens of the PR-1 engines; the jitted decode
step must build no weight-side bitmap/argsort ops (verified on the jaxpr);
``max_nnz`` must be tight (strictly below ``tk`` for structured-pruned
weights); over-tight plans must fail loudly — including at trace time under
jit; and runtime activation popcounts must accumulate for calibration.
"""
import dataclasses

from hypothesis import given, settings, strategies as st
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SparsityConfig, get_smoke_config
from repro.core import sparsity as S
from repro.core.descriptors import NetworkSchedule, SiteDescriptor
from repro.core.flextree import ReduceConfig
from repro.core.scheduler import MatmulSchedule
from repro.kernels import ops
from repro.models import model as model_lib
from repro.serve.engine import ServeEngine, decode_exec_config

TOL = dict(rtol=2e-5, atol=2e-4)
SITE = "mlp.in"


def _table(mode, m, n, k, stationarity="output", blocks=(32, 32, 32)):
    bm, bn, bk = blocks
    sched = MatmulSchedule(stationarity=stationarity, bm=bm, bn=bn, bk=bk,
                           sparsity_mode=mode)
    ns = NetworkSchedule(arch="test", shape="test")
    ns.sites[SITE] = SiteDescriptor(
        site=SITE, m=m, n=n, k=k, schedule=sched,
        reduce=ReduceConfig(axis_name="model", ic_p=1, strategy="psum"),
        sparsity_mode=mode)
    return ns


def _operands(rng, m, k, n, max_live=2, act_thr=0.8, blocks=(32, 32)):
    bk, bn = blocks
    w = S.prune_k_blocks(rng.normal(size=(k, n)).astype(np.float32),
                         bk, bn, max_live)
    x = rng.normal(size=(m, k)).astype(np.float32)
    x = np.where(np.abs(x) > act_thr, x, 0.0)
    return x, w


# ---------------------------------------------------------------------------
# flex_matmul plan path vs trace-time path vs dense
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["weight", "two_sided"])
@pytest.mark.parametrize("stationarity", ["output", "weight", "input"])
def test_plan_path_bitwise_equals_trace_path(rng, mode, stationarity):
    m, k, n = 96, 128, 80
    x, w = _operands(rng, m, k, n)
    ns = _table(mode, m, n, k, stationarity=stationarity)
    pw = S.plan_weight(w, site=SITE, mode=mode, bm=32, bk=32, bn=32)
    assert pw.max_nnz < pw.tk        # structured pruning → strictly tight
    with ops.exec_config(ops.ExecConfig(use_pallas=False, schedules=ns)):
        trace = ops.flex_matmul(jnp.asarray(x), jnp.asarray(w), site=SITE)
        planned = ops.flex_matmul(jnp.asarray(x), pw, site=SITE)
    # same bitmaps → same masked product: bitwise, not just close
    np.testing.assert_array_equal(np.asarray(planned), np.asarray(trace))
    np.testing.assert_allclose(np.asarray(planned), x @ w, **TOL)


@pytest.mark.parametrize("mode", ["weight", "two_sided"])
def test_plan_path_pallas_interpret(rng, mode):
    m, k, n = 64, 96, 64
    x, w = _operands(rng, m, k, n)
    pw = S.plan_weight(w, site=SITE, mode=mode, bm=32, bk=32, bn=32)
    with ops.exec_config(ops.ExecConfig(use_pallas=True, interpret=True)):
        out = ops.flex_matmul(jnp.asarray(x), pw, site=SITE)
    np.testing.assert_allclose(np.asarray(out), x @ w, **TOL)


def test_plan_path_under_jit_and_batched(rng):
    b, s, k, n = 2, 24, 64, 48
    x = rng.normal(size=(b, s, k)).astype(np.float32)
    x = np.where(np.abs(x) > 0.5, x, 0.0)
    w = S.prune_k_blocks(rng.normal(size=(k, n)).astype(np.float32),
                         32, 16, 1)
    pw = S.plan_weight(w, site=SITE, mode="two_sided", bm=32, bk=32, bn=16)
    out = jax.jit(lambda a, p: ops.flex_matmul(a, p, site=SITE))(
        jnp.asarray(x), pw)
    np.testing.assert_allclose(np.asarray(out), x @ w, **TOL)


def test_plan_disabled_falls_back_dense(rng):
    m, k, n = 32, 64, 32
    x, w = _operands(rng, m, k, n)
    pw = S.plan_weight(w, site=SITE, mode="two_sided", bm=32, bk=32, bn=32)
    with ops.exec_config(ops.ExecConfig(sparse_dispatch=False)):
        out = ops.flex_matmul(jnp.asarray(x), pw, site=SITE)
    np.testing.assert_allclose(np.asarray(out), x @ w, **TOL)


def test_planned_weight_rmatmul_fallback(rng):
    """Raw ``x @ w`` call sites (decode fast paths that bypass flex_matmul)
    must see the dense weight through a PlannedWeight."""
    x = rng.normal(size=(4, 32)).astype(np.float32)
    w = rng.normal(size=(32, 16)).astype(np.float32)
    pw = S.plan_weight(w, site=SITE, bm=16, bk=16, bn=16)
    np.testing.assert_allclose(np.asarray(jnp.asarray(x) @ pw), x @ w, **TOL)
    assert pw.shape == w.shape and pw.ndim == 2


# ---------------------------------------------------------------------------
# combine_with_activation_meta ≡ build_block_sparse_meta_jnp (property)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(tm=st.integers(1, 5), tk=st.integers(1, 6), tn=st.integers(1, 5),
       a_density=st.floats(0.0, 1.0), b_density=st.floats(0.0, 1.0),
       seed=st.integers(0, 2**16))
def test_combine_matches_trace_builder(tm, tk, tn, a_density, b_density,
                                       seed):
    rng = np.random.default_rng(seed)
    a_bm = rng.random((tm, tk)) < a_density
    b_bm = rng.random((tk, tn)) < b_density
    wkidx, wkcnt = S.weight_side_lists(b_bm)
    got = S.combine_with_activation_meta(
        jnp.asarray(a_bm), jnp.asarray(wkidx), jnp.asarray(wkcnt),
        jnp.asarray(b_bm))
    want = S.build_block_sparse_meta_jnp(jnp.asarray(a_bm),
                                         jnp.asarray(b_bm),
                                         max_nnz=int(wkidx.shape[-1]))
    np.testing.assert_array_equal(np.asarray(got.kcnt), np.asarray(want.kcnt))
    np.testing.assert_array_equal(np.asarray(got.kidx), np.asarray(want.kidx))


@settings(max_examples=15, deadline=None)
@given(tm=st.integers(1, 5), tk=st.integers(1, 6), tn=st.integers(1, 5),
       b_density=st.floats(0.0, 1.0), seed=st.integers(0, 2**16))
def test_weight_plan_meta_matches_trace_builder(tm, tk, tn, b_density, seed):
    """Weight mode (all-ones IF bitmap): the no-sort broadcast equals the
    argsort builder entry for entry."""
    rng = np.random.default_rng(seed)
    b_bm = rng.random((tk, tn)) < b_density
    wkidx, wkcnt = S.weight_side_lists(b_bm)
    got = S.weight_plan_meta(jnp.asarray(wkidx), jnp.asarray(wkcnt),
                             jnp.asarray(b_bm), tm)
    want = S.build_block_sparse_meta_jnp(jnp.ones((tm, tk), bool),
                                         jnp.asarray(b_bm),
                                         max_nnz=int(wkidx.shape[-1]))
    np.testing.assert_array_equal(np.asarray(got.kcnt), np.asarray(want.kcnt))
    np.testing.assert_array_equal(np.asarray(got.kidx), np.asarray(want.kidx))


# ---------------------------------------------------------------------------
# compile_weight_plan / attach / engine integration
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_setup():
    # d_ff widened so mlp.out has K > the largest schedule block → tk > 1,
    # a real config where the tight bound can be strictly below tk
    cfg = dataclasses.replace(get_smoke_config("stablelm-1.6b"), d_ff=1280)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    sp_cfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(weight_sparsity=0.5,
                                     activation_threshold=0.05))
    # prune mlp.out's stacked weight per output column at the plan's block
    # granularity so every column drops K-blocks → tight max_nnz < tk
    ec0 = decode_exec_config(sp_cfg, n_slots=2)
    d = ec0.schedules.sites["mlp.out"]
    bk, bn = min(d.schedule.bk, cfg.d_ff), min(d.schedule.bn, cfg.d_model)
    w_out = np.asarray(params["stack"]["layers"]["mlp"]["w_out"])
    pruned = np.stack([S.prune_k_blocks(w_out[i], bk, bn,
                                        max(1, -(-cfg.d_ff // bk) - 1))
                       for i in range(w_out.shape[0])])
    params = jax.tree_util.tree_map(lambda a: a, params)     # shallow copy
    params["stack"]["layers"]["mlp"]["w_out"] = jnp.asarray(pruned)
    return cfg, sp_cfg, params


def test_compile_weight_plan_shrinks_max_nnz(smoke_setup):
    cfg, sp_cfg, params = smoke_setup
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params)
    assert ec.plan is not None and ec.plan.entries
    by_site = {e.site: e for e in ec.plan.entries.values()}
    # gate sites get their own plan entries (descriptor-table satellite)
    assert "mlp.gate" in by_site
    out = by_site["mlp.out"]
    assert out.tk > 1
    assert out.max_nnz < out.tk          # strictly tight on a real config
    assert all(e.max_nnz <= e.tk for e in ec.plan.entries.values())
    # measured density replaced the 0.5/profile prior in the selector
    assert 0.0 < ec.plan.wt_densities()["mlp.out"] < 1.0
    # plan stats are artifact-ready: density, max_nnz, bytes saved
    stats = ec.plan.stats()["stack/layers/mlp/w_out"]
    assert stats["bytes_saved"] > 0
    assert 0.0 < stats["wt_density"] < 1.0
    # ZVC packing round-trips to the exact stacked weight
    w = np.asarray(params["stack"]["layers"]["mlp"]["w_out"])
    np.testing.assert_array_equal(
        S.zvc_decode_np(out.zvc_values, out.zvc_bitmap), w)


def test_engine_with_plan_matches_pr1_engines(smoke_setup):
    """Token streams: planned engine ≡ trace-time sparse engine ≡ dense."""
    cfg, sp_cfg, params = smoke_setup
    prompts = [np.array([3, 5, 7], np.int32), np.array([2, 4, 6], np.int32)]
    outs = {}
    for label, ec in (("dense", None),
                      ("trace", decode_exec_config(sp_cfg, n_slots=2)),
                      ("plan", decode_exec_config(sp_cfg, n_slots=2,
                                                  params=params))):
        eng = ServeEngine(cfg, params, n_slots=2, max_seq=32, exec_cfg=ec)
        for p in prompts:
            eng.submit(p, max_new=4)
        outs[label] = list(eng.run_until_drained().values())
    assert outs["plan"] == outs["dense"]
    assert outs["plan"] == outs["trace"]


def test_planned_decode_step_matches_dense_logits(smoke_setup):
    cfg, sp_cfg, params = smoke_setup
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params)
    planned = ec.plan.attach(params)
    state = model_lib.init_decode_state(cfg, 2, 16, dtype=jnp.float32)
    toks = jnp.asarray([[3], [5]], jnp.int32)
    pos = jnp.asarray(0, jnp.int32)
    logits_d, _ = model_lib.decode_step(params, cfg, toks, state, pos)
    with ops.exec_config(ec):
        logits_p, _ = model_lib.decode_step(planned, sp_cfg, toks, state, pos)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_d),
                               **TOL)


def test_planned_decode_builds_no_weight_side_ops(smoke_setup):
    """Acceptance: with a plan, the jitted decode step contains no
    weight-side bitmap/argsort work.  Weight mode: zero sort ops at all
    (trace-time metadata needs one per sparse site); two_sided: the
    weight-bitmap reductions disappear (strictly fewer reduce_max ops)."""
    cfg, _, params = smoke_setup
    state = model_lib.init_decode_state(cfg, 2, 16, dtype=jnp.float32)
    toks = jnp.asarray([[3], [5]], jnp.int32)
    pos = jnp.asarray(0, jnp.int32)

    def jaxpr_for(sp, with_plan):
        sp_cfg = dataclasses.replace(cfg, sparsity=sp)
        ec = decode_exec_config(sp_cfg, n_slots=2,
                                params=params if with_plan else None)
        p = ec.plan.attach(params) if with_plan else params

        def f(pp, t, s):
            with ops.exec_config(ec):
                return model_lib.decode_step(pp, sp_cfg, t, s, pos)
        return str(jax.make_jaxpr(f)(p, toks, state))

    wt = SparsityConfig(weight_sparsity=0.5)
    assert jaxpr_for(wt, with_plan=False).count(" sort[") > 0
    assert jaxpr_for(wt, with_plan=True).count(" sort[") == 0

    two = SparsityConfig(weight_sparsity=0.5, activation_threshold=0.05)
    unplanned = jaxpr_for(two, with_plan=False)
    planned = jaxpr_for(two, with_plan=True)
    assert planned.count("reduce_max") < unplanned.count("reduce_max")
    assert planned.count(" sort[") <= unplanned.count(" sort[")


def test_activation_popcounts_accumulate(smoke_setup):
    cfg, sp_cfg, params = smoke_setup
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params,
                            collect_stats=True)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=32, exec_cfg=ec)
    eng.submit(np.array([3, 5, 7], np.int32), max_new=3)
    for _ in range(4):
        eng.step()
    dens = eng.activation_densities()
    assert dens, "no popcounts accumulated"
    assert all(0.0 < v <= 1.0 for v in dens.values())
    # measured densities feed back into the schedule selector
    ec2 = decode_exec_config(sp_cfg, n_slots=2, params=params,
                             act_densities=dens)
    assert ec2.schedules is not None and ec2.plan is not None


# ---------------------------------------------------------------------------
# over-tight plans fail loudly
# ---------------------------------------------------------------------------

def test_over_tight_plan_raises_with_coordinates(smoke_setup):
    cfg, sp_cfg, params = smoke_setup
    ec = decode_exec_config(sp_cfg, n_slots=2)
    with pytest.raises(ValueError, match=r"mlp\.(in|gate|out).*ni="):
        S.compile_weight_plan(params, ec.schedules,
                              max_nnz={"mlp.in": 0, "mlp.gate": 0,
                                       "mlp.out": 0})


def test_attach_rejects_mismatched_params(smoke_setup):
    """A plan compiled from different tensors (same shapes) must fail at
    attach, not silently skip live MACs."""
    cfg, sp_cfg, params = smoke_setup
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params)
    other = model_lib.init_params(cfg, jax.random.PRNGKey(7),
                                  dtype=jnp.float32)
    with pytest.raises(ValueError, match="does not cover"):
        ec.plan.attach(other)
    # the matching params attach cleanly
    assert ec.plan.attach(params) is not None


# ---------------------------------------------------------------------------
# Total site coverage: MoE expert tensors + lm_head (ISSUE 4)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def moe_setup():
    cfg = get_smoke_config("deepseek-moe-16b")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    # 3-D stacked and 4-D expert leaves get real zeros to skip
    params = {**params, "stack": jax.tree.map(
        lambda leaf: S.prune_stacked_magnitude(leaf, 0.5), params["stack"])}
    sp_cfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(weight_sparsity=0.5,
                                     activation_threshold=0.05))
    return cfg, sp_cfg, params


def test_moe_plan_covers_expert_and_head_leaves(moe_setup):
    cfg, sp_cfg, params = moe_setup
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params)
    assert ec.plan is not None
    by_site = {e.site: e for e in ec.plan.entries.values()}
    for site in ("moe.router", "moe.experts_in", "moe.experts_gate",
                 "moe.experts_out", "moe.shared_in", "moe.shared_gate",
                 "moe.shared_out", "lm_head"):
        assert site in by_site, site
    exp = by_site["moe.experts_in"]
    assert len(exp.lead) == 2          # (L, E): per-(layer, expert) metadata
    assert exp.lead[1] == cfg.moe.n_experts
    assert exp.wkidx.shape[:2] == exp.lead
    assert exp.max_nnz <= exp.tk
    st = exp.stats()
    assert st["experts"] == cfg.moe.n_experts
    assert len(st["expert_wt_density"]) == cfg.moe.n_experts
    assert all(0.0 < v < 1.0 for v in st["expert_wt_density"])
    head = by_site["lm_head"]
    assert head.transpose and head.lead == ()
    # leading dense layer's MLP is planned too (total coverage)
    assert "mlp.in" in by_site


def test_moe_planned_decode_matches_dense(moe_setup):
    cfg, sp_cfg, params = moe_setup
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params)
    planned = ec.plan.attach(params)
    state = model_lib.init_decode_state(cfg, 2, 16, dtype=jnp.float32)
    toks = jnp.asarray([[3], [5]], jnp.int32)
    pos = jnp.asarray(0, jnp.int32)
    logits_d, _ = model_lib.decode_step(params, cfg, toks, state, pos)
    with ops.exec_config(ec):
        logits_p, _ = model_lib.decode_step(planned, sp_cfg, toks, state,
                                            pos)
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_d),
                               **TOL)


def test_moe_engine_with_plan_matches_dense_tokens(moe_setup):
    cfg, sp_cfg, params = moe_setup
    prompts = [np.array([3, 5, 7], np.int32), np.array([2, 4, 6], np.int32)]
    outs = {}
    for label, ec in (("dense", None),
                      ("trace", decode_exec_config(sp_cfg, n_slots=2)),
                      ("plan", decode_exec_config(sp_cfg, n_slots=2,
                                                  params=params))):
        eng = ServeEngine(cfg, params, n_slots=2, max_seq=32, exec_cfg=ec)
        for p in prompts:
            eng.submit(p, max_new=4)
        outs[label] = list(eng.run_until_drained().values())
    assert outs["plan"] == outs["dense"]
    assert outs["plan"] == outs["trace"]


def test_moe_planned_decode_builds_no_weight_side_ops(moe_setup):
    """Acceptance (ISSUE 4): with a plan, the MoE decode step builds zero
    *weight-side* bitmap/argsort work.  The dropless serving MoE layer
    routes with top_k and sorts nothing, so the yardstick is the dense
    decode step: planned weight-mode adds no sort ops over dense (none),
    while the trace-time
    sparse step must argsort weight bitmaps; planned two_sided drops the
    weight-bitmap reductions (strictly fewer reduce_max than unplanned)."""
    cfg, _, params = moe_setup
    state = model_lib.init_decode_state(cfg, 2, 16, dtype=jnp.float32)
    toks = jnp.asarray([[3], [5]], jnp.int32)
    pos = jnp.asarray(0, jnp.int32)

    def jaxpr_for(sp, with_plan):
        sp_cfg = dataclasses.replace(cfg, sparsity=sp)
        ec = (decode_exec_config(sp_cfg, n_slots=2,
                                 params=params if with_plan else None)
              if sp is not None else None)
        p = (ec.plan.attach(params) if with_plan and ec is not None
             else params)

        def f(pp, t, s):
            if ec is None:
                return model_lib.decode_step(pp, cfg, t, s, pos)
            with ops.exec_config(ec):
                return model_lib.decode_step(pp, sp_cfg, t, s, pos)
        return str(jax.make_jaxpr(f)(p, toks, state))

    dense = jaxpr_for(None, with_plan=False)
    assert " top_k[" in dense          # routing
    dense_sorts = dense.count(" sort[")

    wt = SparsityConfig(weight_sparsity=0.5)
    assert jaxpr_for(wt, with_plan=False).count(" sort[") > dense_sorts
    assert jaxpr_for(wt, with_plan=True).count(" sort[") == dense_sorts

    two = SparsityConfig(weight_sparsity=0.5, activation_threshold=0.05)
    unplanned = jaxpr_for(two, with_plan=False)
    planned = jaxpr_for(two, with_plan=True)
    assert planned.count("reduce_max") < unplanned.count("reduce_max")
    assert planned.count(" sort[") <= unplanned.count(" sort[")


def test_head_plan_matmul_bitwise_equals_trace(rng):
    """lm_head leaves are stored (V, D); the plan compiles the transposed
    orientation and head_matmul dispatches it like any other planned site."""
    v, d, m = 96, 64, 8
    head = S.prune_k_blocks(rng.normal(size=(d, v)).astype(np.float32),
                            16, 16, 2).T.copy()
    x = rng.normal(size=(2, m, d)).astype(np.float32)
    ns = _table("weight", 2 * m, v, d, blocks=(8, 16, 16))
    ns.sites["lm_head"] = dataclasses.replace(ns.sites[SITE], site="lm_head",
                                              m=2 * m, n=v, k=d)
    pw = S.plan_weight(head, site="lm_head", mode="weight",
                       bm=8, bk=16, bn=16, transpose=True)
    assert pw.transpose and pw.max_nnz < pw.tk
    with ops.exec_config(ops.ExecConfig(schedules=ns)):
        trace = ops.head_matmul(jnp.asarray(x), jnp.asarray(head))
        planned = ops.head_matmul(jnp.asarray(x), pw)
    np.testing.assert_array_equal(np.asarray(planned), np.asarray(trace))
    np.testing.assert_allclose(np.asarray(planned),
                               x @ head.T, **TOL)


def test_plan_weight_transpose_with_leading_axes(rng):
    """Regression: ``transpose`` must permute only the last two axes
    (matching ``PlannedWeight.w_kn``), not reverse the whole stack — a
    batched (E, N, K) plan dispatches identically to its (E, K, N) twin."""
    e, c, k, n = 3, 8, 64, 32
    w_nk = np.stack([S.prune_k_blocks(
        rng.normal(size=(k, n)).astype(np.float32), 16, 16, 2).T
        for _ in range(e)])                                  # (E, N, K)
    x = rng.normal(size=(e, c, k)).astype(np.float32)
    pw = S.plan_weight(w_nk, site="moe.experts_in", mode="weight",
                       bm=8, bk=16, bn=16, transpose=True)
    pw_kn = S.plan_weight(np.swapaxes(w_nk, -1, -2), site="moe.experts_in",
                          mode="weight", bm=8, bk=16, bn=16)
    assert (pw.max_nnz, pw.tk) == (pw_kn.max_nnz, pw_kn.tk)
    got = ops.flex_expert_matmul(jnp.asarray(x), pw, site="moe.experts_in")
    want = ops.flex_expert_matmul(jnp.asarray(x), pw_kn,
                                  site="moe.experts_in")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tied_embeddings_head_never_planned():
    """Satellite guard: under ``tie_embeddings`` the head *is* the embed
    leaf — the plan must neither create an lm_head entry nor wrap/mutate
    the shared ``embed`` leaf (``embed()`` gathers rows from it)."""
    cfg = get_smoke_config("gemma-2b")
    assert cfg.tie_embeddings
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    sp_cfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(weight_sparsity=0.5,
                                     activation_threshold=0.05))
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params)
    assert ec.plan is not None and ec.plan.entries
    assert all(e.site != "lm_head" for e in ec.plan.entries.values())
    assert not any(k.startswith("embed") for k in ec.plan.entries)
    attached = ec.plan.attach(params)
    assert not isinstance(attached["embed"], S.PlannedWeight)
    np.testing.assert_array_equal(np.asarray(attached["embed"]),
                                  np.asarray(params["embed"]))
    # the tied engine still emits the dense engine's tokens
    outs = []
    for e2 in (None, ec):
        eng = ServeEngine(cfg, params, n_slots=2, max_seq=32, exec_cfg=e2)
        eng.submit(np.array([3, 5, 7], np.int32), max_new=4)
        outs.append(list(eng.run_until_drained().values()))
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# auto-recalibration policy (ROADMAP open item)
# ---------------------------------------------------------------------------

def test_activation_density_drift_pure():
    from repro.serve.engine import activation_density_drift
    assert activation_density_drift(None, {}) == 0.0
    # absent baseline sites measure drift against the 0.5 prior
    assert activation_density_drift(None, {"mlp.in": 0.9}) == \
        pytest.approx(0.4)
    assert activation_density_drift({"mlp.in": 0.85}, {"mlp.in": 0.9}) == \
        pytest.approx(0.05)
    assert activation_density_drift({"mlp.in": 0.2},
                                    {"mlp.in": 0.25, "mlp.out": 0.9}) == \
        pytest.approx(0.4)


def test_maybe_recalibrate_trigger_logic(smoke_setup):
    """The trigger fires on drift past the threshold and stays quiet inside
    it — unit-tested without a real recompile (recompile=False)."""
    cfg, sp_cfg, params = smoke_setup
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params,
                            collect_stats=True)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=32, exec_cfg=ec)
    # no popcounts yet → no trigger
    assert eng.maybe_recalibrate(recompile=False) is None
    # injected density 0.95 vs the 0.5 prior → drift 0.45 > 0.15
    eng._stats.record("mlp.in", 95, 100)
    out = eng.maybe_recalibrate(drift_threshold=0.15, recompile=False)
    assert out == {"mlp.in": 0.95}
    assert eng.exec_cfg is ec          # recompile=False: nothing swapped
    # within-threshold drift → no trigger
    eng._stats.record("mlp.in", 55, 100)
    assert eng.maybe_recalibrate(drift_threshold=0.15,
                                 recompile=False) is None
    # a recalibrated baseline suppresses the trigger at the same density
    ec2 = decode_exec_config(sp_cfg, n_slots=2, params=params,
                             collect_stats=True,
                             act_densities={"mlp.in": 0.95})
    eng2 = ServeEngine(cfg, params, n_slots=2, max_seq=32, exec_cfg=ec2)
    eng2._stats.record("mlp.in", 95, 100)
    assert eng2.maybe_recalibrate(drift_threshold=0.15,
                                  recompile=False) is None


def test_popcounts_survive_quiet_probe(smoke_setup):
    """Regression: the compiled decode step's debug callback closes over
    the collector object at trace time, so a probe must reset the window
    *in place* — a quiet (non-triggering) probe followed by more steps
    must keep accumulating, not record into an orphaned collector."""
    cfg, sp_cfg, params = smoke_setup
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params,
                            collect_stats=True)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=32, exec_cfg=ec)
    eng.submit(np.array([3, 5, 7], np.int32), max_new=8)
    for _ in range(3):
        eng.step()
    # quiet probe: measurements exist but an impossible threshold keeps it
    # from triggering; the window is consumed in place
    assert eng.maybe_recalibrate(drift_threshold=10.0) is None
    assert eng.activation_densities() == {}
    for _ in range(3):
        eng.step()
    assert eng.activation_densities(), \
        "popcounts stopped accumulating after a quiet probe"


def test_maybe_recalibrate_rejects_handbuilt_exec_config(smoke_setup):
    """A hand-built ExecConfig (no arch_cfg) must fail loudly on a
    triggered recompile instead of silently rebuilding a dense table from
    the engine's own (possibly dense-twin) cfg."""
    cfg, sp_cfg, params = smoke_setup
    compiled = decode_exec_config(sp_cfg, n_slots=2)
    handbuilt = ops.ExecConfig(schedules=compiled.schedules,
                               collect_stats=True)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=32,
                      exec_cfg=handbuilt)
    eng._stats.record("mlp.in", 95, 100)
    # trigger-only probe still works (and consumes the popcount window)
    assert eng.maybe_recalibrate(drift_threshold=0.15,
                                 recompile=False) is not None
    assert eng.maybe_recalibrate(recompile=False) is None  # window consumed
    eng._stats.record("mlp.in", 95, 100)
    with pytest.raises(ValueError, match="arch_cfg"):
        eng.maybe_recalibrate(drift_threshold=0.15)


def test_maybe_recalibrate_recompiles_and_keeps_serving(smoke_setup):
    cfg, sp_cfg, params = smoke_setup
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params,
                            collect_stats=True)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=32, exec_cfg=ec)
    eng.submit(np.array([3, 5, 7], np.int32), max_new=6)
    for _ in range(3):
        eng.step()
    plan_before = eng.plan
    measured = eng.maybe_recalibrate(drift_threshold=0.0)  # force trigger
    assert measured
    assert eng.exec_cfg is not ec
    assert eng.exec_cfg.act_densities == measured
    # weights didn't change: when the re-selected schedules keep every
    # planned site's block granularity the old plan is *reused*, not
    # rebuilt (eng.plan stays the same object); a granularity change would
    # rebuild it — either way a plan is in force
    assert eng.plan is not None
    if eng.exec_cfg.plan is plan_before:
        assert eng.plan is plan_before
    assert eng.step()                  # serving continues under the new table


def test_over_tight_meta_raises_under_jit(rng):
    """Regression: an over-tight bound fails loudly at trace time (the plan
    metadata is concrete numpy inside the jitted caller), not by silently
    dropping live MACs."""
    x, w = _operands(rng, 64, 128, 64)
    a_bm = S.block_bitmap(x, 32, 32)
    b_bm = S.block_bitmap(w, 32, 32)
    tight = int(np.asarray(
        S.build_block_sparse_meta(x, w, 32, 32, 32).kcnt).max())
    assert tight > 1

    @jax.jit
    def f(q):
        meta = S.build_block_sparse_meta_jnp(a_bm, b_bm, max_nnz=tight - 1,
                                             site="mlp.in")
        return q * jnp.sum(meta.kcnt)

    with pytest.raises(ValueError, match=r"mlp\.in.*mi=\d+, ni=\d+"):
        f(jnp.float32(1.0))

    with pytest.raises(ValueError, match="output column"):
        S.weight_side_lists(b_bm, max_nnz=0, site="mlp.out")
