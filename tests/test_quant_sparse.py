"""int8 × sparsity: quantized weight plans, fused dispatch, engine knob.

The compounding claim of the PR: ZVC block skipping and int8 payloads save
bytes *multiplicatively*.  Covered here:

  * round-trip regressions — the 4-D (L, E, K, N) MoE ``dequantize_params``
    vmap composition and the transposed ``lm_head`` orientation,
  * quantization-target parity with the weight planner's site coverage
    (every plannable leaf must be a quantization target, tied head skipped
    by both layers),
  * zero preservation as a property: ``prune_k_blocks``-pruned blocks
    quantize to exactly 0, so ZVC block bitmaps are unchanged,
  * planned-quantized dispatch vs the int8 oracle on the Pallas-interpret
    and masked-XLA paths,
  * the engine ``quantize=`` knob: fused quantized serving matches the
    dequantized-dense oracle engine token-for-token (greedy, smoke scale)
    across the dense / MoE / tied-head families,
  * the byte model: plan stats report compounded int8+ZVC bytes, schedule
    selection ranks int8 weights cheaper than bf16.
"""
import dataclasses

from hypothesis import given, settings, strategies as st
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SparsityConfig, get_smoke_config
from repro.core import sparsity as S
from repro.core.descriptors import (compile_network_schedule, matmul_sites,
                                    site_plan_estimate)
from repro.core.scheduler import select_matmul_schedule
from repro.kernels import ops
from repro.kernels.ref import int8_matmul_ref
from repro.models import model as model_lib
from repro.quant.quantize import (QuantizedLinear, _MATMUL_LEAF,
                                  dequantize_leaf, dequantize_params,
                                  quantize_params, quantize_weight)
from repro.serve.engine import ServeEngine, decode_exec_config

from repro.configs.base import ShapeConfig


# ---------------------------------------------------------------------------
# round-trip regressions
# ---------------------------------------------------------------------------

def test_dequantize_params_4d_moe_roundtrip(rng):
    """The 4-D vmap-composition bug: expert leaves (L, E, K, N) must
    round-trip through quantize→dequantize with per-(L, E, N) scales."""
    w = jnp.asarray(rng.normal(size=(2, 3, 32, 16)).astype(np.float32))
    tree = {"moe": {"experts_in": w}}
    qt, stats = quantize_params(tree)
    qw = qt["moe"]["experts_in"]
    assert isinstance(qw, QuantizedLinear)
    assert qw.q.shape == (2, 3, 32, 16) and qw.scale.shape == (2, 3, 16)
    out = dequantize_params(qt, dtype=jnp.float32)["moe"]["experts_in"]
    assert out.shape == w.shape
    # per-channel symmetric RTN bound: |err| <= scale/2
    err = np.abs(np.asarray(out) - np.asarray(w))
    bound = np.asarray(qw.scale)[..., None, :] * 0.5 + 1e-6
    assert np.all(err <= bound)


def test_dequantize_params_lm_head_orientation(rng):
    """lm_head is quantized on the transposed (D, V) view (contraction-
    oriented, per-vocab-row scales) and transposed back on dequant."""
    head = jnp.asarray(rng.normal(size=(48, 32)).astype(np.float32))  # (V, D)
    qt, _ = quantize_params({"lm_head": head})
    qh = qt["lm_head"]
    assert qh.q.shape == (32, 48) and qh.scale.shape == (48,)
    out = dequantize_params(qt, dtype=jnp.float32)["lm_head"]
    assert out.shape == head.shape
    err = np.abs(np.asarray(out) - np.asarray(head))
    assert np.all(err <= np.asarray(qh.scale)[:, None] * 0.5 + 1e-6)
    # tied configs skip the head entirely
    qt2, _ = quantize_params({"lm_head": head}, tie_embeddings=True)
    assert not isinstance(qt2["lm_head"], QuantizedLinear)


@pytest.mark.parametrize("name", ["stablelm-1.6b", "deepseek-moe-16b",
                                  "gemma-2b", "recurrentgemma-9b"])
def test_quant_targets_cover_plannable_sites(name):
    """Parity satellite: every leaf the weight planner can compile must be
    a quantization target (the bug class: ``_MATMUL_LEAF`` missing lm_head
    / w_x silently left bf16 payloads in an int8 serving tree)."""
    cfg = get_smoke_config(name)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    qp, _ = quantize_params(params, tie_embeddings=cfg.tie_embeddings)
    shape = ShapeConfig(name="d", kind="decode", seq_len=1, global_batch=2)
    sites = {s for s, *_ in matmul_sites(cfg, shape)}
    quantized_paths = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            qp, is_leaf=lambda x: isinstance(x, QuantizedLinear)):
        if isinstance(leaf, QuantizedLinear):
            quantized_paths.add(S._path_keys(path))
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        keys = S._path_keys(path)
        site = S._site_for_path(keys)
        if site is None or site not in sites:
            continue
        if site == "lm_head" and cfg.tie_embeddings:
            assert keys not in quantized_paths   # tied head stays float
            continue
        if S._plannable_kn(leaf, site) is None:
            continue
        assert keys in quantized_paths, \
            f"plannable leaf {keys} [{site}] not quantized"


# ---------------------------------------------------------------------------
# zero preservation (the invariant the whole plan-reuse story rests on)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(k=st.integers(2, 6), n=st.integers(1, 4), live=st.integers(1, 3),
       bk=st.sampled_from([8, 16]), bn=st.sampled_from([8, 16]),
       seed=st.integers(0, 2**16))
def test_pruned_blocks_quantize_to_exact_zero(k, n, live, bk, bn, seed):
    """Property: blocks zeroed by ``prune_k_blocks`` quantize to exactly 0,
    so the ZVC block bitmap of the int8 payload equals the float one —
    quantization never resurrects (or kills) a block."""
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(k * bk, n * bn)).astype(np.float32)
    w = S.prune_k_blocks(w, bk, bn, min(live, k))
    qw = quantize_weight(jnp.asarray(w))
    q = np.asarray(qw.q)
    bm_f = S.block_bitmap(w, bk, bn)
    bm_q = S.block_bitmap(q, bk, bn)
    np.testing.assert_array_equal(bm_q, bm_f)
    # element-level: float zeros are int8 zeros
    assert np.all(q[w == 0.0] == 0)


# ---------------------------------------------------------------------------
# planned-quantized dispatch vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["xla", "pallas-interpret"])
@pytest.mark.parametrize("mode", ["weight", "two_sided"])
def test_quantized_plan_dispatch_matches_int8_oracle(rng, mode, use_pallas):
    m, k, n = 48, 256, 384
    w = S.prune_k_blocks(rng.normal(size=(k, n)).astype(np.float32),
                         32, 128, 5)
    qw = quantize_weight(jnp.asarray(w))
    pw = S.plan_weight(qw, site="t", mode=mode, bm=16, bk=32, bn=128)
    assert pw.quantized and pw.w.dtype == jnp.int8
    assert pw.max_nnz < pw.tk            # pruning made the bound tight
    x = rng.normal(size=(m, k)).astype(np.float32)
    x[np.abs(x) > 1.2] = 0.0
    oracle = int8_matmul_ref(jnp.asarray(x), qw.q, qw.scale)
    with ops.exec_config(ops.ExecConfig(use_pallas=use_pallas,
                                        interpret=use_pallas)):
        out = ops.flex_matmul(jnp.asarray(x), pw, site="t")
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-4)


def test_quantized_expert_plan_dispatch(rng):
    """(E, K, N) quantized planned dispatch through flex_expert_matmul on
    both execution paths."""
    e, c, k, n = 3, 16, 128, 128
    w = np.stack([S.prune_k_blocks(
        rng.normal(size=(k, n)).astype(np.float32), 32, 128, 2)
        for _ in range(e)])
    qw = jax.vmap(quantize_weight)(jnp.asarray(w))
    pw = S.plan_weight(qw, site="moe.experts_in", mode="weight",
                       bm=16, bk=32, bn=128)
    x = jnp.asarray(rng.normal(size=(e, c, k)).astype(np.float32))
    oracle = jnp.einsum("eck,ekn->ecn", x,
                        qw.q.astype(jnp.float32) * qw.scale[:, None, :])
    for up in (False, True):
        with ops.exec_config(ops.ExecConfig(use_pallas=up, interpret=up)):
            out = ops.flex_expert_matmul(x, pw, site="moe.experts_in")
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                                   rtol=2e-5, atol=2e-4)


def test_quantized_head_plan_dispatch(rng):
    """Transposed-site (lm_head) quantized plan: contraction-oriented int8
    payload, per-vocab-row scales, no swap at dispatch."""
    v, d, m = 384, 128, 8
    head = rng.normal(size=(v, d)).astype(np.float32)     # stored (V, D)
    qt, _ = quantize_params({"lm_head": jnp.asarray(head)})
    qh = qt["lm_head"]
    pw = S.plan_weight(qh, site="lm_head", mode="weight",
                       bm=8, bk=32, bn=128)
    assert not pw.transpose
    x = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    oracle = int8_matmul_ref(x, qh.q, qh.scale)
    for up in (False, True):
        with ops.exec_config(ops.ExecConfig(use_pallas=up, interpret=up)):
            out = ops.head_matmul(x, pw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                                   rtol=2e-5, atol=2e-4)


def test_plan_weight_rejects_quantized_transpose():
    qw = quantize_weight(jnp.ones((8, 8), jnp.float32))
    with pytest.raises(ValueError):
        S.plan_weight(qw, site="t", transpose=True)


# ---------------------------------------------------------------------------
# byte model: compounded int8 + ZVC economics
# ---------------------------------------------------------------------------

def test_scheduler_ranks_int8_weights_cheaper():
    s16 = select_matmul_schedule(8, 4096, 4096, sparsity_mode="weight",
                                 wt_density=0.5)
    s8 = select_matmul_schedule(8, 4096, 4096, sparsity_mode="weight",
                                wt_density=0.5, wt_bytes=1)
    assert s8.wt_bytes == 1 and s16.wt_bytes == 2
    assert s8.hbm_bytes < s16.hbm_bytes
    # decode is weight-bound: the site's traffic should drop ~2x
    assert s16.hbm_bytes / s8.hbm_bytes > 1.5


def test_compile_network_schedule_quantize_flag():
    cfg = dataclasses.replace(
        get_smoke_config("gemma-2b"),
        sparsity=SparsityConfig(weight_sparsity=0.5))
    shape = ShapeConfig(name="d", kind="decode", seq_len=1, global_batch=2)
    ns16 = compile_network_schedule(cfg, shape)
    ns8 = compile_network_schedule(cfg, shape, quantize=True)
    for site, d8 in ns8.sites.items():
        d16 = ns16.sites[site]
        if site == "lm_head":            # tied → never quantized
            assert d8.schedule.wt_bytes == 2
            continue
        assert d8.schedule.wt_bytes == 1
        assert d8.schedule.hbm_bytes < d16.schedule.hbm_bytes


def test_site_plan_estimate_reports_int8_columns():
    cfg = dataclasses.replace(
        get_smoke_config("stablelm-1.6b"),
        sparsity=SparsityConfig(weight_sparsity=0.5))
    shape = ShapeConfig(name="d", kind="decode", seq_len=1, global_batch=2)
    ns = compile_network_schedule(cfg, shape)
    for d in ns.sites.values():
        est = site_plan_estimate(d, cfg)
        assert est["int8_zvc_bytes"] > 0
        assert est["int8_zvc_bytes"] < est["zvc_bytes"]
        assert est["int8_vs_sparse_reduction"] > 1.0
        assert est["bytes_saved_int8"] >= est["bytes_saved"]


def test_plan_stats_compound_int8_and_zvc(rng):
    """Measured plan stats on a quantized tree: int8_zvc_bytes beats the
    sparse-only zvc_bytes by >= 1.5x (the acceptance floor) when the
    reference dtype is bf16."""
    w = np.stack([S.prune_k_blocks(
        rng.normal(size=(128, 128)).astype(np.float32), 32, 128, 2)
        for _ in range(2)])
    cfg = dataclasses.replace(
        get_smoke_config("stablelm-1.6b"), d_model=128, d_ff=128,
        sparsity=SparsityConfig(weight_sparsity=0.5))
    shape = ShapeConfig(name="d", kind="decode", seq_len=1, global_batch=2)
    ns = compile_network_schedule(cfg, shape)
    qw = jax.vmap(quantize_weight)(jnp.asarray(w))
    plan = S.compile_weight_plan(
        {"stack": {"layers": {"mlp": {"w_out": qw}}}}, ns, ref_elem_bytes=2)
    (stats,) = plan.stats().values()
    assert stats["quantized"]
    assert stats["int8_zvc_bytes"] < stats["zvc_bytes"]
    assert stats["int8_vs_sparse_reduction"] >= 1.5
    assert stats["bytes_saved_int8"] > stats["bytes_saved"]


# ---------------------------------------------------------------------------
# engine quantize= knob: fused quantized serving vs dequantized-dense oracle
# ---------------------------------------------------------------------------

def _family_setup(name):
    cfg = get_smoke_config(name)
    if name == "stablelm-1.6b":
        cfg = dataclasses.replace(cfg, d_ff=1280)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    sp_cfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(weight_sparsity=0.5,
                                     activation_threshold=0.05))
    if name == "stablelm-1.6b":
        # block-prune mlp.out so the plan's tight bound actually bites
        ec0 = decode_exec_config(sp_cfg, n_slots=2)
        d = ec0.schedules.sites["mlp.out"]
        bk = min(d.schedule.bk, cfg.d_ff)
        bn = min(d.schedule.bn, cfg.d_model)
        w_out = np.asarray(params["stack"]["layers"]["mlp"]["w_out"])
        pruned = np.stack(
            [S.prune_k_blocks(w_out[i], bk, bn,
                              max(1, -(-cfg.d_ff // bk) - 1))
             for i in range(w_out.shape[0])])
        params = jax.tree_util.tree_map(lambda a: a, params)
        params["stack"]["layers"]["mlp"]["w_out"] = jnp.asarray(pruned)
    return cfg, sp_cfg, params


def _drain(engine, prompts, max_new=8):
    uids = [engine.submit(p, max_new=max_new) for p in prompts]
    res = engine.run_until_drained()
    return [res[u] for u in uids]


@pytest.mark.parametrize("name", ["stablelm-1.6b", "deepseek-moe-16b",
                                  "gemma-2b"],
                         ids=["dense", "moe", "tied-head"])
def test_engine_quantized_fused_streams_match_oracle(name):
    """The tentpole acceptance: fused quantized decode (planned sparse +
    int8 epilogue, scan/vmap/attach all engaged) streams the same greedy
    tokens as a dequantized-dense oracle engine at smoke scale."""
    cfg, sp_cfg, params = _family_setup(name)
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params, quantize=True)
    assert ec.quantize
    assert ec.plan is not None and ec.plan.entries
    assert any(e.quantized for e in ec.plan.entries.values())
    eng_q = ServeEngine(cfg, params, n_slots=2, max_seq=32, exec_cfg=ec,
                        quantize=True)
    # oracle: same quantization error, no plan / no fusion — per-token loop
    qp, _ = quantize_params(params, tie_embeddings=cfg.tie_embeddings)
    eng_o = ServeEngine(cfg, dequantize_params(qp, dtype=jnp.float32),
                        n_slots=2, max_seq=32, fused=False)
    prompts = [np.array([3, 5, 7, 11], np.int32),
               np.array([2, 9], np.int32)]
    got = _drain(eng_q, prompts)
    want = _drain(eng_o, prompts)
    assert got == want, f"{name}: quantized fused streams diverge"


def test_engine_quantize_knob_implied_by_exec_cfg():
    """An exec config built with quantize=True implies engine quantization
    even when the ctor knob is omitted (int8 plan payloads cannot attach
    onto a float tree)."""
    cfg, sp_cfg, params = _family_setup("stablelm-1.6b")
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params, quantize=True)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=32, exec_cfg=ec)
    assert eng.quantize
    assert eng.quant_stats["n_quantized"] > 0
    (out,) = _drain(eng, [np.array([3, 5, 7], np.int32)], max_new=4)
    assert len(out) == 4


def test_engine_quantized_recalibrate_preserves_quantize():
    """maybe_recalibrate's rebuilt exec config keeps the int8 byte model
    and re-attaches onto the quantized tree."""
    cfg, sp_cfg, params = _family_setup("stablelm-1.6b")
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params, quantize=True,
                            collect_stats=True)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=32, exec_cfg=ec,
                      quantize=True)
    _drain(eng, [np.array([3, 5, 7, 11], np.int32)], max_new=6)
    measured = eng.maybe_recalibrate(drift_threshold=0.0)
    assert measured                       # prior 0.5 never matches exactly
    assert eng.exec_cfg.quantize
    assert eng.plan is not None
    attached = eng._exec_params["stack"]["layers"]["mlp"]["w_out"]
    assert isinstance(attached, S.PlannedWeight)
    assert attached.quantized and attached.w.dtype == jnp.int8
    # still serves correctly after the swap
    (out,) = _drain(eng, [np.array([4, 6], np.int32)], max_new=4)
    assert len(out) == 4
