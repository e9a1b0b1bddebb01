"""Serve-engine regressions: continuous batching slot lifecycle, decode
under a ``two_sided`` descriptor table matching the dense engine exactly
(the sparse dispatch skips zero blocks, it never approximates), and the
fused hot loop (``decode_many`` blocks + batched prefill + donated state)
matching the per-token oracle token-for-token across state families."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SparsityConfig, get_smoke_config
from repro.kernels import ops
from repro.models import model as model_lib
from repro.serve.engine import ServeEngine, decode_exec_config


def _engine(cfg, params, n_slots=2, exec_cfg=None):
    return ServeEngine(cfg, params, n_slots=n_slots, max_seq=32,
                       exec_cfg=exec_cfg)


@pytest.fixture(scope="module")
def cfg_and_params():
    cfg = get_smoke_config("stablelm-1.6b")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    return cfg, params


def test_continuous_batching_frees_and_reuses_slots(cfg_and_params):
    cfg, params = cfg_and_params
    eng = _engine(cfg, params, n_slots=2)
    prompts = [np.array([3, 5, 7], np.int32), np.array([2, 4], np.int32),
               np.array([9, 1, 8], np.int32), np.array([6], np.int32)]
    uids = [eng.submit(p, max_new=3) for p in prompts]
    assert len(eng.queue) == 4                    # nothing admitted yet
    results = eng.run_until_drained()
    # 4 requests drained through 2 slots → every freed slot was reused
    assert sorted(results) == sorted(uids)
    assert all(len(toks) == 3 for toks in results.values())
    assert not eng.queue
    assert all(s.req is None or s.req.done for s in eng.slots)


def test_two_sided_engine_matches_dense_tokens(cfg_and_params):
    """Same params, same prompts: the engine under a two_sided descriptor
    table must emit the dense engine's tokens."""
    cfg, params = cfg_and_params
    sp_cfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(weight_sparsity=0.5,
                                     activation_threshold=0.1))
    exec_cfg = decode_exec_config(sp_cfg, n_slots=2)
    assert exec_cfg.schedules is not None
    assert all(d.sparsity_mode == "two_sided"
               for d in exec_cfg.schedules.sites.values())

    prompts = [np.array([3, 5, 7], np.int32), np.array([2, 4, 6], np.int32)]
    outs = []
    for ec in (None, exec_cfg):
        eng = _engine(cfg, params, n_slots=2, exec_cfg=ec)
        for p in prompts:
            eng.submit(p, max_new=4)
        outs.append(eng.run_until_drained())
    dense, sparse = outs
    assert list(dense.values()) == list(sparse.values())


def test_weight_plan_engine_matches_dense_tokens(cfg_and_params):
    """Engine with a precompiled WeightSparsityPlan (weight metadata hoisted
    to bring-up) emits exactly the PR-1 engines' token streams."""
    cfg, params = cfg_and_params
    sp_cfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(weight_sparsity=0.5,
                                     activation_threshold=0.1))
    exec_cfg = decode_exec_config(sp_cfg, n_slots=2, params=params)
    assert exec_cfg.plan is not None and exec_cfg.plan.entries
    assert all(e.max_nnz <= e.tk for e in exec_cfg.plan.entries.values())

    prompts = [np.array([3, 5, 7], np.int32), np.array([2, 4, 6], np.int32)]
    outs = []
    for ec in (None, exec_cfg):
        eng = _engine(cfg, params, n_slots=2, exec_cfg=ec)
        for p in prompts:
            eng.submit(p, max_new=4)
        outs.append(eng.run_until_drained())
    dense, planned = outs
    assert list(dense.values()) == list(planned.values())


# ---------------------------------------------------------------------------
# Fused hot loop (ISSUE 5): decode_many blocks ≡ per-token oracle
# ---------------------------------------------------------------------------

_PROMPTS = [np.array([3, 5, 7], np.int32), np.array([2, 4], np.int32),
            np.array([9, 1, 8], np.int32), np.array([6], np.int32)]


def _drain_both(cfg, params, exec_cfg=None, prompts=_PROMPTS, max_new=4,
                n_slots=2, decode_block=3):
    """Run the same queue through the per-token oracle loop and the fused
    block loop; return both result dicts.  decode_block deliberately does
    not divide max_new, so block-boundary logic is exercised."""
    outs = []
    for fused in (False, True):
        eng = ServeEngine(cfg, params, n_slots=n_slots, max_seq=32,
                          exec_cfg=exec_cfg, fused=fused,
                          decode_block=decode_block)
        for p in prompts:
            eng.submit(p, max_new=max_new)
        outs.append(eng.run_until_drained())
    return outs


def test_fused_matches_per_token_dense(cfg_and_params):
    """Fused blocks emit exactly the oracle's tokens — mixed prompt
    lengths and queue churn (4 requests through 2 slots) included."""
    cfg, params = cfg_and_params
    oracle, fused = _drain_both(cfg, params)
    assert oracle == fused


def test_fused_matches_per_token_planned_sparse(cfg_and_params):
    """Fused ≡ per-token under a precompiled WeightSparsityPlan: the
    PlannedWeight pytree survives lax.scan + donation unchanged."""
    cfg, params = cfg_and_params
    sp_cfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(weight_sparsity=0.5,
                                     activation_threshold=0.1))
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params)
    assert ec.plan is not None and ec.plan.entries
    oracle, fused = _drain_both(cfg, params, exec_cfg=ec)
    assert oracle == fused


def test_fused_matches_per_token_moe():
    """MoE family: the dropless per-token expert layer gives the same
    stream on both paths (planned sparse dispatch included)."""
    cfg = get_smoke_config("deepseek-moe-16b")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    sp_cfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(weight_sparsity=0.5,
                                     activation_threshold=0.1))
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params)
    for exec_cfg in (None, ec):
        oracle, fused = _drain_both(cfg, params, exec_cfg=exec_cfg,
                                    prompts=_PROMPTS[:2])
        assert oracle == fused


def test_fused_matches_per_token_tied_head():
    """Tied-embeddings family (gemma): the head is the embed leaf — the
    on-device argmax runs over the tied logits path."""
    cfg = get_smoke_config("gemma-2b")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    assert cfg.tie_embeddings
    oracle, fused = _drain_both(cfg, params)
    assert oracle == fused


@pytest.mark.parametrize("arch", ["mamba2-1.3b", "recurrentgemma-9b"])
def test_fused_matches_per_token_recurrent(arch):
    """Recurrent state families (SSM / RG-LRU): the per-layer recurrent
    leaves thread through the decode_many scan carry and the prefill
    slot-masked merge."""
    cfg = get_smoke_config(arch)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    oracle, fused = _drain_both(cfg, params, prompts=_PROMPTS[:3],
                                max_new=3)
    assert oracle == fused


def test_slot_reuse_no_recurrent_state_leak():
    """Regression for the zero-reset in prefill_into_slot: a freed slot's
    recurrent state (SSM) must not bleed into the next occupant — the
    second request through a 1-slot engine gets the tokens it gets from a
    fresh engine."""
    cfg = get_smoke_config("mamba2-1.3b")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.float32)
    fresh = ServeEngine(cfg, params, n_slots=1, max_seq=32)
    fresh.submit(_PROMPTS[2], max_new=4)
    iso = list(fresh.run_until_drained().values())[0]
    for fused in (False, True):
        eng = ServeEngine(cfg, params, n_slots=1, max_seq=32, fused=fused)
        u1 = eng.submit(_PROMPTS[0], max_new=4)   # occupies, then frees
        u2 = eng.submit(_PROMPTS[2], max_new=4)   # reuses the slot
        res = eng.run_until_drained()
        assert res[u2] == iso, f"fused={fused}: state leaked into reused slot"
        assert len(res[u1]) == 4


def test_staggered_admit_per_slot_positions(cfg_and_params):
    """Regression for the lockstep ``pos = max(live pos)`` hack: requests
    admitted at different depths must decode at their own positions.  Every
    request's tokens must equal the tokens it gets running *alone* —
    exactly what lockstep positions broke for staggered admits."""
    cfg, params = cfg_and_params
    prompts = [np.array([3, 5, 7, 9, 2], np.int32),
               np.array([8, 1], np.int32),
               np.array([4, 4, 4], np.int32)]
    iso = {}
    for j, p in enumerate(prompts):
        eng = ServeEngine(cfg, params, n_slots=1, max_seq=32)
        eng.submit(p, max_new=6)
        iso[j] = list(eng.run_until_drained().values())[0]
    for fused in (False, True):
        eng = ServeEngine(cfg, params, n_slots=2, max_seq=32, fused=fused,
                          decode_block=4)
        u0 = eng.submit(prompts[0], max_new=6)
        u1 = eng.submit(prompts[1], max_new=6)
        # a third request arrives mid-flight → admitted at a different
        # depth than the running slots
        if fused:
            eng.decode_block_step(2)
        else:
            eng.step()
            eng.step()
        u2 = eng.submit(prompts[2], max_new=6)
        res = eng.run_until_drained()
        got = [res[u0], res[u1], res[u2]]
        assert got == [iso[0], iso[1], iso[2]], f"fused={fused}: {got}"


def test_popcounts_and_recalibrate_after_fused_run(cfg_and_params):
    """Popcount feedback (debug callbacks inside the scanned block) and
    maybe_recalibrate survive the fused loop: densities accumulate, the
    recompiled executables keep serving, tokens stay the oracle's."""
    cfg, params = cfg_and_params
    sp_cfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(weight_sparsity=0.5,
                                     activation_threshold=0.1))
    ec = decode_exec_config(sp_cfg, n_slots=2, params=params,
                            collect_stats=True)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=32, exec_cfg=ec,
                      fused=True, decode_block=4)
    u1 = eng.submit(_PROMPTS[0], max_new=8)
    first = eng.run_until_drained()
    assert eng.activation_densities(), "no popcounts after a fused run"
    assert eng.maybe_recalibrate(drift_threshold=0.0) is not None
    u2 = eng.submit(_PROMPTS[0], max_new=8)
    again = eng.run_until_drained()
    # same prompt, same params → the post-recalibration engine must emit
    # the same stream (schedules change dispatch, never numerics)
    assert again[u2] == first[u1]


def test_donated_state_matches_undonated(cfg_and_params):
    """donate_state only changes buffer aliasing, never tokens."""
    cfg, params = cfg_and_params
    outs = []
    for donate in (True, False):
        eng = ServeEngine(cfg, params, n_slots=2, max_seq=32, fused=True,
                          donate_state=donate)
        for p in _PROMPTS[:2]:
            eng.submit(p, max_new=4)
        outs.append(eng.run_until_drained())
    assert outs[0] == outs[1]


def test_queue_is_constant_time_deque(cfg_and_params):
    """The request queue must be a deque (O(1) admits under deep queues)."""
    import collections
    cfg, params = cfg_and_params
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=32)
    assert isinstance(eng.queue, collections.deque)


def test_two_sided_decode_step_matches_dense_logits(cfg_and_params):
    """One decode step, logits-level: dense vs two_sided dispatch."""
    cfg, params = cfg_and_params
    sp_cfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(weight_sparsity=0.4,
                                     activation_threshold=0.05))
    n_slots = 2
    state = model_lib.init_decode_state(cfg, n_slots, 16, dtype=jnp.float32)
    toks = jnp.asarray([[3], [5]], jnp.int32)
    pos = jnp.asarray(0, jnp.int32)
    logits_d, _ = model_lib.decode_step(params, cfg, toks, state, pos)
    with ops.exec_config(decode_exec_config(sp_cfg, n_slots=n_slots)):
        logits_s, _ = model_lib.decode_step(params, cfg, toks, state, pos)
    np.testing.assert_allclose(np.asarray(logits_s), np.asarray(logits_d),
                               rtol=2e-5, atol=2e-4)
