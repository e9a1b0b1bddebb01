"""MoE dispatch correctness: sort-based vs GShard oracle vs EP shard_map,
and the planned sparse-dispatch coverage of the expert einsum sites
(``moe.experts_*``) — ``apply_moe`` under ``weight``/``two_sided`` descriptor
tables must match the oracle token-for-token (blocks are skipped, never
approximated)."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import SHAPES, SparsityConfig, get_config, \
    get_smoke_config
from repro.core import sparsity as S
from repro.core.descriptors import compile_network_schedule, \
    site_plan_estimate
from repro.kernels import ops
from repro.models import moe as M
from repro.serve.engine import decode_exec_config

from conftest import run_with_devices


def _cfg(cf=8.0, arch="deepseek-moe-16b"):
    cfg = get_smoke_config(arch)
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=cf))


def _sparse_cfg(cfg, mode):
    sp = (SparsityConfig(weight_sparsity=0.5) if mode == "weight"
          else SparsityConfig(weight_sparsity=0.5,
                              activation_threshold=0.05))
    return dataclasses.replace(cfg, sparsity=sp)


def _prune_experts(p, max_live=1, bk=16, bn=16):
    """Structured-prune every expert tensor so the weight bitmaps see real
    zeros and the plan's tight bound drops below tk."""
    out = dict(p)
    for key in ("experts_in", "experts_gate", "experts_out"):
        w = np.asarray(p[key])
        pruned = np.stack([S.prune_k_blocks(w[e], bk, bn, max_live)
                           for e in range(w.shape[0])])
        out[key] = jnp.asarray(pruned, p[key].dtype)
    return out


def test_local_matches_gshard_no_drops(rng):
    cfg = _cfg(cf=8.0)
    p = M.init_moe(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 16, cfg.d_model)).astype(np.float32))
    y_local = M.apply_moe(p, cfg, x)
    y_oracle = M.apply_moe_gshard(p, cfg, x)
    np.testing.assert_allclose(np.asarray(y_local), np.asarray(y_oracle),
                               rtol=1e-4, atol=1e-4)


def test_local_matches_gshard_with_drops(rng):
    """Same first-come capacity policy → identical drops."""
    cfg = _cfg(cf=0.5)
    p = M.init_moe(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 32, cfg.d_model)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(M.apply_moe(p, cfg, x)),
                               np.asarray(M.apply_moe_gshard(p, cfg, x)),
                               rtol=1e-4, atol=1e-4)


def test_top1_arch(rng):
    cfg = _cfg(cf=8.0, arch="llama4-scout-17b-a16e")
    p = M.init_moe(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)).astype(np.float32))
    np.testing.assert_allclose(np.asarray(M.apply_moe(p, cfg, x)),
                               np.asarray(M.apply_moe_gshard(p, cfg, x)),
                               rtol=1e-4, atol=1e-4)


def test_moe_grads_flow(rng):
    cfg = _cfg(cf=4.0)
    p = M.init_moe(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)).astype(np.float32))
    g = jax.grad(lambda p_: jnp.sum(M.apply_moe(p_, cfg, x) ** 2))(p)
    for name in ("router", "experts_in", "experts_out"):
        assert float(jnp.abs(g[name]).max()) > 0, name
        assert np.all(np.isfinite(np.asarray(g[name])))


def test_dispatch_indices_first_come():
    fid = jnp.asarray([1, 0, 1, 1, 2, 0], jnp.int32)
    f_sel, valid = M._dispatch_indices(fid, 3, 2)
    # expert 0 gets flats (1, 5); expert 1 gets (0, 2) — flat 3 dropped
    assert list(np.asarray(f_sel[0])) == [1, 5]
    assert list(np.asarray(f_sel[1])[:2]) == [0, 2]
    assert bool(valid[1, 1]) and not bool(valid[2, 1])


def test_dispatch_indices_sentinel_never_dispatched():
    fid = jnp.asarray([3, 3, 1, 3], jnp.int32)      # 3 = sentinel (n_bins=3)
    f_sel, valid = M._dispatch_indices(fid, 3, 4)
    assert int(valid.sum()) == 1
    assert int(f_sel[1, 0]) == 2


# ---------------------------------------------------------------------------
# Planned sparse dispatch over the expert einsum sites (ISSUE 4 tentpole)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["weight", "two_sided"])
def test_sparse_apply_moe_matches_gshard_oracle(rng, mode):
    """apply_moe under a weight/two_sided descriptor table must equal the
    dense gshard oracle — the expert contractions route through the CSB
    block-sparse path, which skips only true-zero blocks."""
    cfg = _cfg(cf=8.0)
    sp_cfg = _sparse_cfg(cfg, mode)
    p = _prune_experts(M.init_moe(cfg, jax.random.PRNGKey(0),
                                  dtype=jnp.float32))
    x = jnp.asarray(rng.normal(size=(2, 16, cfg.d_model)).astype(np.float32))
    y_oracle = M.apply_moe_gshard(p, cfg, x)
    y_dense = M.apply_moe(p, cfg, x)           # dense sort-based twin
    with ops.exec_config(decode_exec_config(sp_cfg, n_slots=32)):
        y_sparse = M.apply_moe(p, sp_cfg, x)
    # same dispatch algorithm, blocks skipped not approximated → bitwise
    # equal to the dense path; the one-hot oracle contracts differently, so
    # it agrees to float tolerance (and token-for-token in the engine tests)
    np.testing.assert_array_equal(np.asarray(y_sparse), np.asarray(y_dense))
    np.testing.assert_allclose(np.asarray(y_sparse), np.asarray(y_oracle),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("mode", ["weight", "two_sided"])
def test_planned_expert_matmul_bitwise_equals_trace(rng, mode):
    """Per-expert PlannedWeight metadata (leading E axis) through
    flex_expert_matmul must be bitwise-identical to the trace-time path and
    match the dense batched einsum."""
    e, c, k, n = 4, 8, 64, 48
    w = np.stack([S.prune_k_blocks(
        rng.normal(size=(k, n)).astype(np.float32), 16, 16, 2)
        for _ in range(e)])
    x = rng.normal(size=(e, c, k)).astype(np.float32)
    x = np.where(np.abs(x) > 0.6, x, 0.0)
    sp_cfg = _sparse_cfg(_cfg(), mode)
    ec = decode_exec_config(sp_cfg, n_slots=c)
    pw = S.plan_weight(w, site="moe.experts_in", mode=mode,
                       bm=16, bk=16, bn=16)
    assert pw.max_nnz < pw.tk          # structured pruning → strictly tight
    with ops.exec_config(ec):
        trace = ops.flex_expert_matmul(jnp.asarray(x), jnp.asarray(w),
                                       site="moe.experts_in")
        planned = ops.flex_expert_matmul(jnp.asarray(x), pw,
                                         site="moe.experts_in")
    np.testing.assert_array_equal(np.asarray(planned), np.asarray(trace))
    np.testing.assert_allclose(np.asarray(planned),
                               np.einsum("eck,ekn->ecn", x, w),
                               rtol=2e-5, atol=2e-4)


def test_planned_expert_matmul_pallas_interpret(rng):
    """The Pallas path unrolls the static expert axis (scalar-prefetch
    kernels have no vmap rule) — interpret mode must match dense."""
    e, c, k, n = 3, 8, 64, 32
    w = np.stack([S.prune_k_blocks(
        rng.normal(size=(k, n)).astype(np.float32), 16, 16, 2)
        for _ in range(e)])
    x = rng.normal(size=(e, c, k)).astype(np.float32)
    pw = S.plan_weight(w, site="moe.experts_in", mode="two_sided",
                       bm=8, bk=16, bn=16)
    with ops.exec_config(ops.ExecConfig(use_pallas=True, interpret=True)):
        out = ops.flex_expert_matmul(jnp.asarray(x), pw,
                                     site="moe.experts_in")
    np.testing.assert_allclose(np.asarray(out),
                               np.einsum("eck,ekn->ecn", x, w),
                               rtol=2e-5, atol=2e-4)


def test_dense_expert_matmul_pallas_uses_site_schedule(rng):
    """Dense expert sites don't bypass the dataflow dispatch on the Pallas
    path: each expert runs the schedule-flexible kernel (interpret mode
    here) and matches the batched einsum."""
    e, c, k, n = 3, 8, 64, 32
    w = rng.normal(size=(e, k, n)).astype(np.float32)
    x = rng.normal(size=(e, c, k)).astype(np.float32)
    ec = decode_exec_config(_cfg(), n_slots=c, use_pallas=True,
                            interpret=True)
    assert ec.schedules.sites["moe.experts_in"].sparsity_mode == "dense"
    with ops.exec_config(ec):
        out = ops.flex_expert_matmul(jnp.asarray(x), jnp.asarray(w),
                                     site="moe.experts_in")
    np.testing.assert_allclose(np.asarray(out),
                               np.einsum("eck,ekn->ecn", x, w),
                               rtol=2e-5, atol=2e-4)


def test_expert_and_head_sites_in_descriptor_table():
    """Dry-run cell artifacts record the expert einsum + head sites with
    per-expert plan economics (the ``sites``/``plan`` record in
    ``launch.dryrun.run_cell`` is built from exactly these two calls)."""
    sp_cfg = _sparse_cfg(get_config("deepseek-moe-16b"), "two_sided")
    ns = compile_network_schedule(sp_cfg, SHAPES["decode_32k"])
    assert {"moe.experts_in", "moe.experts_gate", "moe.experts_out",
            "moe.shared_in", "moe.shared_gate", "moe.shared_out",
            "lm_head"} <= set(ns.sites)
    est = site_plan_estimate(ns.sites["moe.experts_in"], sp_cfg)
    assert est["experts"] == sp_cfg.moe.n_experts
    assert est["dense_bytes"] == (est["per_expert_dense_bytes"]
                                  * sp_cfg.moe.n_experts)
    assert est["bytes_saved"] > 0
    # sharded meshes report *per-device* expert economics (EP over model)
    est_ep = site_plan_estimate(ns.sites["moe.experts_in"], sp_cfg,
                                model_shards=16)
    assert est_ep["experts"] == sp_cfg.moe.n_experts // 16
    assert est_ep["dense_bytes"] == est["dense_bytes"] // 16
    # non-expert sites carry no expert fields
    est_head = site_plan_estimate(ns.sites["lm_head"], sp_cfg)
    assert "experts" not in est_head


@pytest.mark.slow        # subprocess mesh — heavy
def test_ep_shard_map_matches_oracle():
    """EP all-to-all path on 8 forced host devices (2 data × 4 model)."""
    run_with_devices("""
import dataclasses, numpy as np, jax, jax.numpy as jnp
from repro.configs.base import get_smoke_config
from repro.models import moe as M
from repro.sharding.partition import make_rules, use_rules

cfg = get_smoke_config('deepseek-moe-16b')
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
p = M.init_moe(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model), jnp.float32)
y_oracle = M.apply_moe_gshard(p, cfg, x)
mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
rules = make_rules(mesh, kind='train', n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads)
with use_rules(rules):
    y_ep = jax.jit(lambda p, x: M.apply_moe(p, cfg, x))(p, x)
err = float(jnp.abs(y_ep - y_oracle).max())
assert err < 2e-4, err
# grads flow through the EP path
with use_rules(rules):
    g = jax.grad(lambda p_, x_: jnp.sum(M.apply_moe(p_, cfg, x_)**2))(p, x)
assert float(jnp.linalg.norm(g['router'])) > 0
print('EP OK', err)
""")
