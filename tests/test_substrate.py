"""Data pipeline, checkpointing, serving engine, FlexTree, HLO parser."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import ckpt as C
from repro.core import flextree as FT
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.roofline.hlo import f32_upcast_bytes, parse_collectives

from conftest import run_with_devices


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def test_pipeline_deterministic_and_resumable():
    cfg = DataConfig(vocab=1000, seq_len=16, global_batch=4, seed=3)
    p1 = TokenPipeline(cfg)
    batches = [p1.next_batch() for _ in range(5)]
    snap = p1.snapshot()
    after = p1.next_batch()

    p2 = TokenPipeline(cfg)
    p2.restore(snap)
    replay = p2.next_batch()
    np.testing.assert_array_equal(replay["tokens"], after["tokens"])

    # restart from scratch replays identically
    p3 = TokenPipeline(cfg)
    np.testing.assert_array_equal(p3.next_batch()["tokens"],
                                  batches[0]["tokens"])


def test_pipeline_shards_disjoint():
    base = dict(vocab=1000, seq_len=16, global_batch=8, seed=3)
    s0 = TokenPipeline(DataConfig(**base, shard=0, n_shards=2)).next_batch()
    s1 = TokenPipeline(DataConfig(**base, shard=1, n_shards=2)).next_batch()
    assert s0["tokens"].shape == (4, 16)
    assert not np.array_equal(s0["tokens"], s1["tokens"])


def test_pipeline_labels_shifted():
    cfg = DataConfig(vocab=1000, seq_len=16, global_batch=2, seed=0)
    b = TokenPipeline(cfg).next_batch()
    assert b["tokens"].shape == b["labels"].shape == (2, 16)


def test_pipeline_file_source(tmp_path):
    toks = np.arange(10_000, dtype=np.uint16)
    path = tmp_path / "tokens.bin"
    toks.tofile(path)
    cfg = DataConfig(vocab=500, seq_len=16, global_batch=2, source="file",
                     path=str(path))
    b = TokenPipeline(cfg).next_batch()
    assert b["tokens"].max() < 500
    np.testing.assert_array_equal(b["labels"][:, :-1], b["tokens"][:, 1:])


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def test_ckpt_roundtrip_and_keep_k(tmp_path):
    d = str(tmp_path)
    state = {"w": jnp.arange(6.0).reshape(2, 3), "n": jnp.asarray(3)}
    for step in (1, 2, 3, 4):
        C.save(d, step, state, extra={"step": step}, keep=2)
    assert C.all_steps(d) == [3, 4]
    restored, extra = C.restore(d, state)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(state["w"]))
    assert extra["step"] == 4


def test_ckpt_atomicity_partial_write_invisible(tmp_path):
    d = str(tmp_path)
    C.save(d, 1, {"w": jnp.ones(3)}, keep=3)
    # a crashed writer leaves only a .tmp dir — must be ignored
    os.makedirs(os.path.join(d, "step_000000002.tmp/arrays"))
    assert C.latest_step(d) == 1


def test_ckpt_zvc_compression(tmp_path):
    """ZVC-at-rest (Fig 12): sparse leaves roundtrip exactly and shrink;
    dense leaves bypass compression (raw mode)."""
    from repro.core.sparsity import prune_magnitude
    d = str(tmp_path)
    rng = np.random.default_rng(0)
    sparse_w = prune_magnitude(rng.normal(size=(64, 64)).astype(np.float32),
                               0.7)
    state = {"w": jnp.asarray(sparse_w),
             "dense": jnp.asarray(rng.normal(size=(128,)).astype(np.float32))}
    C.save(d, 1, state, zvc=True)
    restored, _ = C.restore(d, state)
    np.testing.assert_array_equal(np.asarray(restored["w"]), sparse_w)
    np.testing.assert_array_equal(np.asarray(restored["dense"]),
                                  np.asarray(state["dense"]))
    import glob
    arrays = glob.glob(os.path.join(d, "step_000000001/arrays/*"))
    zvcs = [f for f in arrays if f.endswith(".zvc.npz")]
    assert len(zvcs) == 1                     # only the sparse leaf
    assert os.path.getsize(zvcs[0]) < 64 * 64 * 4 * 0.5


def test_ckpt_restore_casts_dtype(tmp_path):
    d = str(tmp_path)
    C.save(d, 1, {"w": jnp.ones(4, jnp.float32)})
    like = {"w": jax.ShapeDtypeStruct((4,), jnp.bfloat16)}
    restored, _ = C.restore(d, like)
    assert restored["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# Serving engine
# ---------------------------------------------------------------------------

@pytest.mark.slow        # token-by-token engine drain — heavy
def test_serve_engine_drains_and_matches_decode():
    from repro.configs.base import get_smoke_config
    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    cfg = get_smoke_config("gemma-2b")
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    eng = ServeEngine(cfg, params, n_slots=2, max_seq=48)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=6) for _ in range(4)]
    uids = [eng.submit(p, max_new=4) for p in prompts]
    results = eng.run_until_drained()
    assert set(results) == set(uids)
    assert all(len(v) == 4 for v in results.values())

    # single-request greedy reference
    state = M.init_decode_state(cfg, 1, 48, dtype=jnp.float32)
    toks = list(prompts[0])
    out = []
    for t in range(len(toks) - 1):
        _, state = M.decode_step(params, cfg,
                                 jnp.asarray([[toks[t]]], jnp.int32), state,
                                 jnp.asarray(t, jnp.int32))
    cur = toks[-1]
    for t in range(len(toks) - 1, len(toks) + 3):
        lg, state = M.decode_step(params, cfg,
                                  jnp.asarray([[cur]], jnp.int32), state,
                                  jnp.asarray(t, jnp.int32))
        cur = int(jnp.argmax(lg[0, 0]))
        out.append(cur)
    assert out == results[uids[0]]


# ---------------------------------------------------------------------------
# FlexTree
# ---------------------------------------------------------------------------

def test_flextree_tap_points_match_paper():
    """§III-B: tap points [8, 8, 4, 2, 1] for IC_P = [1, 2, 4, 8, 16]."""
    assert [FT._tap_points(p) for p in (1, 2, 4, 8, 16)] == [8, 8, 4, 2, 1]


@pytest.mark.parametrize("ic_p", [2, 3, 4, 8, 16])
def test_flextree_speedups(ic_p):
    n = 64
    assert FT.flextree_speedup_vs_chain(n, ic_p) >= 1.0
    assert FT.flextree_speedup_vs_fixed(n, ic_p) >= 1.0
    # §III-B headline: up to ~2.14× vs neighbor chain at moderate IC_P
    if ic_p == 2:
        assert FT.flextree_speedup_vs_chain(n, ic_p) >= 1.8


def test_flextree_nonpow2_zero_padding():
    """Non-powers-of-2 IC_P round up to the next tree level (§III-B)."""
    assert FT.flextree_cycles(64, 3) == FT.flextree_cycles(64, 4)


def test_link_bytes_and_best_strategy():
    assert FT.link_bytes("allreduce", 100.0, 4) == pytest.approx(150.0)
    assert FT.link_bytes("scatter", 100.0, 4) == pytest.approx(75.0)
    assert FT.link_bytes("tree", 100.0, 4) == pytest.approx(200.0)
    assert FT.best_strategy(100.0, 4, consumer_sharded=True) == "scatter"
    assert FT.best_strategy(100.0, 4, consumer_sharded=False) == "allreduce"
    assert FT.link_bytes("allreduce", 100.0, 1) == 0.0


@pytest.mark.slow        # subprocess mesh — heavy
def test_reduce_psum_strategies_agree():
    """allreduce / tree / scatter produce the correct sum on 8 devices."""
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core.flextree import ReduceConfig, reduce_psum

mesh = jax.make_mesh((8,), ('model',),
                     axis_types=(jax.sharding.AxisType.Auto,))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 16), jnp.float32)
expect = x.sum(0)
for strat in ('allreduce', 'tree'):
    cfg = ReduceConfig(axis_name='model', ic_p=8, strategy=strat)
    f = shard_map(lambda v: reduce_psum(v[0], cfg)[None], mesh=mesh,
                  in_specs=P('model'), out_specs=P('model'), check_vma=False)
    out = jax.jit(f)(x)
    for i in range(8):
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(expect),
                                   rtol=1e-5)
# scatter: each device ends with its tile of the sum
cfg = ReduceConfig(axis_name='model', ic_p=8, strategy='scatter')
f = shard_map(lambda v: reduce_psum(v[0], cfg, scatter_dim=0)[None],
              mesh=mesh, in_specs=P('model'), out_specs=P('model'),
              check_vma=False)
out = jax.jit(f)(x).reshape(-1)
np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=1e-5)
print('reduce strategies OK')
""")


# ---------------------------------------------------------------------------
# HLO parser
# ---------------------------------------------------------------------------

SAMPLE_HLO = """
  %ar = f32[1024,256]{1,0} all-reduce(f32[1024,256] %x), replica_groups=[16,16]<=[256], to_apply=%add
  %ag = bf16[512,128]{1,0} all-gather(bf16[32,128] %y), replica_groups=[16,16]<=[256], dimensions={0}
  %rs = f32[64,128]{1,0} reduce-scatter(f32[1024,128] %z), replica_groups={{0,1,2,3}}, dimensions={0}
  %cp = bf16[256]{0} collective-permute(bf16[256] %w), source_target_pairs={{0,1}}
  %dead = f32[8]{0} add(f32[8] %a, f32[8] %b)
"""


def test_parse_collectives_kinds_and_bytes():
    s = parse_collectives(SAMPLE_HLO, 256)
    kinds = s.by_kind()
    assert kinds["all-reduce"]["count"] == 1
    ar_bytes = 1024 * 256 * 4
    assert kinds["all-reduce"]["operand_bytes"] == ar_bytes
    assert kinds["all-reduce"]["wire_bytes"] == pytest.approx(
        2 * ar_bytes * 15 / 16)
    ag_res = 512 * 128 * 2
    assert kinds["all-gather"]["operand_bytes"] == pytest.approx(ag_res / 16)
    assert kinds["reduce-scatter"]["count"] == 1
    # group size from explicit list {{0,1,2,3}}
    rs = [o for o in s.ops if o.kind == "reduce-scatter"][0]
    assert rs.group_size == 4
    assert kinds["collective-permute"]["wire_bytes"] == 256 * 2


def test_f32_upcast_detection():
    hlo = """
  %p = bf16[8,4096,4096]{2,1,0} parameter(0)
  %cv = f32[8,4096,4096]{2,1,0} convert(%p)
  %acc = f32[512,512]{1,0} add(%a, %b)
"""
    up = f32_upcast_bytes(hlo, min_bytes=1024)
    assert up == 8 * 4096 * 4096 * 4        # the convert twin only
