"""Ahead-of-time compiles for one TPU v5e chip: the Pallas kernels and the
serving loop's decode-state commit.

Each kernel is lowered and compiled by the TPU compiler from shapes alone,
at StableLM-2-1.6B widths (d_model 2048, d_ff 5632, vocab 100352, head_dim
64) with M = 8 decode rows and the block shapes the schedule selector picks
for those sites.  The chip is described, not attached: nothing runs, so
this says nothing about results or times.  It catches what interpret mode
cannot — Mosaic layout and tiling refusals.  The fused decode block and
the prefill segment are compiled at the model's full size, and their
programs are read for what they do to the stacked KV cache: update it in
place, with no copy, relayout or whole-state select.

The topology is described inside a module-scoped fixture, never at import,
so collecting this file loads no TPU library; where no topology can be
described, the tests skip from that fixture.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import SparsityConfig, get_config
from repro.core.sparsity import BlockSparseMeta
from repro.kernels import block_sparse as bs
from repro.kernels import flex_matmul as fm
from repro.kernels.flash_attention import flash_attention
from repro.kernels.int8_matmul import int8_matmul
from repro.quant.quantize import QuantizedLinear

M = 8                                   # decode rows (engine n_slots)
D_MODEL, D_FF, VOCAB, N_HEADS, HEAD_DIM = 2048, 5632, 100352, 32, 64
# (site, K, N) — the distinct matmul widths of one StableLM-2-1.6B layer
# plus the lm_head
SITES = [("mlp.in", D_MODEL, D_FF), ("mlp.out", D_FF, D_MODEL),
         ("lm_head", D_MODEL, VOCAB)]


@pytest.fixture(scope="module")
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def schedules():
    """The decode-shape descriptor table the serving engine would use."""
    from repro.serve.engine import decode_exec_config
    cfg = dataclasses.replace(get_config("stablelm-1.6b"),
                              sparsity=SparsityConfig(weight_sparsity=0.5))
    return decode_exec_config(cfg, M).schedules.sites


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_for_chip(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _block_sparse_args(sched, k, n, dtype, chip):
    tk, tn = k // sched.bk, n // sched.bn
    assert tk * sched.bk == k and tn * sched.bn == n
    return tk, tn, (_shape((M, k), jnp.bfloat16, chip),
                    _shape((k, n), dtype, chip),
                    _shape((1, tn, tk), jnp.int32, chip),
                    _shape((1, tn), jnp.int32, chip))


def _meta(kidx, kcnt, tk, tn):
    return BlockSparseMeta(kidx=kidx, kcnt=kcnt,
                           a_bitmap=jnp.ones((1, tk), bool),
                           b_bitmap=jnp.ones((tk, tn), bool), max_nnz=tk)


@pytest.mark.parametrize("site,k,n", SITES)
def test_block_sparse_compiles(one_chip, schedules, site, k, n):
    sched = schedules[site].schedule
    tk, tn, args = _block_sparse_args(sched, k, n, jnp.bfloat16, one_chip)

    def run(a, b, kidx, kcnt):
        return bs.block_sparse_matmul(a, b, _meta(kidx, kcnt, tk, tn),
                                      out_dtype=jnp.float32)

    _compile_for_chip(run, *args)


@pytest.mark.parametrize("site,k,n", SITES)
def test_block_sparse_int8_scaled_compiles(one_chip, schedules, site, k, n):
    sched = schedules[site].schedule
    tk, tn, args = _block_sparse_args(sched, k, n, jnp.int8, one_chip)

    def run(a, b, kidx, kcnt, scale):
        return bs.block_sparse_matmul(a, b, _meta(kidx, kcnt, tk, tn),
                                      out_dtype=jnp.float32, scale=scale)

    _compile_for_chip(run, *args, _shape((n,), jnp.float32, one_chip))


@pytest.mark.parametrize("site,k,n", SITES)
def test_int8_matmul_compiles(one_chip, site, k, n):
    def run(a, q, scale):
        return int8_matmul(a, QuantizedLinear(q, scale),
                           out_dtype=jnp.float32)

    _compile_for_chip(run, _shape((M, k), jnp.bfloat16, one_chip),
                      _shape((k, n), jnp.int8, one_chip),
                      _shape((n,), jnp.float32, one_chip))


@pytest.mark.parametrize("stationarity", ["output", "weight", "input"])
def test_flex_matmul_compiles(one_chip, schedules, stationarity):
    sched = dataclasses.replace(schedules["mlp.in"].schedule,
                                stationarity=stationarity)

    def run(a, b):
        return fm.flex_matmul(a, b, schedule=sched)

    _compile_for_chip(run, _shape((M, D_MODEL), jnp.bfloat16, one_chip),
                      _shape((D_MODEL, D_FF), jnp.bfloat16, one_chip))


def test_flash_attention_compiles(one_chip):
    qkv = _shape((N_HEADS, 2048, HEAD_DIM), jnp.bfloat16, one_chip)
    _compile_for_chip(lambda q, k, v: flash_attention(q, k, v, causal=True),
                      qkv, qkv, qkv)


def _state_producers(hlo: str, shape: str):
    """(op, fused root op) of every instruction of the compiled ``hlo``
    whose result has the array type ``shape`` (e.g. ``bf16[2,8]``)."""
    roots, comp = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            comp = head.group(1)
        root = re.match(r"\s*ROOT %\S+ = \S+ ([\w\-]+)\(", line)
        if root:
            roots[comp] = root.group(1)
    out = []
    for line in hlo.splitlines():
        m = re.search(r"= " + re.escape(shape) + r"\{[^}]*\} ([\w\-]+)\(",
                      line)
        if m:
            calls = re.search(r"calls=%([\w.\-]+)", line)
            out.append((m.group(1), calls and roots.get(calls.group(1))))
    return out


def test_decode_state_commits_in_place(one_chip):
    """At full size, ``decode_many`` (a 16-step block) and
    ``prefill_into_slot`` (a 128-token segment) with the state donated
    write the stacked K/V cache only through in-place scatters and
    ``dynamic-update-slice``s: no copy, relayout or select of it, and the
    state output aliases the state input."""
    from repro.models import model as model_lib
    cfg = get_config("stablelm-1.6b")
    n_slots, max_seq = M, 1024

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _shape(a.shape, a.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: model_lib.init_params(
        cfg, jax.random.PRNGKey(0))))
    state = on_chip(jax.eval_shape(lambda: model_lib.init_decode_state(
        cfg, n_slots, max_seq)))
    k = state["layers"]["k"]
    shape = "bf16[{}]".format(",".join(map(str, k.shape)))
    rows = _shape((n_slots,), jnp.int32, one_chip)
    flags = _shape((n_slots,), jnp.bool_, one_chip)
    scalar = _shape((), jnp.int32, one_chip)
    decode = jax.jit(
        lambda p, s, t, pos, live, rem: model_lib.decode_many(
            p, cfg, t, s, pos, live, 16, rem=rem),
        donate_argnums=(1,)).lower(params, state, rows, rows, flags, rows)
    prefill = jax.jit(
        lambda p, s, t, v, slot, sp, start, reset: model_lib.prefill_into_slot(
            p, cfg, t, v, slot, s, sp, start, reset),
        donate_argnums=(1,)).lower(
            params, state, _shape((128,), jnp.int32, one_chip),
            _shape((128,), jnp.bool_, one_chip), scalar, rows, scalar,
            _shape((), jnp.bool_, one_chip))
    writes = {("scatter", None), ("fusion", "scatter"),
              ("dynamic-update-slice", None),
              ("fusion", "dynamic-update-slice")}
    passes = {("parameter", None), ("get-tuple-element", None),
              ("bitcast", None)}
    for name, lowered in (("decode_many", decode), ("prefill", prefill)):
        hlo = lowered.compile().as_text()
        producers = set(_state_producers(hlo, shape))
        assert producers & writes, name
        assert producers <= writes | passes, (name, producers - writes - passes)
        header = hlo.splitlines()[0]        # HloModule ..., input_output_alias
        assert header.count("may-alias") >= 2, name     # k and v donated


def _top_level_labels(hlo: str):
    """``trace.op_label`` of every instruction outside fused computations
    (those a ``fusion`` instruction calls, nested ones included): the
    operations a device trace shows as events."""
    import importlib.util
    import sys
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "benchmarks/chip/trace.py"
    spec = importlib.util.spec_from_file_location("bench_trace", path)
    trace = sys.modules[spec.name] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(trace)
    called = {m.group(1) for line in hlo.splitlines() if " fusion(" in line
              for m in [re.search(r"calls=%([\w.\-]+)", line)] if m}
    labels, fused = set(), False
    for line in hlo.splitlines():
        if line and not line[0].isspace():
            name = line.split("(")[0].split()[-1].lstrip("%") \
                if "(" in line else ""
            fused = "fused" in line.split("(")[0] or name in called
        elif not fused and " = " in line:
            label = trace.op_label(line.strip().removeprefix("ROOT "))
            if label is not None:
                labels.add(label)
    return labels


def test_moe_share_decode_block_fits_one_chip(one_chip):
    """The DeepSeek-MoE-16B chip share of the benchmark (28 layers, 8 of
    64 routed experts held, 8 slots of 1024 positions, bf16) compiles as a
    16-step decode block and a 128-token prefill segment for one v5e; the
    arguments and temporaries of each fit its 16 GiB, and each holds the
    expert operations the ``expert_ms_per_step`` rule picks by shape: the
    up/gate projection and the down projection fused with the gated sum,
    one each.  The decode block's rows are the engine's 8 slots; the
    prefill segment runs as one forward pass, so its rows are its 128
    tokens.  The ``expert_ms_per_pass`` rule, which takes every row count
    the engine runs the experts at, picks the same two in each."""
    import importlib.util
    import json
    from pathlib import Path
    from repro.configs.base import ArchConfig
    from repro.models import model as model_lib
    bench = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
    spec = json.loads(
        (bench / "configs" / "deepseek-moe-16b-ep8.json").read_text())
    cfg = ArchConfig(**spec["model"])
    n_slots, max_seq = spec["engine"]["n_slots"], spec["engine"]["max_seq"]
    assert (cfg.n_layers, cfg.moe.experts_held, cfg.moe.n_experts) == \
        (28, 8, 64)
    rspec = importlib.util.spec_from_file_location(
        "bench_expert_ms", bench / "metrics" / "expert_ms_per_step.py")
    reader = importlib.util.module_from_spec(rspec)
    rspec.loader.exec_module(reader)
    pspec = importlib.util.spec_from_file_location(
        "bench_expert_pass", bench / "metrics" / "expert_ms_per_pass.py")
    per_pass = importlib.util.module_from_spec(pspec)
    pspec.loader.exec_module(per_pass)

    def on_chip(tree):
        return jax.tree.map(
            lambda a: _shape(a.shape, a.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(lambda: model_lib.init_params(
        cfg, jax.random.PRNGKey(0))))
    state = on_chip(jax.eval_shape(lambda: model_lib.init_decode_state(
        cfg, n_slots, max_seq)))
    rows = _shape((n_slots,), jnp.int32, one_chip)
    flags = _shape((n_slots,), jnp.bool_, one_chip)
    scalar = _shape((), jnp.int32, one_chip)
    decode = jax.jit(
        lambda p, s, t, pos, live, rem: model_lib.decode_many(
            p, cfg, t, s, pos, live, 16, rem=rem, moe_counts=True),
        donate_argnums=(1,)).lower(params, state, rows, rows, flags, rows)
    prefill = jax.jit(
        lambda p, s, t, v, slot, sp, start, reset: model_lib.prefill_into_slot(
            p, cfg, t, v, slot, s, sp, start, reset, moe_counts=True),
        donate_argnums=(1,)).lower(
            params, state, _shape((128,), jnp.int32, one_chip),
            _shape((128,), jnp.bool_, one_chip), scalar, rows, scalar,
            _shape((), jnp.bool_, one_chip))
    for name, lowered, rows_in in (("decode_many", decode, n_slots),
                                   ("prefill", prefill, 128)):
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        total = mem.argument_size_in_bytes + mem.temp_size_in_bytes
        assert total < 16 * 2 ** 30, (name, total)
        labels = _top_level_labels(compiled.as_text())
        picked = reader.expert_labels(dict.fromkeys(labels, 0.0),
                                      spec["model"],
                                      {**spec["engine"], "n_slots": rows_in})
        assert sorted(picked) == [f"fusion bf16[8,{rows_in},1408]",
                                  f"fusion f32[{rows_in},2048]"], \
            (name, picked)
        assert sorted(per_pass.expert_labels(
            dict.fromkeys(labels, 0.0), spec["model"], spec["engine"])) == \
            sorted(picked), name
