"""Ahead-of-time compiles of the Pallas kernels for one TPU v5e chip.

Each kernel is lowered and compiled by the TPU compiler from shapes alone,
at StableLM-2-1.6B widths (d_model 2048, d_ff 5632, vocab 100352, head_dim
64) with M = 8 decode rows and the block shapes the schedule selector picks
for those sites.  The chip is described, not attached: nothing runs, so
this says nothing about results or times.  It catches what interpret mode
cannot — Mosaic layout and tiling refusals.

The topology is described inside a module-scoped fixture, never at import,
so collecting this file loads no TPU library; where no topology can be
described, the tests skip from that fixture.
"""
import dataclasses

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
