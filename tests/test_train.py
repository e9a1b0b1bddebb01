"""Trainer / optimizer / grad-compression / fault-tolerance tests."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ShapeConfig, get_smoke_config
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.train.optimizer import (AdamWConfig, adamw_update, cosine_lr,
                                   clip_by_global_norm, init_opt_state)
from repro.train.train_step import build_train_step, make_step_fn
from repro.train.trainer import Trainer, TrainerConfig, Watchdog, WatchdogConfig

from conftest import run_with_devices

SHAPE = ShapeConfig(name="t", kind="train", seq_len=32, global_batch=4,
                    loss_chunk=16, attn_chunk=16, remat="none")


def _setup(arch="stablelm-1.6b", **shape_kw):
    import dataclasses
    cfg = get_smoke_config(arch)
    shape = dataclasses.replace(SHAPE, **shape_kw)
    opt = AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=50)
    return cfg, shape, opt


def _batch(cfg, seed=0, b=4, s=32):
    rng = np.random.default_rng(seed)
    return {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)),
                                  jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)),
                                  jnp.int32)}


# ---------------------------------------------------------------------------
# Optimizer
# ---------------------------------------------------------------------------

def test_cosine_lr_schedule():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110,
                      min_lr_frac=0.1)
    assert float(cosine_lr(cfg, jnp.asarray(5))) == pytest.approx(0.5)
    assert float(cosine_lr(cfg, jnp.asarray(10))) == pytest.approx(1.0)
    assert float(cosine_lr(cfg, jnp.asarray(110))) == pytest.approx(0.1)


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 3.0), "b": jnp.full((4,), 4.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(10.0)
    from repro.train.optimizer import global_norm
    assert float(global_norm(clipped)) == pytest.approx(1.0, rel=1e-5)


def test_adamw_decays_matrices_only():
    cfg = AdamWConfig(lr=1e-2, weight_decay=1.0, clip_norm=1e9)
    params = {"w": jnp.ones((4, 4)), "scale": jnp.ones((4,))}
    grads = {"w": jnp.zeros((4, 4)), "scale": jnp.zeros((4,))}
    st = init_opt_state(params)
    new, _, _ = adamw_update(cfg, params, grads, st)
    assert float(new["w"][0, 0]) < 1.0          # decayed
    assert float(new["scale"][0]) == 1.0        # not decayed


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def test_loss_decreases_fixed_batch():
    cfg, shape, opt = _setup()
    step = build_train_step(cfg, shape, opt, donate=False)
    params = _init_params(cfg)
    st = init_opt_state(params)
    batch = _batch(cfg)
    losses = []
    for _ in range(8):
        params, st, m = step(params, st, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2


def _init_params(cfg):
    from repro.models import model as M
    return M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


def test_microbatch_equivalence():
    """n_micro=2 must produce (nearly) the same update as n_micro=1."""
    cfg, shape1, opt = _setup()
    import dataclasses
    shape2 = dataclasses.replace(shape1, n_micro=2)
    params = _init_params(cfg)
    st = init_opt_state(params)
    batch = _batch(cfg)
    p1, _, m1 = make_step_fn(cfg, shape1, opt)(params, st, batch)
    p2, _, m2 = make_step_fn(cfg, shape2, opt)(params, st, batch)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-4)
    diffs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), p1, p2)
    assert max(jax.tree.leaves(diffs)) < 5e-3


# ---------------------------------------------------------------------------
# Gradient compression
# ---------------------------------------------------------------------------

def test_int8_quantize_roundtrip(rng):
    from repro.train.grad_compress import dequantize_int8, quantize_int8
    x = jnp.asarray(rng.normal(size=(256,)).astype(np.float32))
    q, s = quantize_int8(x)
    err = float(jnp.abs(dequantize_int8(q, s) - x).max())
    assert err <= float(s) * 0.51 + 1e-6


@pytest.mark.slow        # subprocess mesh — heavy
def test_compressed_mean_shard_map():
    """EF-int8 and ZVC-top-k means vs exact mean on 8 devices; error
    feedback carries the residual."""
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.train.grad_compress import CompressConfig, compressed_mean

mesh = jax.make_mesh((8,), ('data',),
                     axis_types=(jax.sharding.AxisType.Auto,))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 64), jnp.float32)
exact = g.mean(0)

for mode, tol in (('int8', 0.05), ('zvc_topk', 1.0), ('none', 1e-6)):
    cfg = CompressConfig(mode=mode, topk_frac=0.25, axis_name='data')
    def f(g):
        r, e = compressed_mean(g[0], jnp.zeros_like(g[0]), cfg)
        return r[None], e[None]
    red, err = jax.jit(shard_map(f, mesh=mesh, in_specs=P('data'),
                                 out_specs=(P('data'), P('data')),
                                 check_vma=False))(g)
    d = float(jnp.abs(red[0] - exact).max())
    assert d < tol, (mode, d)
    if mode == 'int8':
        # error feedback: residual equals what quantization dropped
        assert float(jnp.abs(err).max()) > 0
print('compressed means OK')
""")


def test_wire_bytes_model():
    from repro.train.grad_compress import CompressConfig, wire_bytes_per_element
    assert wire_bytes_per_element(CompressConfig(mode="int8")) == 1.0
    assert wire_bytes_per_element(
        CompressConfig(mode="zvc_topk", topk_frac=0.05)) == pytest.approx(
            0.05 * 4 + 0.125)
    assert wire_bytes_per_element(CompressConfig(mode="none")) == 4.0


# ---------------------------------------------------------------------------
# Trainer: checkpoint/restart + watchdog
# ---------------------------------------------------------------------------

@pytest.mark.slow        # subprocess mesh — heavy
def test_trainer_checkpoint_resume(tmp_path):
    cfg, shape, opt = _setup()
    pipe_cfg = DataConfig(vocab=cfg.vocab, seq_len=shape.seq_len,
                          global_batch=shape.global_batch, seed=7)
    tc = TrainerConfig(steps=6, ckpt_dir=str(tmp_path), ckpt_every=3,
                       log_every=100)

    t1 = Trainer(cfg, shape, opt, tc, pipeline=TokenPipeline(pipe_cfg))
    log1 = t1.run()
    assert len(log1) == 6

    # crash-restart: a fresh trainer resumes from step 6 checkpoint
    tc2 = TrainerConfig(steps=9, ckpt_dir=str(tmp_path), ckpt_every=3,
                        log_every=100)
    t2 = Trainer(cfg, shape, opt, tc2, pipeline=TokenPipeline(pipe_cfg))
    log2 = t2.run()
    assert [r["step"] for r in log2] == [7, 8, 9]

    # continuous run over the same data is step-identical
    tc3 = TrainerConfig(steps=9, ckpt_dir=None)
    t3 = Trainer(cfg, shape, opt, tc3, pipeline=TokenPipeline(pipe_cfg))
    log3 = t3.run()
    assert float(log3[-1]["loss"]) == pytest.approx(float(log2[-1]["loss"]),
                                                    rel=1e-4)


def test_watchdog_detects_straggler():
    wd = Watchdog(WatchdogConfig(factor=3.0, min_history=3))
    for i in range(5):
        assert not wd.observe(i, 1.0)
    assert wd.observe(5, 10.0)            # 10× median breaches 3× deadline
    assert wd.events and wd.events[0]["step"] == 5
    assert not wd.observe(6, 1.1)         # normal step after


def test_watchdog_warmup_no_false_positives():
    wd = Watchdog(WatchdogConfig(factor=2.0, min_history=5))
    assert not wd.observe(0, 100.0)       # no deadline yet
    assert wd.deadline() is None
