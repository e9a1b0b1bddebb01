"""Partition rules, batch/state shardings, schedule descriptors."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, get_config
from repro.core.descriptors import compile_network_schedule, matmul_sites

from conftest import run_with_devices


def test_matmul_sites_cover_families():
    train = SHAPES["train_4k"]
    sites = dict((s[0], s[1:]) for s in matmul_sites(get_config("yi-9b"),
                                                     train))
    assert {"attn.q", "attn.kv", "attn.out", "mlp.in", "mlp.out",
            "lm_head"} <= set(sites)
    m, n, k = sites["attn.q"]
    assert m == train.global_batch * train.seq_len
    assert k == 4096

    moe_sites = dict((s[0], s[1:]) for s in
                     matmul_sites(get_config("deepseek-moe-16b"), train))
    assert {"moe.router", "moe.experts_in", "moe.experts_gate",
            "moe.experts_out", "moe.shared_in", "moe.shared_gate",
            "moe.shared_out", "lm_head"} <= set(moe_sites)
    # the leading dense layers use the ordinary MLP sites
    assert {"mlp.in", "mlp.gate", "mlp.out"} <= set(moe_sites)

    ssm_sites = dict((s[0], s[1:]) for s in
                     matmul_sites(get_config("mamba2-1.3b"), train))
    assert {"ssm.in_proj", "ssm.out_proj", "lm_head"} <= set(ssm_sites)

    rec_sites = dict((s[0], s[1:]) for s in
                     matmul_sites(get_config("recurrentgemma-9b"), train))
    assert {"rglru.in", "rglru.out"} <= set(rec_sites)


def test_decode_sites_use_token_m():
    dec = SHAPES["decode_32k"]
    sites = dict((s[0], s[1:]) for s in matmul_sites(get_config("yi-9b"),
                                                     dec))
    assert sites["attn.q"][0] == dec.global_batch       # 1 new token per seq


def test_compile_network_schedule_all_archs():
    from repro.configs.base import ARCH_IDS
    for arch in ARCH_IDS:
        ns = compile_network_schedule(get_config(arch), SHAPES["train_4k"],
                                      model_shards=16)
        assert ns.sites, arch
        for d in ns.sites.values():
            assert d.schedule.bm >= 1 and d.schedule.hbm_bytes > 0
            # K-sharded sites get the FlexTree contraction partition
            if d.site.endswith(".out") or d.site.endswith("out_proj"):
                assert d.reduce.ic_p == 16, d.site
        assert "NetworkSchedule" in ns.describe()


@pytest.mark.slow        # subprocess mesh — heavy
def test_partition_rules_on_mesh():
    """Param/batch/state shardings resolve and divide on an 8-dev mesh."""
    run_with_devices("""
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.configs.base import get_smoke_config
from repro.models import model as M
from repro.sharding.partition import (batch_shardings, make_rules,
                                      partition_params, tree_paths)

mesh = jax.make_mesh((2, 4), ('data', 'model'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_smoke_config('yi-9b')
rules = make_rules(mesh, kind='train', n_heads=cfg.n_heads,
                   n_kv_heads=cfg.n_kv_heads)
p_sds = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
sh = partition_params(p_sds, rules)
paths = tree_paths(sh)
# stacked attn weight: leading layer dim unsharded, d/model split
wq = paths['stack/layers/attn/wq']
assert wq.spec[0] is None, wq.spec
assert 'model' in str(wq.spec), wq.spec
# embedding: vocab over model
assert str(paths['embed'].spec[0]) == 'model'
# every spec divides its dim
for path, s in paths.items():
    leaf = tree_paths(p_sds)[path]
    for dim, ax in zip(leaf.shape, tuple(s.spec) + (None,) * 8):
        if ax is None: continue
        size = np.prod([mesh.shape[a] for a in ((ax,) if isinstance(ax, str) else ax)])
        assert dim % size == 0, (path, leaf.shape, s.spec)

# decode state shardings: cache_seq over model when seq_shard
specs = M.input_specs(cfg, __import__('repro.configs.base', fromlist=['SHAPES']).SHAPES['decode_32k'])
bs = batch_shardings(specs, mesh, seq_shard=True)
k_sh = tree_paths(bs)['state/layers/k']
assert str(k_sh.spec[2]) == 'model', k_sh.spec      # (L, B, C, KVH, hd)
assert str(k_sh.spec[1]) == 'data', k_sh.spec
print('partition rules OK')
""")


@pytest.mark.slow        # subprocess mesh — heavy
def test_train_step_on_mesh_runs():
    """A sharded train step executes end-to-end on an 8-device host mesh."""
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs.base import ShapeConfig, get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models import model as M
from repro.sharding.partition import make_rules
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import build_train_step

cfg = get_smoke_config('yi-9b')
shape = ShapeConfig(name='t', kind='train', seq_len=32, global_batch=8,
                    loss_chunk=16, attn_chunk=16, remat='none', n_micro=2)
mesh = make_host_mesh(model=4)
rules = make_rules(mesh, kind='train', n_heads=cfg.n_heads,
                   n_kv_heads=cfg.n_kv_heads)
step = build_train_step(cfg, shape, AdamWConfig(), mesh, rules, donate=False)
params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
st = init_opt_state(params)
rng = np.random.default_rng(0)
batch = {'tokens': jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32),
         'labels': jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32)}
p2, st2, m = step(params, st, batch)
assert np.isfinite(float(m['loss']))
# params actually changed
d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), params, p2)
assert max(jax.tree.leaves(d)) > 0
print('sharded train step OK, loss', float(m['loss']))
""")


@pytest.mark.slow        # subprocess mesh — heavy
def test_dp_compressed_step_runs():
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.configs.base import ShapeConfig, get_smoke_config
from repro.models import model as M
from repro.train.grad_compress import CompressConfig, init_error_state
from repro.train.optimizer import AdamWConfig, init_opt_state
from repro.train.train_step import build_dp_compressed_step

cfg = get_smoke_config('stablelm-1.6b')
shape = ShapeConfig(name='t', kind='train', seq_len=16, global_batch=8,
                    loss_chunk=16, attn_chunk=16, remat='none')
mesh = jax.make_mesh((8,), ('data',),
                     axis_types=(jax.sharding.AxisType.Auto,))
step = build_dp_compressed_step(cfg, shape, AdamWConfig(), mesh,
                                CompressConfig(mode='int8'))
params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
st = init_opt_state(params)
err = init_error_state(params)
rng = np.random.default_rng(0)
batch = {'tokens': jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
         'labels': jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32)}
p2, st2, err2, m = step(params, st, err, batch)
assert np.isfinite(float(m['loss']))
# error feedback is carrying quantization residuals
enorm = sum(float(jnp.abs(e).sum()) for e in jax.tree.leaves(err2))
assert enorm > 0
print('dp-compressed step OK')
""", n_devices=8)
