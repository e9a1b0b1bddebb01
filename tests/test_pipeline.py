"""Pipeline parallelism: pipelined result == sequential reference."""
import pytest

from repro.sharding.pipeline import bubble_fraction

from conftest import run_with_devices


def test_bubble_fraction():
    assert bubble_fraction(2, 4) == pytest.approx(1 / 5)
    assert bubble_fraction(4, 12) == pytest.approx(3 / 15)
    assert bubble_fraction(1, 8) == 0.0


@pytest.mark.slow        # subprocess mesh — heavy
def test_pipeline_matches_sequential():
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.sharding.pipeline import pipeline_apply, split_stages

mesh = jax.make_mesh((4, 2), ('pod', 'data'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
L, D, B = 8, 16, 12

def layer_fn(lp, h):
    return jnp.tanh(h @ lp['w'] + lp['b'])

k = jax.random.PRNGKey(0)
stacked = {'w': jax.random.normal(k, (L, D, D)) * 0.3,
           'b': jnp.zeros((L, D))}
x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

# sequential reference
ref = x
for i in range(L):
    ref = layer_fn(jax.tree.map(lambda p: p[i], stacked), ref)

stages = split_stages(stacked, 4)
out = jax.jit(lambda sp, x: pipeline_apply(
    layer_fn, sp, x, mesh=mesh, axis_name='pod', n_micro=3))(stages, x)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           rtol=2e-5, atol=2e-5)
print('pipeline == sequential OK')
""")


@pytest.mark.slow        # subprocess mesh — heavy
def test_pipeline_single_stage_degenerates():
    run_with_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.sharding.pipeline import pipeline_apply, split_stages

mesh = jax.make_mesh((1, 8), ('pod', 'data'),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
L, D, B = 4, 8, 8
def layer_fn(lp, h):
    return h + lp['w']
stacked = {'w': jnp.arange(L, dtype=jnp.float32)[:, None].repeat(D, 1)}
x = jnp.zeros((B, D))
out = jax.jit(lambda sp, x: pipeline_apply(
    layer_fn, sp, x, mesh=mesh, axis_name='pod', n_micro=2))(
        split_stages(stacked, 1), x)
np.testing.assert_allclose(np.asarray(out), float(sum(range(L))))
print('single-stage OK')
""")
