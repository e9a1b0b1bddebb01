"""``chip_smoke.py`` on the CPU: its phases at a tiny size (Pallas in
interpret mode) and its refusal to report without a TPU.  This checks the
script's own checks and bookkeeping; only the chip run says anything about
the chip."""
import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from conftest import REPO_ROOT, run_with_devices

SCRIPT = os.path.join(REPO_ROOT, "chip_smoke.py")
TINY = dict(n_slots=8, max_seq=64, prefill_chunk=16, max_new=8)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serving_phases_at_tiny_size(smoke):
    from repro.configs.base import get_smoke_config
    from repro.models import model as model_lib
    cfg = get_smoke_config(smoke.MODEL)
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   dtype=jnp.bfloat16)
    prompts = smoke.make_prompts(cfg.vocab, 0, lens=(8, 40))
    assert sorted(map(len, prompts)) == [8, 12, 17, 21, 26, 30, 35, 40]
    streams = smoke.phase_dense(cfg, params, prompts, **TINY)
    assert len(streams) == len(prompts)
    smoke.phase_flexnn(cfg, smoke.prune_params(params), prompts,
                       interpret=True, **TINY)


def test_mesh_phase_on_four_cpu_devices():
    out = run_with_devices(f"""
import importlib.util, jax
spec = importlib.util.spec_from_file_location('chip_smoke', {SCRIPT!r})
smoke = importlib.util.module_from_spec(spec)
spec.loader.exec_module(smoke)
from repro.configs.base import get_smoke_config
losses, ref = smoke.phase_mesh_train(get_smoke_config(smoke.MODEL),
                                     jax.devices(), seq=32)
print('steps', len(losses), len(ref))
""", n_devices=4)
    assert "steps 3 3" in out


def test_refuses_to_report_without_a_tpu(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    proc = subprocess.run([sys.executable, SCRIPT], env=env, cwd=tmp_path,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
