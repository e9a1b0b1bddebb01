"""What the entry points do to their process: the persistent compilation
cache (``launch.compile_cache``) goes to the directory
``JAX_COMPILATION_CACHE_DIR`` names, else to ``<repo>/.jax_cache``; the
compile-only tools change no environment when imported.  The cache cases
run in their own CPU process, so the test process's cache settings stay
untouched."""
import importlib
import os

import pytest

from conftest import REPO_ROOT, run_with_devices

CODE = """
import jax, jax.numpy as jnp
from repro.launch.compile_cache import setup_compile_cache
print(setup_compile_cache())
print(jax.config.jax_compilation_cache_dir)
if {compile}:
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def test_cache_goes_where_the_variable_says(tmp_path):
    where = str(tmp_path / "cache")
    out = run_with_devices(CODE.format(compile=True), n_devices=1,
                           extra_env={"JAX_COMPILATION_CACHE_DIR": where})
    assert out.split() == [where, where]
    assert os.listdir(where)            # the compile was written there


def test_cache_defaults_to_the_repository():
    # no compile: only the placement is checked, nothing is written
    out = run_with_devices(CODE.format(compile=False), n_devices=1)
    default = os.path.join(REPO_ROOT, ".jax_cache")
    assert out.split() == [default, default]


@pytest.mark.parametrize("module", ["repro.launch.dryrun",
                                    "repro.roofline.hillclimb"])
def test_compile_only_tools_leave_the_environment_alone(module):
    import jax                          # noqa: F401 — jax's own set-up
    before = dict(os.environ)
    importlib.import_module(module)
    assert dict(os.environ) == before
