"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests run on 1 CPU device;
multi-device behaviour is exercised in subprocesses (see helpers below),
which are pinned to the CPU backend so that no test child reaches for an
accelerator.

A per-test timeout shim that needs no ``pytest-timeout`` plugin: a SIGALRM
itimer around each test call phase turns a hung async drain into a failing test instead of a wedged lane.
Default 600 s, overridable per test with ``@pytest.mark.timeout(N)`` or
globally via ``PYTEST_PER_TEST_TIMEOUT`` (0 disables).  POSIX main-thread
only — elsewhere it degrades to a no-op, never a false failure.
"""
import os
import signal
import subprocess
import sys
import threading

import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy model/system tests excluded from the fast "
        "CI lane (run with -m slow or no marker filter)")
    config.addinivalue_line(
        "markers", "timeout(seconds): per-test wall-clock limit enforced by "
        "the conftest SIGALRM shim (default from PYTEST_PER_TEST_TIMEOUT, "
        "600 s)")


_DEFAULT_TIMEOUT = float(os.environ.get("PYTEST_PER_TEST_TIMEOUT", "600"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    marker = item.get_closest_marker("timeout")
    seconds = float(marker.args[0]) if marker and marker.args \
        else _DEFAULT_TIMEOUT
    can_alarm = (seconds > 0 and hasattr(signal, "SIGALRM")
                 and threading.current_thread() is threading.main_thread())
    if not can_alarm:
        yield
        return

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"{item.nodeid} exceeded the per-test timeout of {seconds:g}s "
            f"(conftest SIGALRM shim; raise with @pytest.mark.timeout)")

    prev = signal.signal(signal.SIGALRM, _on_alarm)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, prev)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 600,
                     extra_env=None) -> str:
    """Run a python snippet in a CPU-only subprocess with N forced host
    devices (``extra_env`` adds variables to its otherwise fixed
    environment)."""
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={n_devices}",
           "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": "src", "PATH": os.environ.get(
               "PATH", "/usr/bin:/bin:/usr/local/bin"),
           "HOME": os.environ.get("HOME", "/root")}
    env.update(extra_env or {})
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout,
                          cwd=REPO_ROOT)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout

