"""The trace reduction on a small trace recorded on a TPU v5e (one chip,
``jax.profiler``): three host ticks, each a call of the block-sparse
Pallas kernel (8 x 512 by 512 x 512, half its K-blocks zero) and of a
small matmul, then a 20 ms sleep in a ``bench.wait_arrival`` span, all
inside the ``bench.traced`` span.  The trace stamps device events about
1 ms earlier than the host events of the same moment, so the first
tick's kernel falls just before the window."""
from pathlib import Path

import pytest

from bench_smoke import BENCH
import harness

TRACE = Path(__file__).resolve().parent / "data" / "tiny_v5e.xplane.pb"
reduce_trace = harness.load_module(BENCH / "trace.py").reduce


@pytest.fixture(scope="module")
def summary():
    return reduce_trace(str(TRACE))


def test_busy_and_window(summary):
    assert summary.n_devices == 1
    assert 0.06 < summary.window_s < 0.5
    assert 0 < summary.busy_s < 0.1 * summary.window_s


def test_kernel_events_are_found_by_name(summary):
    # two of the three calls fall inside the window, each a few us
    seconds = summary.op_seconds["custom-call %_block_sparse_matmul f32[8,512]"]
    assert 2e-6 < seconds < 2e-5
    ops = dict(summary.breakdown()["device_ops"])
    assert "custom-call %_block_sparse_matmul f32[8,512]" in ops


def test_idle_gaps_are_labelled_by_the_host_span(summary):
    waits = [s for name, s in summary.gaps
             if name == "bench.wait_arrival" and s > 0.01]
    assert len(waits) == 3 and all(0.015 < s < 0.05 for s in waits)
    total = sum(s for _, s in summary.gaps)
    assert total == pytest.approx(summary.window_s - summary.busy_s,
                                  rel=1e-6)


def test_breakdown_lists_at_most_ten_of_each(summary):
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] == "bench.wait_arrival"
