"""The ``expert_ms_per_step`` reader on a trace recorded on one TPU v5e by
``record_moe_trace.py`` (``data/tiny_moe_v5e.xplane.pb``): the DeepSeek-MoE
chip-share configuration at a tiny width (a dense layer and two MoE
layers, 4 of 16 routed experts of width 192 held, 8 slots), two requests
served in three prefill segments (4 + 2 and 2 padded steps) and two
decode blocks of 2 steps inside ``bench.traced``."""
import types
from pathlib import Path

import pytest

from bench_smoke import BENCH
import harness

TRACE = Path(__file__).resolve().parent / "data" / "tiny_moe_v5e.xplane.pb"
reader = harness.load_module(BENCH / "metrics" / "expert_ms_per_step.py")
recorder = harness.load_module(BENCH / "tests" / "record_moe_trace.py")
trace_mod = harness.load_module(BENCH / "trace.py")
sm = harness.load_module(BENCH / "serve_metrics.py")
EXPERT_OPS = ["fusion bf16[4,8,192]", "fusion f32[8,256]"]


@pytest.fixture(scope="module")
def summary():
    return trace_mod.reduce(str(TRACE))


def _run(summary, steps, model=recorder.TINY_MODEL):
    """The fields of ``harness.RunData`` the reader reads."""
    cell = types.SimpleNamespace(config={"model": model,
                                         "engine": recorder.TINY_ENGINE})
    return types.SimpleNamespace(cell=cell, trace=summary,
                                 trace_span=(0.0, 1.0),
                                 steps_in=lambda lo, hi: dict(steps))


def test_the_rule_picks_the_expert_operations(summary):
    labels = reader.expert_labels(summary.op_seconds, recorder.TINY_MODEL,
                                  recorder.TINY_ENGINE)
    assert sorted(labels) == EXPERT_OPS
    assert all(summary.op_seconds[k] > 0 for k in labels)


def test_device_ms_per_step_in_the_expert_operations(summary):
    served = sm.reduce(str(TRACE)).executables
    steps = {"prefill": served[sm.PREFILL]["steps"],
             "decode": served[sm.DECODE]["steps"]}
    assert steps == {"prefill": 8, "decode": 4}
    got = reader.read(_run(summary, steps))
    want = sum(summary.op_seconds[k] for k in EXPERT_OPS) / 12 * 1e3
    assert got == pytest.approx(want)
    # a part of each step's device time, which the two MoE layers' expert
    # operations take only part of
    assert 0 < got < summary.busy_s / 12 * 1e3


def test_nothing_to_read(summary):
    steps = {"prefill": 8, "decode": 4}
    assert reader.read(_run(None, steps)) is None
    assert reader.read(_run(summary, {"prefill": 0, "decode": 0})) is None
    dense = {k: v for k, v in recorder.TINY_MODEL.items() if k != "moe"}
    assert reader.read(_run(summary, steps, dense)) is None
