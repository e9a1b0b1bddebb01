"""The ``expert_ms_per_pass`` reader: the routed experts' operations at
every row count the engine runs them at, over decode steps plus prefill
segments.

On the trace that ``record_moe_trace.py`` recorded on one TPU v5e
(``data/tiny_moe_v5e.xplane.pb``, a program that prefilled token by
token: three prefill segments of 4 + 2 and 2 padded steps and two decode
blocks of 2 steps inside ``bench.traced``), and on the operation labels of
a program that prefills a segment in one forward pass at the MoE cell's
sizes."""
import json
import types
from pathlib import Path

import pytest

from bench_smoke import BENCH
import harness

TRACE = Path(__file__).resolve().parent / "data" / "tiny_moe_v5e.xplane.pb"
reader = harness.load_module(BENCH / "metrics" / "expert_ms_per_pass.py")
per_step = harness.load_module(BENCH / "metrics" / "expert_ms_per_step.py")
recorder = harness.load_module(BENCH / "tests" / "record_moe_trace.py")
trace_mod = harness.load_module(BENCH / "trace.py")
sm = harness.load_module(BENCH / "serve_metrics.py")
CELL = json.loads((BENCH / "configs" / "deepseek-moe-16b-ep8.json")
                  .read_text())
# the tiny trace's dispatches, in the window (0, 1) but the last
TINY_STEPS = [(0.1, "prefill", 4), (0.2, "prefill", 2), (0.3, "prefill", 2),
              (0.4, "decode", 2), (0.5, "decode", 2), (1.5, "decode", 2)]


@pytest.fixture(scope="module")
def summary():
    return trace_mod.reduce(str(TRACE))


def _run(summary, steps, model=recorder.TINY_MODEL,
         engine=recorder.TINY_ENGINE):
    """The fields of ``harness.RunData`` the reader reads."""
    cell = types.SimpleNamespace(config={"model": model, "engine": engine})
    return types.SimpleNamespace(cell=cell, trace=summary,
                                 trace_span=(0.0, 1.0), steps=steps)


def test_row_counts_are_the_slots_and_the_segment_lengths():
    assert reader.row_counts(CELL["engine"]) == [1, 2, 4, 8, 16, 32, 64, 128]
    assert reader.row_counts({"n_slots": 6, "max_seq": 40,
                              "prefill_chunk": 5}) == [1, 2, 4, 6, 8]
    assert reader.row_counts({"n_slots": 8, "max_seq": 48,
                              "prefill_chunk": None}) == \
        [1, 2, 4, 8, 16, 32, 64]


def test_token_serial_trace_counts_each_segment_as_one_pass(summary):
    served = sm.reduce(str(TRACE)).executables
    assert (served[sm.PREFILL]["runs"], served[sm.DECODE]["steps"]) == (3, 4)
    labels = reader.expert_labels(summary.op_seconds, recorder.TINY_MODEL,
                                  recorder.TINY_ENGINE)
    # a token-serial prefill runs the experts on the slots' rows, as decode
    assert sorted(labels) == sorted(per_step.expert_labels(
        summary.op_seconds, recorder.TINY_MODEL, recorder.TINY_ENGINE))
    got = reader.read(_run(summary, TINY_STEPS))
    want = sum(summary.op_seconds[k] for k in labels) / (4 + 3) * 1e3
    assert got == pytest.approx(want)


def test_one_pass_prefill_operations_are_picked_at_the_segment_rows():
    expert = ["fusion bf16[8,8,1408]", "fusion f32[8,2048]",
              "fusion bf16[8,128,1408]", "fusion f32[128,2048]",
              "fusion bf16[8,32,1408]", "fusion f32[32,2048]"]
    others = ["copy bf16[8,2048,1408]",        # an expert weight
              "fusion bf16[8,100,1408]",       # no row count of the engine
              "fusion f32[128,64]",            # the router
              "fusion f32[1,128,2048]",
              "fusion f32[1,2048]",            # no expert ran on one row
              "fusion bf16[128,2816]"]         # the shared experts
    ops = dict.fromkeys(expert + others, 0.002)
    assert sorted(reader.expert_labels(ops, CELL["model"], CELL["engine"])) \
        == sorted(expert)
    steps = [(0.2, "prefill", 128), (0.3, "prefill", 32),
             (0.5, "decode", 16), (0.7, "decode", 16)]
    summary = types.SimpleNamespace(op_seconds=ops)
    got = reader.read(_run(summary, steps, CELL["model"], CELL["engine"]))
    assert got == pytest.approx(6 * 0.002 / (2 + 32) * 1e3)


def test_nothing_to_read(summary):
    assert reader.read(_run(None, TINY_STEPS)) is None
    assert reader.read(_run(summary, TINY_STEPS[-1:])) is None
    dense = {k: v for k, v in recorder.TINY_MODEL.items() if k != "moe"}
    assert reader.read(_run(summary, TINY_STEPS, dense)) is None
