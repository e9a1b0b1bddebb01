"""The reduction of the serving engine's own records (``serve_metrics.py``)
on synthetic spans and stamps, and on the trace recorded before the
program had spans (``data/tiny_v5e.xplane.pb``, see
``test_bench_trace.py``): that trace still reduces to the same busy time
and gaps, and a program without ``serve.*`` spans gives no values."""
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench_smoke import BENCH
import harness

DATA = Path(__file__).resolve().parent / "data"
sm = harness.load_module(BENCH / "serve_metrics.py")
trace = harness.load_module(BENCH / "trace.py")
Span = sm.Span


def test_the_old_trace_reduces_as_before():
    base = trace.reduce(str(DATA / "tiny_v5e.xplane.pb"))
    assert base.busy_s == pytest.approx(1.0929e-05, rel=1e-9)
    assert base.window_s == pytest.approx(0.066471579, rel=1e-9)
    waits = sorted(s for n, s in base.gaps if n == "bench.wait_arrival")
    assert waits == pytest.approx([0.000721479, 0.00079848, 0.021000211,
                                   0.021711532, 0.022228935], rel=1e-9)
    assert [n for n, _ in base.gaps].count("device.between_ops") == 1
    # without serve.* spans the relabelled gaps are the benchmark's own
    serve = sm.reduce(str(DATA / "tiny_v5e.xplane.pb"))
    assert serve.gaps == base.gaps
    assert serve.window_s == base.window_s


def test_a_program_without_spans_gives_no_values():
    serve = sm.reduce(str(DATA / "tiny_v5e.xplane.pb"))
    assert serve.executables == {} and serve.tick_self_s == []
    assert sm.prefill_ms_per_token(serve) is None
    assert sm.decode_step_ms(serve) is None
    assert sm.tick_host_ms(serve) is None
    rec = SimpleNamespace(uid=1, submitted=0.0)
    assert sm.admit_wait_p95_ms([rec], {1: None}, 1.0) is None


def test_dispatches_match_runs_by_run_id():
    """Two prefill segments and a decode block; an enqueue outside any
    dispatch span, a run the trace lacks, and a dispatch outside the
    window are not counted."""
    spans = [Span("serve.prefill.dispatch", 100, 200,
                  {"uid": 1, "tokens": 5, "steps": 8}),
             Span("serve.prefill.dispatch", 300, 400,
                  {"uid": 1, "tokens": 2, "steps": 2}),
             Span("serve.decode.dispatch", 500, 600,
                  {"steps": 16, "live": 3}),
             Span("serve.decode.dispatch", 2000, 2100,
                  {"steps": 16, "live": 3})]
    enqueues = [(150, 7), (350, 8), (550, 9), (700, 10), (580, 11),
                (2050, 12)]
    runs = {7: ("jit_serve_prefill", 0.08), 8: ("jit_serve_prefill", 0.02),
            9: ("jit_serve_decode_many", 0.48), 10: ("jit_other", 1.0),
            12: ("jit_serve_decode_many", 0.5)}
    table = sm.match_dispatches(spans, enqueues, runs, 0, 1000)
    assert table == {
        "jit_serve_prefill": {"runs": 2, "device_s": pytest.approx(0.1),
                              "steps": 10, "tokens": 7},
        "jit_serve_decode_many": {"runs": 1, "device_s": 0.48, "steps": 16,
                                  "tokens": 0}}
    s = sm.ServeSummary(1e-6, table, [], [])
    assert sm.prefill_ms_per_token(s) == pytest.approx(100 / 7)
    assert sm.decode_step_ms(s) == pytest.approx(30.0)


def test_tick_self_time_leaves_out_its_syncs():
    spans = [Span("serve.tick", 0, 1_000_000),
             Span("serve.sync", 100_000, 700_000),
             Span("serve.sync", 800_000, 900_000),
             Span("serve.tick", 1_000_000, 1_500_000),
             Span("serve.tick", 1_900_000, 2_500_000)]   # past the window
    got = sm.tick_self_s(spans, 0, 2_000_000)
    assert got == pytest.approx([300e-6, 500e-6])
    s = sm.ServeSummary(2e-3, {}, got, [])
    assert sm.tick_host_ms(s) == pytest.approx(0.4)


def test_admit_waits_from_the_stamps():
    """Waits on the engine clock from the stamps; a request never
    admitted waits until the cut-off on the host clock."""
    recs = [SimpleNamespace(uid=u, submitted=10.0 + u) for u in range(1, 5)]
    times = {1: {"submitted": 100.0, "admitted": 100.5},
             2: {"submitted": 101.0, "admitted": 101.0},
             3: {"submitted": 102.0, "admitted": 104.0},
             4: {"submitted": 103.0, "admitted": None}}
    assert sm.admit_waits_s(recs, times, cutoff=20.0) == [0.5, 0.0, 2.0,
                                                          6.0]
    assert sm.admit_wait_p95_ms(recs, times, 20.0) == pytest.approx(
        (2.0 + 0.85 * 4.0) * 1e3)
