"""The reduction of the program's own spans (``serve_metrics.py``) on a
trace recorded on one TPU v5e by ``record_serve_trace.py``
(``data/tiny_serve_v5e.xplane.pb``): one layer of width 128, 8 slots,
prefill chunks of 4, decode blocks of 2; two requests with prompt feeds
of 6 (segments 4 + 2) and 2 tokens, 4 new tokens each, served in three
``bench.tick`` spans inside ``bench.traced``.  The runtime enqueued each
program from a thread of its own, at times after the dispatch had
returned, so the match follows the profiler's flow back to the
dispatch."""
from pathlib import Path

import pytest

from bench_smoke import BENCH
import harness

TRACE = Path(__file__).resolve().parent / "data" / "tiny_serve_v5e.xplane.pb"
sm = harness.load_module(BENCH / "serve_metrics.py")


@pytest.fixture(scope="module")
def events():
    """(name, start ns, end ns, stats, line) of every event."""
    from jax.profiler import ProfileData
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats), line.name)
            for plane in ProfileData.from_file(str(TRACE)).planes
            for line in plane.lines for ev in line.events]


@pytest.fixture(scope="module")
def served():
    return sm.reduce(str(TRACE))


def _by_order(events):
    """Dispatch spans paired with device runs by order alone: one stream,
    nothing else ran on the device, so the k-th dispatch is the k-th
    run_id."""
    spans = sorted((s, n, st) for n, s, _, st, _ in events
                   if n.startswith("serve.") and n.endswith(".dispatch"))
    runs = sorted((st["run_id"], n.split("(")[0], (e - s) * 1e-9)
                  for n, s, e, st, line in events if line == "XLA Modules")
    assert len(spans) == len(runs) == 5
    return [(n, st, name, secs)
            for (_, n, st), (_, name, secs) in zip(spans, runs)]


def test_an_enqueue_can_lie_in_the_next_dispatch(events):
    """What following the flow is for: the first prefill's program was
    enqueued while the second prefill was being dispatched."""
    spans = sorted((s, e) for n, s, e, _, _ in events
                   if n.startswith("serve.") and n.endswith(".dispatch"))
    enq = sorted(s for n, s, _, _, _ in events if n == "DoEnqueueProgram")
    assert len(enq) == len(spans) == 5
    assert not all(a <= t <= b for t, (a, b) in zip(enq, spans))
    assert spans[1][0] <= enq[0] <= spans[1][1]


def test_runs_match_dispatches_by_run_id(events, served):
    pairs = _by_order(events)
    assert [(n, name) for n, _, name, _ in pairs] == \
        [("serve.prefill.dispatch", "jit_serve_prefill")] * 3 + \
        [("serve.decode.dispatch", "jit_serve_decode_many")] * 2
    pre = served.executables["jit_serve_prefill"]
    dec = served.executables["jit_serve_decode_many"]
    assert set(served.executables) == {"jit_serve_prefill",
                                       "jit_serve_decode_many"}
    assert (pre["runs"], pre["tokens"], pre["steps"]) == (3, 8, 8)
    assert (dec["runs"], dec["tokens"], dec["steps"]) == (2, 0, 4)
    for row, name in ((pre, "jit_serve_prefill"),
                      (dec, "jit_serve_decode_many")):
        assert row["device_s"] == pytest.approx(
            sum(secs for _, _, n, secs in pairs if n == name), rel=1e-9)


def test_the_readers_by_hand(events, served):
    pairs = _by_order(events)
    pre_s = sum(s for n, _, _, s in pairs if n == "serve.prefill.dispatch")
    dec_s = sum(s for n, _, _, s in pairs if n == "serve.decode.dispatch")
    tokens = sum(st["tokens"] for n, st, _, _ in pairs
                 if n == "serve.prefill.dispatch")
    steps = sum(st["steps"] for n, st, _, _ in pairs
                if n == "serve.decode.dispatch")
    assert (tokens, steps) == (8, 4)
    assert sm.prefill_ms_per_token(served) == pytest.approx(pre_s * 1e3 / 8)
    assert sm.decode_step_ms(served) == pytest.approx(dec_s * 1e3 / 4)
    ticks = [(s, e) for n, s, e, _, _ in events if n == "serve.tick"]
    syncs = [(s, e) for n, s, e, _, _ in events if n == "serve.sync"]
    self_ns = [(e - s) - sum(b - a for a, b in syncs if s <= a and b <= e)
               for s, e in ticks]
    assert len(ticks) == len(served.tick_self_s) == 3
    assert sm.tick_host_ms(served) == pytest.approx(
        sum(self_ns) / 3 * 1e-6, rel=1e-9)


def test_an_in_tick_gap_is_named_by_a_serve_span(served):
    labels = {n for n, s in served.gaps if s >= 50e-6}
    assert {"serve.prefill.dispatch", "serve.decode.dispatch",
            "bench.wait_arrival"} <= labels
    base = harness.load_module(BENCH / "trace.py").reduce(str(TRACE))
    assert sum(s for _, s in served.gaps) == pytest.approx(
        base.window_s - base.busy_s, rel=1e-6)
    # the benchmark's own labels see only the tick around the same gaps
    assert not any(n.startswith("serve.") for n, _ in base.gaps)
