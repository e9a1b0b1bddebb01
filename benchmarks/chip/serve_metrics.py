"""Per-layer values from what the serving engine records about itself.

Two sources, both the program's own instrumentation:

* its host spans in a profiler trace (``.xplane.pb``): ``serve.tick``
  around a serving tick, ``serve.admit``, ``serve.prefill.dispatch`` and
  ``serve.decode.dispatch`` around each dispatch of a jitted entry point
  (keyword arguments ``uid``, ``slot``, ``tokens``, ``steps``, ``live``),
  ``serve.sync`` where the host waits for a decode block, and
  ``serve.account`` around its crediting;
* its request stamps, ``ServeEngine.request_times(uid)``, on the engine's
  clock.

A dispatch is matched to the device run it caused through the ``run_id``
that the host's ``DoEnqueueProgram`` event and the device's ``XLA
Modules`` event both carry, and the profiler's flow from that enqueue
back to the execute call on the dispatching thread (``host_events``): the
dispatch is the ``serve.*.dispatch`` span that encloses that call, and
the run is named after the jitted entry point
(``jit_serve_prefill(<hash>)``).  Only runs whose dispatch lies inside
the traced window (``bench.traced``) and whose device event the trace
holds are counted, so a run cut by the end of the trace leaves out its
steps as well as its time.

Idle gaps are labelled as ``trace.py`` labels them, by the innermost span
covering a gap's middle, with the program's ``serve.*`` spans counted
beside the benchmark's ``bench.*`` ones.

A trace of a program without these spans (or with its entry points under
other names) gives an empty table and no tick times, and each value below
is then None.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
SPAN_PREFIXES = ("bench.", "serve.")
WINDOW_SPAN = "bench.traced"
TICK_SPAN, SYNC_SPAN = "serve.tick", "serve.sync"
DISPATCH = re.compile(r"^serve\.\w+\.dispatch$")
ENQUEUE = "DoEnqueueProgram"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
PREFILL, DECODE = "jit_serve_prefill", "jit_serve_decode_many"


@dataclass
class Span:
    name: str
    start: float      # ns
    end: float        # ns
    args: Dict[str, object] = field(default_factory=dict)


@dataclass
class ServeSummary:
    window_s: float
    # per jitted entry point: matched runs, their device seconds, and the
    # steps and valid tokens their dispatch spans name
    executables: Dict[str, Dict[str, float]]
    # host self time of each serve.tick inside the window: the tick less
    # the serve.sync spans inside it (seconds)
    tick_self_s: List[float]
    # idle gaps labelled by bench.* or serve.* spans (see trace.py)
    gaps: List[Tuple[str, float]]


def host_events(planes):
    """(spans with a benchmark or program prefix, enqueues as (dispatch
    time in ns, run_id)) over every host plane.

    The runtime enqueues a run with a ``DoEnqueueProgram`` event that
    carries its ``run_id``.  That event sits inside a flow's child event
    (stats ``_ct``, ``_c``: the flow's type and id) whose origin, the event
    with the same ``_pt``, ``_p``, is the runtime's execute call on the
    thread that dispatched the program.  Where the program could not start
    at once (its donated inputs still in use), the enqueue runs later on a
    runtime thread, after the dispatch has returned; the origin still lies
    inside the dispatch.  The time given is the origin's start, or the
    enqueue's own where it has no enclosing flow."""
    spans, found, origins = [], [], {}
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            children, enqueues = [], []
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIXES):
                    # a TraceAnnotation's keyword arguments are the
                    # event's stats
                    spans.append(Span(ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns,
                                      dict(ev.stats)))
                    continue
                st = dict(ev.stats)
                if "_p" in st:
                    origins.setdefault((st.get("_pt"), st["_p"]),
                                       ev.start_ns)
                if "_c" in st:
                    children.append((ev.start_ns,
                                     ev.start_ns + ev.duration_ns,
                                     (st.get("_ct"), st["_c"])))
                if ev.name == ENQUEUE and "run_id" in st:
                    enqueues.append((ev.start_ns, st["run_id"]))
            for t, run in enqueues:
                flows = [c for c in children if c[0] <= t <= c[1]]
                flow = min(flows, key=lambda c: c[1] - c[0])[2] \
                    if flows else None
                found.append((t, run, flow))
    return spans, [(origins.get(flow, t), run) for t, run, flow in found]


def device_runs(planes) -> Dict[int, Tuple[str, float]]:
    """run_id -> (entry point, device seconds) from the devices' ``XLA
    Modules`` lines; a run on several devices takes its longest."""
    runs: Dict[int, Tuple[str, float]] = {}
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                run = dict(ev.stats).get("run_id")
                if run is None:
                    continue
                name = ev.name.split("(")[0]
                secs = ev.duration_ns * 1e-9
                if run not in runs or runs[run][1] < secs:
                    runs[run] = (name, secs)
    return runs


def match_dispatches(spans: List[Span], enqueues, runs, lo: float,
                     hi: float) -> Dict[str, Dict[str, float]]:
    """Per entry point: the runs enqueued inside a ``serve.*.dispatch``
    span within [lo, hi], with their device seconds, steps and tokens."""
    dispatches = sorted((s for s in spans if DISPATCH.match(s.name)
                         and lo <= s.start and s.end <= hi),
                        key=lambda s: s.start)
    starts = [s.start for s in dispatches]
    table: Dict[str, Dict[str, float]] = defaultdict(
        lambda: {"runs": 0, "device_s": 0.0, "steps": 0, "tokens": 0})
    for t, run in enqueues:
        i = int(np.searchsorted(starts, t, side="right")) - 1
        if i < 0 or t > dispatches[i].end or run not in runs:
            continue
        name, secs = runs[run]
        row = table[name]
        row["runs"] += 1
        row["device_s"] += secs
        row["steps"] += int(dispatches[i].args.get("steps", 0))
        row["tokens"] += int(dispatches[i].args.get("tokens", 0))
    return dict(table)


def tick_self_s(spans: List[Span], lo: float, hi: float) -> List[float]:
    ticks = [s for s in spans if s.name == TICK_SPAN
             and lo <= s.start and s.end <= hi]
    syncs = [s for s in spans if s.name == SYNC_SPAN]
    return [((t.end - t.start)
             - sum(s.end - s.start for s in syncs
                   if t.start <= s.start and s.end <= t.end)) * 1e-9
            for t in ticks]


def _trace_module():
    """A private instance of ``trace.py`` whose gap labels take the
    program's spans too."""
    import harness
    mod = harness.load_module(HERE / "trace.py")
    mod.SPAN_PREFIX = SPAN_PREFIXES
    return mod


def reduce(path: str) -> ServeSummary:
    """Reduce the trace at ``path``."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    spans, enqueues = host_events(planes)
    window = [s for s in spans if s.name == WINDOW_SPAN]
    if len(window) != 1:
        raise RuntimeError(f"trace holds {len(window)} {WINDOW_SPAN} spans")
    lo, hi = window[0].start, window[0].end
    labelled = _trace_module().reduce(path)
    return ServeSummary(
        window_s=(hi - lo) * 1e-9,
        executables=match_dispatches(spans, enqueues, device_runs(planes),
                                     lo, hi),
        tick_self_s=tick_self_s(spans, lo, hi),
        gaps=labelled.gaps)


def prefill_ms_per_token(s: ServeSummary) -> Optional[float]:
    """Device ms of the matched ``jit_serve_prefill`` runs per valid
    prompt token their dispatches fed."""
    row = s.executables.get(PREFILL)
    return row["device_s"] * 1e3 / row["tokens"] if row and row["tokens"] \
        else None


def decode_step_ms(s: ServeSummary) -> Optional[float]:
    """Device ms of the matched ``jit_serve_decode_many`` runs per
    scanned decode step."""
    row = s.executables.get(DECODE)
    return row["device_s"] * 1e3 / row["steps"] if row and row["steps"] \
        else None


def tick_host_ms(s: ServeSummary) -> Optional[float]:
    """Mean host self time of a ``serve.tick`` in the window (ms)."""
    return float(np.mean(s.tick_self_s)) * 1e3 if s.tick_self_s else None


def admit_waits_s(records, times: Dict[int, Optional[Dict]],
                  cutoff: float) -> Optional[List[float]]:
    """``admitted - submitted`` on the engine clock for each record, from
    ``times`` (uid -> ``request_times(uid)``); a request never admitted
    waits until ``cutoff``, measured on the host clock from its
    ``Record.submitted``.  None where the program kept no stamps."""
    out = []
    for r in records:
        t = times.get(r.uid)
        if t is None:
            return None
        out.append(t["admitted"] - t["submitted"]
                   if t.get("admitted") is not None
                   else cutoff - r.submitted)
    return out


def admit_wait_p95_ms(records, times, cutoff: float) -> Optional[float]:
    waits = admit_waits_s(records, times, cutoff)
    return float(np.percentile(waits, 95)) * 1e3 if waits else None
