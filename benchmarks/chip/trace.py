"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Reads the trace with ``jax.profiler.ProfileData``.  The device planes are
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation run, named by its HLO text (``%fusion.85 = bf16[...] fusion(...)``;
loops appear as ``while`` events around their body's operations).  The
traced window is the host span ``bench.traced`` that the harness opens
and closes around it.  Reports:

* busy seconds: the union of the operation intervals inside the window,
  averaged over the devices;
* device time per operation label (kind and result shape, loops left
  out); a Pallas kernel's event is named after the function that calls
  ``pallas_call`` (``%_block_sparse_matmul.58 = f32[8,100352]
  custom-call(...)``), and its label keeps that name;
* idle gaps: the intervals inside the window with no operation running,
  those of 50 us or more each labelled by the innermost benchmark span
  (``bench.*``) that the host thread was in at the gap's middle, the
  shorter ones summed.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.traced"
SPAN_PREFIX = "bench."
# gaps shorter than this are summed under SHORT_GAPS instead of labelled
LABEL_NS = 50_000
SHORT_GAPS = "device.between_ops"
HLO_OP = re.compile(r"^%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
CONTAINERS = ("while", "conditional", "call")


@dataclass
class Summary:
    window_s: float
    busy_s: float
    n_devices: int
    op_seconds: Dict[str, float]
    gaps: List[Tuple[str, float]] = field(default_factory=list)

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def find_xplane(logdir: str) -> str:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {logdir}, found "
                           f"{len(paths)}")
    return paths[0]


def op_label(name: str) -> Optional[str]:
    """``kind shape`` of an HLO op event (``custom-call`` adds the callee's
    name), without layouts; None for loops and other containers."""
    m = HLO_OP.match(name)
    if not m:
        return name[:80]
    op, shape, kind = m.groups()
    if kind in CONTAINERS:
        return None
    shape = re.sub(r"\{[^{}]*\}", "", shape)
    if shape.startswith("("):
        shape = shape.split(",")[0] + ", ...)"
    if kind == "custom-call":
        return "custom-call %" + re.sub(r"[.]\d+$", "", op) + " " + shape
    return f"{kind} {shape}"


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def host_spans(planes) -> List[Tuple[str, float, float]]:
    """Every ``bench.*`` span on the host planes: (name, start, end) ns."""
    out = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def reduce(path: str) -> Summary:
    """Reduce the trace at ``path``."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(path).planes)
    spans = host_spans(planes)
    window = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if len(window) != 1:
        raise RuntimeError(f"trace holds {len(window)} {WINDOW_SPAN} spans")
    lo, hi = window[0]
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]

    busy_ns, n_dev = 0.0, 0
    op_ns: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[str, float]] = []
    for plane in planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
        if not lines:
            continue
        n_dev += 1
        ivals = []
        for ev in lines[0].events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= lo or s >= hi:
                continue
            ivals.append((s, e))
            label = op_label(ev.name)
            if label is not None:
                op_ns[label] += min(e, hi) - max(s, lo)
        merged = _clip(_union(ivals), lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        short = 0.0
        for s, e in zip(edges[0::2], edges[1::2]):
            if e - s >= LABEL_NS:
                gaps.append((_label(inner, (s + e) / 2), (e - s) * 1e-9))
            elif e > s:
                short += (e - s) * 1e-9
        gaps.append((SHORT_GAPS, short))
    if not n_dev:
        raise RuntimeError("trace holds no TPU device plane with XLA Ops")
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_ns / n_dev * 1e-9,
                   n_devices=n_dev,
                   op_seconds={k: v / n_dev * 1e-9 for k, v in op_ns.items()},
                   gaps=gaps)


def _label(spans, t: float) -> str:
    """The innermost span covering ``t``, or ``host.other``."""
    best: Optional[Tuple[float, str]] = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, name)
    return best[1] if best else "host.other"
