"""expert_ms_per_pass (MoE expert layer): device milliseconds in the routed
experts' operations per pass of the expert layer, over the ``--trace 1``
sub-window.

A pass is one decode step (the engine's ``n_slots`` rows) or one prefill
segment, as the engine dispatches them.  A program that prefills a segment
in one forward pass runs the experts once on its W tokens; one that scans
the segment token by token runs them W times on ``n_slots`` rows, and its
pass costs that much more.

The operations are picked by kind and result shape (``trace.op_label``),
as ``expert_ms_per_step`` picks them, at every row count R the engine can
run the experts at: its ``n_slots`` and each power-of-two segment length
up to its prefill chunk (``max_seq`` without one).  With E the experts
held, F the expert width and D the model width:

* every operation whose result is ``[E, R, F]``: the experts' up and gate
  projections, each held expert on every row;
* the ``fusion f32[R, D]``: the experts' down projection, fused by XLA
  with the gate-weighted sum over the held experts; taken only at a row
  count R where an ``[E, R, F]`` operation ran, since a program may hold a
  ``f32[1, D]`` operation of its own.

The device seconds of those operations inside the traced window are
divided by the decode steps plus the prefill segments dispatched in it.
None where the run was not traced, the configuration holds no experts,
nothing was dispatched or no operation matches.
"""
import re


def row_counts(engine):
    """The row counts the experts can run at: the engine's slots and its
    power-of-two prefill segment lengths."""
    longest = engine.get("prefill_chunk") or engine["max_seq"]
    seg = {1 << k for k in range(max(longest - 1, 0).bit_length() + 1)}
    return sorted(seg | {engine["n_slots"]})


def expert_labels(op_seconds, model, engine):
    """The labels of ``op_seconds`` that are routed-expert operations."""
    moe = model.get("moe") or {}
    if not moe.get("n_experts"):
        return []
    e = moe.get("experts_held") or moe["n_experts"]
    f, d = moe["expert_d_ff"], model["d_model"]
    rows = "|".join(map(str, row_counts(engine)))
    up = re.compile(rf"^\S+ \w+\[{e},({rows}),{f}\]$")
    ran = {m.group(1) for m in map(up.match, op_seconds) if m}
    down = {f"fusion f32[{r},{d}]" for r in ran}
    return [label for label in op_seconds
            if up.match(label) or label in down]


def read(run):
    if run.trace is None or run.trace_span is None:
        return None
    cfg = run.cell.config
    labels = expert_labels(run.trace.op_seconds, cfg["model"], cfg["engine"])
    lo, hi = run.trace_span
    passes = sum(n if kind == "decode" else 1
                 for t, kind, n in run.steps if lo <= t <= hi)
    if not labels or not passes:
        return None
    return sum(run.trace.op_seconds[k] for k in labels) / passes * 1e3
