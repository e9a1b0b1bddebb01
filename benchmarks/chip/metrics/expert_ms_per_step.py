"""expert_ms_per_step (MoE expert layer): device milliseconds a step in
the routed experts' operations, over the ``--trace 1`` sub-window.

The trace reduction labels each device operation by its kind and result
shape (``trace.op_label``).  The routed experts' operations are picked by
shape, with E the experts held (``model.moe.experts_held``), B the engine's
rows (``engine.n_slots``), F the expert width (``model.moe.expert_d_ff``)
and D the model width (``model.d_model``):

* every operation whose result is ``[E, B, F]``: the experts' up and gate
  projections, each held expert on every row;
* the ``fusion f32[B, D]``: the experts' down projection, fused by XLA
  with the gate-weighted sum over the held experts.

In the program's decode block and prefill segment at the cell's sizes no
other operation has either shape (the router's are ``[B, n_experts]``
and ``[E, B]``, the shared experts' ``[B, 2F]``, everything else in
bf16).  The device seconds of those operations inside the traced window
are divided by the model steps (decode steps plus padded prefill steps)
dispatched in it.  None where the run was not traced, the configuration
holds no experts, no step was dispatched or no operation matches.
"""
import re


def expert_labels(op_seconds, model, engine):
    """The labels of ``op_seconds`` that are routed-expert operations."""
    moe = model.get("moe") or {}
    if not moe.get("n_experts"):
        return []
    e = moe.get("experts_held") or moe["n_experts"]
    b, f, d = engine["n_slots"], moe["expert_d_ff"], model["d_model"]
    pattern = re.compile(rf"^\S+ \w+\[{e},{b},{f}\]$|^fusion f32\[{b},{d}\]$")
    return [label for label in op_seconds if pattern.match(label)]


def read(run):
    if run.trace is None or run.trace_span is None:
        return None
    cfg = run.cell.config
    labels = expert_labels(run.trace.op_seconds, cfg["model"], cfg["engine"])
    steps = sum(run.steps_in(*run.trace_span).values())
    if not labels or not steps:
        return None
    return sum(run.trace.op_seconds[k] for k in labels) / steps * 1e3
