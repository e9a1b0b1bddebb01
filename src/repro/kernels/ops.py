"""Jit'd dispatch wrappers for the Pallas kernels (DESIGN.md D3).

Every matmul site in the model zoo routes through one of three entry points
— ``flex_matmul`` (2-D / stacked leaves), ``flex_expert_matmul`` (the MoE
batched-expert einsums, (E, C, K) × (E, K, N)) and ``head_matmul`` (the
einsum-based lm_head/logits contraction) — so plan coverage is *total*: no
matmul in the decode path bypasses the site dispatch.  A process-wide
execution config decides whether the Pallas TPU kernels run (TPU target /
interpret mode) or the semantically identical XLA ops (CPU tests and the
compile-only dry-run — Pallas TPU kernels do not lower for the CPU backend).

The Pallas path consults the site's ``MatmulSchedule`` (FlexNN descriptor)
for stationarity + block shapes; the XLA path leaves tiling to XLA while the
*sharding*-level schedule decisions still apply.

Sparsity dispatch (the §III-D wiring): when the site's descriptor carries
``sparsity_mode`` of ``weight`` or ``two_sided``, the site routes through
the block-sparse path instead of the dense matmul.  Two sources of CSB
metadata:

  * **Precompiled plan** — when the weight arrives as a
    ``core.sparsity.PlannedWeight`` (the engine attached a
    ``WeightSparsityPlan`` into the params pytree at bring-up), the
    weight-side bitmaps and live-K lists are ordinary jit inputs; only the
    *activation-side* bitmap is derived at trace time, ANDed in via
    ``combine_with_activation_meta`` (two_sided) or broadcast without any
    sort (weight mode).  The kernel grid runs the plan's tight static
    ``max_nnz`` ≤ tk.
  * **Trace time** — without a plan, metadata is built from the operand
    block bitmaps at the schedule's (bm, bk, bn) granularity with the safe
    ``max_nnz = tk`` bound — so per-layer weight slices inside a scan each
    get their own bitmap, rebuilt every step.

``weight`` mode uses an all-ones activation bitmap (FL-side skipping only).
On the Pallas path the scalar-prefetch kernel in ``kernels.block_sparse``
chases the compressed K-index lists (the CAG-unit analogue); on CPU the
masked-XLA oracle computes the same skip semantics.  Bitmaps derived from
the data make every mode numerically identical to the dense product — zero
blocks are skipped, never approximated.

Runtime feedback: when a ``SparsityStatsCollector`` is installed
(``sparsity_stats``), two-sided sites emit their activation popcounts via
``jax.debug.callback`` — the measured densities calibrate the scheduler's
0.5 activation prior (``core.descriptors.sparsity_densities_for``).

Fused serving blocks (``model.decode_many`` — a ``lax.scan`` over T decode
steps with a donated state carry) change nothing here by design: the
``PlannedWeight`` leaves are scan *constants* (attached params, not carry),
so the precompiled metadata is fetched once per block rather than per
token, and ``jax.debug.callback`` fires once per scanned step per site —
a T-step block accumulates exactly the popcount window T per-token steps
would.  Donation only aliases the state carry; plan arrays and collector
identity are untouched (test-enforced by the post-fused recalibration
regressions).
"""
from __future__ import annotations

import contextlib
import functools
import threading
from dataclasses import dataclass
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core.sparsity import PlannedWeight
from repro.quant.quantize import QuantizedLinear

_state = threading.local()


@dataclass(frozen=True)
class ExecConfig:
    use_pallas: bool = False          # run Pallas kernels (TPU / interpret)
    interpret: bool = False           # Pallas interpret mode (CPU validation)
    schedules: Optional[object] = None   # NetworkSchedule (descriptor table)
    default_stationarity: str = "output"
    sparse_dispatch: bool = True      # honor SiteDescriptor.sparsity_mode
    plan: Optional[object] = None     # WeightSparsityPlan (engine bring-up)
    collect_stats: bool = False       # emit activation popcounts per site
    # the per-site activation densities the schedule was *selected under*
    # (None = the 0.5 prior) — the drift baseline for
    # ``serve.engine.ServeEngine.maybe_recalibrate`` — plus the ArchConfig
    # and sharding the descriptor table was compiled from, so the engine
    # can recompile the schedule without re-deriving them
    act_densities: Optional[Dict[str, float]] = None
    arch_cfg: Optional[object] = None
    model_shards: int = 1
    # params were int8-quantized at bring-up (QuantizedLinear leaves /
    # quantized PlannedWeight payloads); recorded so recalibration
    # recompiles under the same weight-byte model
    quantize: bool = False


def _cfg() -> ExecConfig:
    return getattr(_state, "cfg", None) or ExecConfig()


@contextlib.contextmanager
def exec_config(cfg: ExecConfig):
    prev = getattr(_state, "cfg", None)
    _state.cfg = cfg
    try:
        yield cfg
    finally:
        _state.cfg = prev


def _site_descriptor(site: str, cfg: Optional[ExecConfig] = None):
    cfg = cfg or _cfg()
    if cfg.schedules is not None and site in cfg.schedules.sites:
        return cfg.schedules.sites[site]
    return None


def site_schedule(site: str):
    desc = _site_descriptor(site)
    return desc.schedule if desc is not None else None


def site_sparsity_mode(site: str) -> str:
    cfg = _cfg()
    desc = _site_descriptor(site, cfg)
    if desc is None or not cfg.sparse_dispatch:
        return "dense"
    return desc.sparsity_mode


# ---------------------------------------------------------------------------
# Runtime activation-density feedback (popcount accumulation)
# ---------------------------------------------------------------------------

class SparsityStatsCollector:
    """Accumulates per-site activation popcounts emitted from inside the
    jitted step (via ``jax.debug.callback``) — the runtime half of the
    density-calibration loop: bring-up plan → decode step → popcount
    feedback → recompiled schedule."""

    def __init__(self):
        self._live: Dict[str, int] = {}
        self._total: Dict[str, int] = {}

    def reset(self) -> None:
        """Clear the window *in place*.  The jitted step's debug callback
        closed over this object at trace time, so the collector must never
        be replaced while a compiled step is live — resetting keeps the
        traced callback and the reader looking at the same instance."""
        self._live.clear()
        self._total.clear()

    def record(self, site: str, live, total):
        self._live[site] = self._live.get(site, 0) + int(live)
        self._total[site] = self._total.get(site, 0) + int(total)

    def densities(self) -> Dict[str, float]:
        """Measured element-level activation density per site.

        Zero-sample sites are skipped rather than divided by zero: a site
        whose every recorded tick had zero total elements (e.g. a block
        dispatched with no live rows) contributes no density estimate, and
        a fresh/reset collector returns ``{}``.  ``_live.get`` guards the
        (callback-ordering) corner where a total was recorded without a
        matching live count."""
        return {s: self._live.get(s, 0) / t
                for s, t in self._total.items() if t}


@contextlib.contextmanager
def sparsity_stats(collector: SparsityStatsCollector):
    """Install ``collector`` for the enclosed trace: two-sided sparse sites
    emit activation popcounts to it at run time."""
    prev = getattr(_state, "collector", None)
    _state.collector = collector
    try:
        yield collector
    finally:
        _state.collector = prev


@contextlib.contextmanager
def active_rows(mask):
    """Install a (B,) bool row-validity mask for the enclosed trace region.

    The serving batch always carries ``n_slots`` rows, but only some are
    *live* (dead slots and done/mid-prefill rows run token-0 filler).  The
    model's decode/prefill entry points install the mask they already carry
    (``decode_many``'s active mask, ``prefill_into_slot``'s admitted-row
    merge mask) around the inner ``decode_step`` so popcount accumulation
    counts live rows only — otherwise filler rows skew
    ``maybe_recalibrate`` toward the filler token's density at low
    occupancy.  ``mask`` may be a tracer: the scope is entered inside the
    traced function, so the masked popcount lowers into the same jaxpr.
    Sites whose leading operand dim is not the slot batch (e.g. the
    capacity-padded MoE expert buffers) ignore the mask — their rows encode
    routing occupancy, not slot liveness.
    """
    prev = getattr(_state, "rows", None)
    _state.rows = mask
    try:
        yield mask
    finally:
        _state.rows = prev


def _record_act_stats(site: str, x2: jax.Array) -> None:
    col = getattr(_state, "collector", None)
    if col is None or not site:
        return
    rows = getattr(_state, "rows", None)
    if rows is not None and x2.ndim == 2 and rows.shape[0] == x2.shape[0]:
        # count live rows only: a 1-live-of-N engine must measure the same
        # density as a 1-slot engine (test-enforced)
        live = jnp.sum(jnp.where(rows[:, None], x2 != 0, False)
                       .astype(jnp.int32))
        total = jnp.sum(rows.astype(jnp.int32)) * x2.shape[-1]
    else:
        live = jnp.sum((x2 != 0).astype(jnp.int32))
        total = x2.size
    jax.debug.callback(functools.partial(col.record, site), live, total)


def _leading_flat(x: jax.Array):
    """(..., K) -> ((M, K), lead_shape) with M = prod of leading dims."""
    lead = x.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    return x.reshape(m, x.shape[-1]), lead


def _run_block_sparse(xp: jax.Array, wp: jax.Array, meta, cfg: ExecConfig,
                      m: int, n: int, scale=None) -> jax.Array:
    """Shared kernel dispatch + unpad tail for both metadata sources.

    ``scale`` (padded-N,) f32 selects the quantized epilogue: ``wp`` is an
    int8 payload, dequantized inside the kernel (Pallas) or fused into the
    masked dot (XLA) with the accumulator scaled once per N column.
    """
    from repro.kernels import block_sparse as bs
    if cfg.use_pallas:
        out = bs.block_sparse_matmul(xp, wp, meta, interpret=cfg.interpret,
                                     out_dtype=jnp.float32, scale=scale)
    else:
        out = bs.block_sparse_matmul_ref(xp, wp, meta, scale=scale)
    return out[:m, :n]


def _sparse_site_matmul(x2: jax.Array, w: jax.Array, mode: str, sched,
                        cfg: ExecConfig, site: str = "") -> jax.Array:
    """(M, K) @ (K, N) through the CSB block-sparse path.

    Block granularity is the site schedule's (bm, bk, bn) clamped to the
    operand dims; inputs are zero-padded to block multiples (padding blocks
    are all-zero → CSB-dead → skipped).  Returns f32.
    """
    from repro.core import sparsity as sparsity_lib
    from repro.kernels.flex_matmul import DEFAULT_BLOCKS, pad_to_blocks

    m, k = x2.shape
    n = w.shape[1]
    if sched is not None:
        bm, bn, bk = sched.bm, sched.bn, sched.bk
    else:
        bm, bn, bk = DEFAULT_BLOCKS
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    xp = pad_to_blocks(x2, bm, bk)
    wp = pad_to_blocks(w, bk, bn)
    tm, tk = xp.shape[0] // bm, xp.shape[1] // bk
    b_bitmap = sparsity_lib.block_bitmap_jnp(wp, bk, bn)
    if mode == "two_sided":
        a_bitmap = sparsity_lib.block_bitmap_jnp(xp, bm, bk)
    else:                             # weight-sided: IF bitmap all ones
        a_bitmap = jnp.ones((tm, tk), bool)
    meta = sparsity_lib.build_block_sparse_meta_jnp(a_bitmap, b_bitmap,
                                                    site=site)
    return _run_block_sparse(xp, wp, meta, cfg, m, n)


def _gathered_planned_matmul(x2: jax.Array, pw: PlannedWeight) -> jax.Array:
    """Pruned-tier XLA dispatch: contract only the plan's live K-blocks.

    The masked-dense fallback (``block_sparse_matmul_ref``) zeroes dead
    blocks but still runs the full dense dot, so on the XLA path a pruned
    tier costs exactly as much as the full plan.  Here the plan's
    ``wkidx``/``wkcnt`` lists gather the ≤ ``max_nnz`` live K-blocks per
    output column and contract just those — FLOPs and weight bytes scale
    with ``max_nnz / tk``, which is what makes a pruned draft tier actually
    cheaper per decode step on the host substrate.

    Block sums are reassociated relative to the dense dot (last-ulp f32
    drift), so this path is reserved for ``gather``-marked tiers: their
    output is either re-verified token-by-token under the full plan
    (speculative drafts) or explicitly accuracy-relaxed (latency classes).

    Attach-time tiers carry the compacted payload precomputed
    (``pw.wgather``, padded slots pre-zeroed) — per-step work is then one
    small activation gather plus an einsum over ``max_nnz`` blocks.  When
    absent (hand-built nodes), the payload is gathered inline from the
    dense leaf; zero-padded index entries point at block 0 and the
    ``wkcnt`` mask zeroes their blocks, so they contribute nothing.
    """
    m, k = x2.shape
    tn = pw.wkcnt.shape[-1]
    kp = pw.tk * pw.bk
    if pw.qscale is not None:
        n = pw.w.shape[-1]
    else:
        n = pw.w.shape[-2] if pw.transpose else pw.w.shape[-1]
    np_ = tn * pw.bn
    xpad = jnp.pad(x2, ((0, 0), (0, kp - k))) if kp != k else x2
    xb = xpad.reshape(m, pw.tk, pw.bk)
    xg = xb[:, pw.wkidx, :]                         # (m, tn, nnz, bk)
    if pw.wgather is not None:
        wg = pw.wgather.astype(jnp.float32)         # (tn, nnz, bk, bn)
    else:
        w = pw.w if pw.qscale is not None else pw.w_kn
        wpad = (jnp.pad(w, ((0, kp - k), (0, np_ - n)))
                if (kp != k or np_ != n) else w)
        wb = wpad.reshape(pw.tk, pw.bk, tn, pw.bn)
        cols = jnp.arange(tn)
        wg = wb[pw.wkidx, :, cols[:, None], :]      # (tn, nnz, bk, bn)
        live = jnp.arange(pw.max_nnz)[None, :] < pw.wkcnt[:, None]
        wg = wg.astype(jnp.float32) * live[:, :, None, None]
    # batch-first dot_general over the tn output columns, contracting the
    # gathered (nnz·bk) axis jointly — one batched GEMM instead of tn·nnz
    # tiny matmuls (measured ~3x faster than the 4-D einsum lowering)
    lhs = xg.astype(jnp.float32).reshape(
        m, tn, pw.max_nnz * pw.bk).transpose(1, 0, 2)
    rhs = wg.reshape(tn, pw.max_nnz * pw.bk, pw.bn)
    out = jax.lax.dot_general(
        lhs, rhs, (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)         # (tn, m, bn)
    out = out.transpose(1, 0, 2).reshape(m, np_)[:, :n]
    if pw.qscale is not None:
        out = out * pw.qscale[None, :].astype(jnp.float32)
    return out


def _planned_matmul(x2: jax.Array, pw: PlannedWeight,
                    cfg: ExecConfig) -> jax.Array:
    """(M, K) @ planned (K, N): weight-side metadata comes precompiled from
    the plan (ordinary jit inputs); only the activation bitmap is derived at
    trace time.  The kernel grid runs the plan's tight static ``max_nnz``.

    ``gather``-marked tiers (pruned draft/latency tiers) on the XLA path
    take :func:`_gathered_planned_matmul` — live-block gather with
    max_nnz-proportional cost — instead of the masked dense dot.

    Quantized plans keep the weight as the int8 payload end-to-end: the
    block-sparse kernel fetches int8 tiles and the per-output-channel
    scales are applied once to the f32 accumulator in the epilogue
    (K-invariant scales — exact; `int8_matmul`'s trick on the sparse path).
    """
    from repro.core import sparsity as sparsity_lib
    from repro.kernels.flex_matmul import pad_to_blocks

    if pw.gather and not cfg.use_pallas:
        # two_sided sites take this path too: an all-zero activation block
        # contributes zero to the einsum, so not skipping it is exact — the
        # draft simply forgoes the activation-side discount
        return _gathered_planned_matmul(x2, pw)
    # quantized plans: dispatch on the raw int8 payload (always stored
    # contraction-oriented); float plans: dense (K, N) orientation
    w = pw.w if pw.qscale is not None else pw.w_kn
    m, k = x2.shape
    n = w.shape[-1]
    xp = pad_to_blocks(x2, pw.bm, pw.bk)
    wp = pad_to_blocks(w, pw.bk, pw.bn)
    tm, tk = xp.shape[0] // pw.bm, xp.shape[1] // pw.bk
    if tk != pw.tk:
        raise ValueError(
            f"{pw.site}: plan compiled for tk={pw.tk} K-blocks of {pw.bk}, "
            f"operand K={k} gives {tk} — rebuild the plan for these shapes")
    if pw.mode == "two_sided":
        a_bitmap = sparsity_lib.block_bitmap_jnp(xp, pw.bm, pw.bk)
        meta = sparsity_lib.combine_with_activation_meta(
            a_bitmap, pw.wkidx, pw.wkcnt, pw.b_bitmap)
    else:
        meta = sparsity_lib.weight_plan_meta(pw.wkidx, pw.wkcnt,
                                             pw.b_bitmap, tm)
    scale = None
    if pw.qscale is not None:
        pad_n = wp.shape[1] - n
        scale = (jnp.pad(pw.qscale, (0, pad_n)) if pad_n
                 else pw.qscale).astype(jnp.float32)
    return _run_block_sparse(xp, wp, meta, cfg, m, n, scale=scale)


def flex_matmul(x: jax.Array, w: jax.Array, *, site: str = "",
                precision=None) -> jax.Array:
    """x (..., K) @ w (K, N) through the schedule-flexible matmul.

    Dispatch order (descriptor → ops → kernel):
      1. ``w`` is a ``PlannedWeight`` (precompiled weight-sparsity plan) →
         block-sparse path with the plan's static per-site ``max_nnz``; no
         weight-side bitmap/argsort ops are traced,
      2. site descriptor says ``weight``/``two_sided`` → block-sparse path
         with trace-time metadata (Pallas kernel or masked-XLA oracle; see
         module docstring),
      3. Pallas enabled → ``kernels.flex_matmul`` with the site's
         (stationarity, block shapes),
      4. otherwise dot_general (tiling delegated to XLA; sharding-level
         schedule still applies upstream).
    """
    cfg = _cfg()
    if isinstance(w, PlannedWeight):
        if cfg.sparse_dispatch and w.w.ndim == 2 and x.ndim >= 2:
            x2, lead = _leading_flat(x)
            if w.mode == "two_sided":
                _record_act_stats(w.site or site, x2)
            out = _planned_matmul(x2, w, cfg)
            return out.reshape(*lead, out.shape[-1]).astype(x.dtype)
        w = w.w_kn                     # plan disabled → dense fallback
    desc = _site_descriptor(site, cfg) if cfg.sparse_dispatch else None
    if isinstance(w, QuantizedLinear):
        # unplanned quantized leaf (e.g. plan-less bring-up, or a site the
        # plan skipped): dense-Pallas 2-D sites run the fused int8 kernel;
        # everything else dequantizes at trace time (XLA fuses the cast)
        # and falls through to the ordinary dispatch below
        if (cfg.use_pallas and w.q.ndim == 2 and x.ndim >= 2
                and (desc is None or desc.sparsity_mode == "dense")):
            from repro.kernels.int8_matmul import int8_matmul
            x2, lead = _leading_flat(x)
            out = int8_matmul(x2, w, interpret=cfg.interpret,
                              out_dtype=jnp.float32)
            return out.reshape(*lead, out.shape[-1]).astype(x.dtype)
        w = (w.q.astype(jnp.float32) * w.scale[..., None, :]).astype(x.dtype)
    sparse = (desc is not None and w.ndim == 2
              and desc.sparsity_mode in ("weight", "two_sided"))
    if (sparse or cfg.use_pallas) and x.ndim >= 2:
        x2, lead = _leading_flat(x)
        if sparse:
            if desc.sparsity_mode == "two_sided":
                _record_act_stats(site, x2)
            out = _sparse_site_matmul(x2, w, desc.sparsity_mode,
                                      desc.schedule, cfg, site)
        else:
            from repro.kernels import flex_matmul as fm
            out = fm.flex_matmul(x2, w, schedule=site_schedule(site),
                                 interpret=cfg.interpret)
        return out.reshape(*lead, w.shape[-1]).astype(x.dtype)
    return jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        precision=precision, preferred_element_type=jnp.float32,
    ).astype(x.dtype)


def head_matmul(x: jax.Array, head, *, site: str = "lm_head",
                precision=None) -> jax.Array:
    """x (..., D) @ head (V, D)ᵀ → (..., V) — the einsum-based logits path
    routed through the same per-site dispatch as every other matmul.

    ``head`` is either the raw embedding-shaped (V, D) matrix (tied or
    unplanned configs — the transpose happens at trace time and fuses into
    the dot), a ``PlannedWeight`` compiled in the transposed (D, V)
    orientation by ``core.sparsity.compile_weight_plan``, or a
    ``QuantizedLinear`` — which ``quant.quantize_params`` already stores
    contraction-oriented (q (D, V), per-vocab-row scales), so no swap.
    """
    if isinstance(head, (PlannedWeight, QuantizedLinear)):
        return flex_matmul(x, head, site=site, precision=precision)
    return flex_matmul(x, jnp.swapaxes(head, -1, -2), site=site,
                       precision=precision)


def _map_experts(fn, x: jax.Array, w, cfg: ExecConfig) -> jax.Array:
    """Apply a per-expert (C, K) × (K, N) function over the leading E axis.

    XLA path: ``jax.vmap`` (the metadata builders and the masked oracle are
    all pure jnp).  Pallas path: the scalar-prefetch ``pallas_call`` has no
    batching rule, so the static expert axis is unrolled — one kernel
    launch per expert.  ``w`` may be a raw (E, K, N) array or a
    ``PlannedWeight`` whose leaves carry the leading E axis (``tree_map``
    slices both the same way).
    """
    if cfg.use_pallas:
        slices = [fn(x[e], jax.tree_util.tree_map(lambda a: a[e], w))
                  for e in range(x.shape[0])]
        return jnp.stack(slices)
    return jax.vmap(fn)(x, w)


def flex_expert_matmul(x: jax.Array, w, *, site: str = "") -> jax.Array:
    """Batched-expert contraction x (E, C, K) @ w (E, K, N) → (E, C, N).

    The MoE expert-FFN einsums routed through the same per-site planned
    dispatch as the 2-D sites (the ``moe.experts_*`` descriptor entries):
    per-expert precompiled metadata when ``w`` is a ``PlannedWeight`` with
    a leading E axis (the plan's tight site-wide ``max_nnz`` shrinks every
    expert's kernel grid), trace-time per-expert bitmaps otherwise.  Dense
    sites run the schedule-flexible Pallas matmul per expert when Pallas is
    on; on the XLA path they fall back to the batched einsum, bit-identical
    to the pre-dispatch path.

    NOTE on popcounts: in the capacity-bounded forward ``x`` is the
    capacity-padded dispatch buffer, so the recorded two-sided activation
    density folds routing occupancy (invalid capacity slots are zero rows)
    into activation sparsity (the serving layer feeds every row to every
    held expert, so there it does not).  That is the
    density the expert matmul *actually executes under* — those rows really
    are skipped — but it moves with load; like the engine's idle-slot
    caveat, calibrate (and set ``maybe_recalibrate`` thresholds) from a
    representative traffic mix.
    """
    cfg = _cfg()
    if isinstance(w, PlannedWeight):
        if (cfg.sparse_dispatch and w.w.ndim == 3 and x.ndim == 3
                and x.shape[0] == w.w.shape[0]):
            if w.mode == "two_sided":
                _record_act_stats(w.site or site, x)
            out = _map_experts(lambda xe, pwe: _planned_matmul(xe, pwe, cfg),
                               x, w, cfg)
            return out.astype(x.dtype)
        w = w.w_kn                     # plan disabled → dense fallback
    if isinstance(w, QuantizedLinear):
        # unplanned quantized expert stack: dequantize at trace time (the
        # per-expert scale axis broadcasts against the last dim) and fall
        # through — the scalar-prefetch kernel has no batched int8 variant
        w = (w.q.astype(jnp.float32) * w.scale[..., None, :]).astype(x.dtype)
    desc = _site_descriptor(site, cfg) if cfg.sparse_dispatch else None
    sparse = (desc is not None and w.ndim == 3 and x.ndim == 3
              and x.shape[0] == w.shape[0]
              and desc.sparsity_mode in ("weight", "two_sided"))
    if sparse:
        if desc.sparsity_mode == "two_sided":
            _record_act_stats(site, x)
        out = _map_experts(
            lambda xe, we: _sparse_site_matmul(xe, we, desc.sparsity_mode,
                                               desc.schedule, cfg, site),
            x, w, cfg)
        return out.astype(x.dtype)
    if (cfg.use_pallas and w.ndim == 3 and x.ndim == 3
            and x.shape[0] == w.shape[0]):
        # dense site on the Pallas path: the schedule-flexible kernel per
        # expert (same dataflow dispatch as the 2-D dense sites)
        from repro.kernels import flex_matmul as fm
        sched = site_schedule(site)
        slices = [fm.flex_matmul(x[e], w[e], schedule=sched,
                                 interpret=cfg.interpret)
                  for e in range(x.shape[0])]
        return jnp.stack(slices).astype(x.dtype)
    return jnp.einsum("eck,ekn->ecn", x, w)


def block_sparse_matmul(x: jax.Array, w: jax.Array, meta, *,
                        site: str = "") -> jax.Array:
    """Two-sided block-sparse matmul with *precomputed* metadata.  ``meta``
    is a ``core.sparsity.BlockSparseMeta``; None falls back to the
    descriptor-driven ``flex_matmul`` dispatch."""
    cfg = _cfg()
    if meta is None:
        return flex_matmul(x, w, site=site)
    from repro.kernels import block_sparse as bs
    if cfg.use_pallas:
        return bs.block_sparse_matmul(x, w, meta, interpret=cfg.interpret)
    return bs.block_sparse_matmul_ref(x, w, meta)
