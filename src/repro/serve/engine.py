"""Batched serving engine: fused-loop continuous batching.

Serving path of the framework (the assigned ``decode_*`` cells lower
``serve_step``).  Slot-based continuous batching: a fixed decode batch of
``n_slots`` sequences; finished sequences free their slot and queued
requests are prefilled into it.

**Fused hot loop** (the data-movement view of serving, per FlexNN's
movement-over-compute premise): the per-token host round-trip — one jitted
dispatch, one logits sync, one host argmax per token — is the serving
analogue of wasted operand movement, so the engine runs on-device
executables whose host cost is O(1) per *batch of tokens*:

  * ``models.model.decode_many`` — a ``lax.scan`` over T decode steps with
    on-device token selection (greedy argmax, or temperature/top-k
    sampling keyed by (seed, position) when a live request carries
    ``SamplingParams``) feeding the next token; only the (T, n_slots)
    token block returns to the host.  Positions are per-slot vectors and
    live slots carry a mask; a per-slot ``rem`` budget and optional
    ``eos_id`` stop each row *inside* the scan — an inactive row stops
    writing cache and emits a -1 sentinel, so one short request no longer
    shrinks everyone's block (``_block_len`` sizes blocks by the *max*
    remaining budget and ``_append_block`` truncates each column at its
    sentinel).
  * ``models.model.prefill_into_slot`` — admitted prompts feed one slot
    through jitted scans with slot masking (one dispatch per *segment*,
    not per prompt token), uniform across dense / MoE / SSM / hybrid state
    families; the admitted row is zero-reset on the first segment so no
    recurrent state leaks from the slot's previous occupant.  With
    ``prefill_chunk`` set, long prompts feed in fixed-size chunks
    interleaved one-per-iteration with decode blocks (``_Slot`` tracks a
    ``prefill_cursor``; a mid-prefill slot rides decode dispatches as a
    masked filler row), so admission never stalls live decodes.  Segments
    are padded to power-of-two lengths so the trace count stays
    O(log max_seq).
  * **Donated decode state** — the fused executables take the decode state
    with ``donate_argnums``, so the KV / recurrent caches mutate in place
    instead of being copied every block.  The *params* (including attached
    ``PlannedWeight`` plan arrays) are deliberately **not** donated: they
    are inputs to every subsequent call, never outputs, so donating them
    would consume live buffers for zero aliasing benefit.

**Double-buffered dispatch**: the host would sit on the critical path if
each (T, n_slots) token block were synced (and its EOS/truncation
accounting run) before the next block was dispatched — the device would
idle for the whole host-side bookkeeping window.  Instead block k+1 is
dispatched from the device-resident (token, pos, rem) carries *before*
block k's token array is synced: host accounting for block k then
overlaps device compute for block k+1.  Host-side truncation/EOS
accounting and occupancy updates are deferred by exactly one block.  A
block is only speculated while the live set is unchanged (keyed by (slot,
uid) pairs, so a recycled slot can never inherit a stale carry); when
block k's accounting reveals an occupancy change — a request finished, a
prefill completed — the tick returns block k's tokens and the next tick
accounts the speculative block (its tokens are still oracle-exact: rows
that stopped emit the ``-1`` sentinel and never touch state) and
relaunches from host state.  Two gates keep the deferral off the latency
paths: a block carrying some request's *first* token is synced in its own
tick (first-token urgency — TTFT never pays the one-block deferral), and
speculation is skipped while a request could join the live set this tick
(``_joinable``: a slot mid-prefill, or a queued request with a free
slot), so late joiners board the very next launch.  The serving tick
(``decode_block_step``) is the one loop: ``run_until_drained`` is ticks
until the engine is drained.  ``flush()`` syncs any in-flight block on
demand; the per-token ``step()``, ``warmup()`` and
``maybe_recalibrate()`` flush implicitly.

**Admission policy** (``AdmissionPolicy``): which queued request a freed
slot takes, and how large a prefill chunk each tick feeds, are policy — not
hard-coded FIFO + constant.  ``FIFOAdmission`` is the baseline (queue
order, constructor ``prefill_chunk``); ``AdaptiveAdmission`` scales the
chunk with live-decode occupancy (large chunks while slots idle, small
chunks while decode is hot, power-of-two so the trace count stays bounded)
and switches to shortest-prompt-first when the queue depth crosses its
burst threshold.  Policies only reorder *scheduling*; per-request token
streams are schedule-invariant (masked state commits keep slots
independent), so every policy stays token-for-token equal to the oracle.

The per-token ``step()`` API is kept as the reference oracle: it runs the
same per-slot-position ``decode_step`` one token at a time, and the fused
block is computation-identical to T oracle steps (test-enforced
token-for-token across dense, planned-sparse MoE and tied-head families).
``run_until_drained`` drives the fused loop (``fused=False`` runs the
oracle loop instead), picking each block length as the max live-slot
remaining budget clamped to ``decode_block``; per-slot device budgets stop
each row at its own limit so no slot overshoots its request.

Sparsity/dataflow wiring: an optional ``ExecConfig`` (see ``kernels.ops``)
is installed around every decode trace, so the engine's matmul sites consult
their ``SiteDescriptor`` — per-site stationarity and ``weight``/``two_sided``
block-sparse dispatch run inside the jitted executables (the attached
``WeightSparsityPlan`` arrays ride through ``lax.scan`` + donation as
ordinary jit inputs).  ``decode_exec_config`` compiles the decode-shape
``NetworkSchedule`` for an arch; given ``params`` it also compiles the
``WeightSparsityPlan`` at bring-up.  Runtime activation-bitmap popcounts
accumulate per site across every scanned step (``activation_densities``),
and ``maybe_recalibrate`` closes the loop: on density drift past the
threshold the engine recompiles the descriptor table + plan in place and
rebuilds all three jitted executables; decode state and in-flight requests
carry over.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import time
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ArchConfig, ShapeConfig
from repro.kernels import ops
from repro.models import model as model_lib, transformer


def decode_exec_config(cfg: ArchConfig, n_slots: int, *,
                       model_shards: int = 1,
                       use_pallas: bool = False,
                       interpret: bool = False,
                       params=None,
                       collect_stats: bool = False,
                       act_densities: Optional[Dict[str, float]] = None,
                       wt_densities: Optional[Dict[str, float]] = None,
                       quantize: bool = False,
                       ) -> ops.ExecConfig:
    """ExecConfig carrying the decode-shape descriptor table for ``cfg``.

    The schedule compiler sees M = n_slots (one new token per live slot);
    sparsity modes/densities flow from ``cfg.sparsity`` via
    ``compile_network_schedule``.

    With ``params``, a ``WeightSparsityPlan`` is compiled at bring-up: the
    descriptor table is first built under the density priors, a cheap
    nonzero-count pass measures each site's actual weight density, the
    schedule is re-selected under the measured densities, and the plan is
    compiled once at the final block granularity.  ``act_densities`` feeds
    measured runtime activation densities
    (``ServeEngine.activation_densities``) back into the selector;
    ``collect_stats`` makes the engine accumulate those popcounts.
    ``wt_densities`` seeds the selector with already-measured weight
    densities (e.g. an existing plan's ``wt_densities()``) when ``params``
    is not re-walked — a recalibration that knows the weights didn't
    change.

    ``quantize`` int8-quantizes the matmul weights before planning
    (``quant.quantize_params`` — deterministic, so the engine quantizing
    the same params gets a bitwise-identical tree): schedules are costed at
    1-byte weights, the plan compiles on the dequantized values
    (quantization is zero-preserving → identical bitmaps) and carries the
    int8 payloads + per-output-channel scales for fused dispatch.
    """
    from repro.core.descriptors import (compile_network_schedule,
                                        sparsity_mode_for)
    from repro.core.sparsity import (compile_weight_plan,
                                     measure_weight_densities)
    shape = ShapeConfig(name="serve_decode", kind="decode", seq_len=1,
                        global_batch=n_slots)
    ns = compile_network_schedule(cfg, shape, model_shards=model_shards,
                                  act_densities=act_densities,
                                  wt_densities=wt_densities,
                                  quantize=quantize)
    if quantize and params is not None:
        from repro.quant.quantize import quantize_params
        params, _ = quantize_params(params,
                                    tie_embeddings=cfg.tie_embeddings)
    plan = None
    if params is not None and sparsity_mode_for(cfg) != "dense":
        measured = measure_weight_densities(params, ns)
        if measured:
            ns = compile_network_schedule(
                cfg, shape, model_shards=model_shards,
                wt_densities=measured, act_densities=act_densities,
                quantize=quantize)
            plan = compile_weight_plan(
                params, ns, ref_elem_bytes=2 if quantize else None)
    return ops.ExecConfig(use_pallas=use_pallas, interpret=interpret,
                          schedules=ns, plan=plan,
                          collect_stats=collect_stats,
                          act_densities=(dict(act_densities)
                                         if act_densities else None),
                          arch_cfg=cfg, model_shards=model_shards,
                          quantize=quantize)


def activation_density_drift(baseline: Optional[Dict[str, float]],
                             measured: Dict[str, float], *,
                             prior: float = 0.5) -> float:
    """Max |measured − selected-under| activation density over sites.

    ``baseline`` holds the densities the current schedule was selected
    under (``ExecConfig.act_densities``); sites absent from it were
    selected under the scheduler's 0.5 activation ``prior``.  The pure
    trigger-side of the auto-recalibration policy — unit-testable without
    a recompile.
    """
    drift = 0.0
    for site, m in (measured or {}).items():
        drift = max(drift, abs(m - (baseline or {}).get(site, prior)))
    return drift


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


@dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling policy.

    ``temperature`` 0 (the default) is greedy argmax — the fused-vs-oracle
    token-for-token guarantees live on this path.  ``temperature > 0``
    samples from the temperature-scaled distribution, truncated to the
    ``top_k`` highest logits when ``top_k > 0``.  Randomness is
    position-keyed — row r at position p draws from
    ``fold_in(PRNGKey(seed), p)`` — so a sampled stream is reproducible
    from ``seed`` alone and invariant to how the engine blocks its decode
    steps (fused blocks sample exactly what per-token oracle steps would).
    """
    temperature: float = 0.0
    top_k: int = 0
    seed: int = 0


# Terminal ``Request.status`` values.  A request ends in exactly one:
#   done            — EOS / budget / sequence-wall completion
#   cancelled       — ServeEngine.cancel(uid)
#   deadline_missed — submit(deadline=...) budget expired before completion
#   failed          — on-device NaN/Inf quarantine (-2 sentinel)
#   shed            — bounded-queue overload eviction / rejection
TERMINAL_STATES = ("done", "cancelled", "deadline_missed", "failed", "shed")
# lifecycle stamps of a request, in the order it reaches them
REQUEST_STAMPS = ("submitted", "admitted", "prefilled", "first_token",
                  "finished")


@dataclass
class Request:
    uid: int
    prompt: np.ndarray            # (S,) int32
    max_new: int = 16
    sampling: Optional[SamplingParams] = None   # None = greedy
    # plan-tier routing: 0 = full-quality tier; higher classes may decode
    # under more aggressively pruned plan tiers (clamped to the engine's
    # tier count).  Scheduling-only for class 0; relaxed classes trade
    # accuracy for latency by construction.
    latency_class: int = 0
    # admission ordering class for PriorityAdmission (lower = sooner);
    # schedule-only — never changes any stream
    priority: int = 0
    # absolute deadline on the engine clock (None = no deadline); set by
    # ``submit(deadline=...)`` relative to the engine's ``clock()``
    deadline: Optional[float] = None
    out: List[int] = field(default_factory=list)
    done: bool = False
    # lifecycle: queued -> prefill -> decode -> one of TERMINAL_STATES.
    # ``done`` stays the boolean "is terminal" fast path (it is True for
    # every terminal status, not only "done").
    status: str = "queued"
    # deadline-pressure tier demotions applied (latency_class increments)
    demotions: int = 0
    # lifecycle stamps on the engine clock (None until reached); see
    # ``ServeEngine.request_times``
    submitted: Optional[float] = None
    admitted: Optional[float] = None      # given a slot
    prefilled: Optional[float] = None     # last prefill segment dispatched
    first_token: Optional[float] = None   # first token credited
    finished: Optional[float] = None      # reached a terminal status

    def times(self) -> Dict[str, Optional[float]]:
        return {k: getattr(self, k) for k in REQUEST_STAMPS}


@dataclass
class _Slot:
    req: Optional[Request] = None
    pos: int = 0                  # next position to write
    prefill_cursor: int = 0       # prompt-feed tokens already prefilled


@dataclass
class _InflightBlock:
    """A dispatched-but-unsynced ``decode_many`` block.

    ``key`` is the live-set identity at dispatch time — ``(slot, uid)``
    pairs, so a slot recycled to a new request can never be mistaken for
    the one the block was dispatched for.  ``block`` is the (T, n_slots)
    device token array; syncing it is the deferred host cost.
    """
    key: tuple
    live: List[int]
    t_block: int
    block: jax.Array
    # >0 marks a speculative verify block: ``spec_k`` draft proposals were
    # scored under the full (verify-tier) plan, so ``block`` is up to
    # (spec_k + 1) rows of verify-tier tokens with -1 sentinels after the
    # first rejected draft.  Used only for acceptance accounting —
    # credit / drain / finish logic is identical to decode blocks.
    spec_k: int = 0


class AdmissionPolicy:
    """Pluggable admission: queue ordering + prefill chunk sizing.

    The engine consults the policy at two points:

    * ``pick(queue, engine)`` — which queued request the next freed slot
      takes (an index into ``queue``).  The base policy is FIFO (index 0).
    * ``chunk(engine)`` — the prefill chunk size for the next feed, or
      ``None`` for whole-prompt prefill (the stall baseline).  The base
      policy returns the engine's constructor ``prefill_chunk``.

    ``chunk_cap(engine)`` bounds every value ``chunk`` may return so
    ``ServeEngine.warmup`` can precompile all dispatchable prefill shapes.
    Policies must treat the engine as **read-only** scheduling state
    (queue, slots, occupancy); they reorder work, they never change what
    any request's token stream is — streams are schedule-invariant.
    """

    def pick(self, queue: Deque[Request], engine: "ServeEngine") -> int:
        return 0

    def chunk(self, engine: "ServeEngine") -> Optional[int]:
        return engine.prefill_chunk

    def chunk_cap(self, engine: "ServeEngine") -> Optional[int]:
        """Largest chunk ``chunk`` may ever return (None = unbounded, the
        whole-prompt path — warmup then compiles up to ``max_seq``)."""
        return engine.prefill_chunk

    def shed(self, queue: Deque[Request], engine: "ServeEngine",
             incoming: Request) -> Optional[int]:
        """Overload valve, consulted only when the engine's bounded queue
        (``max_queue``) is full at submit time: return the index of a
        queued request to evict in favour of ``incoming``, or ``None`` to
        reject ``incoming`` itself.  The base policy is **reject-new**:
        admitted work is never evicted, the late arrival is shed.  Either
        victim ends terminal ``status == "shed"`` (and counts in
        ``engine.counters["shed"]``); shedding never touches requests that
        already hold a slot."""
        return None


def _lowest_priority_victim(queue: Deque[Request],
                            incoming: Request) -> Optional[int]:
    """Shared shed rule: evict the numerically highest-priority (least
    important) queued request, newest within a class, but only when the
    incoming request strictly outranks it — otherwise reject the
    incoming one (equal classes keep admitted work, matching the
    reject-new baseline)."""
    if not queue:
        return None
    worst = max(range(len(queue)), key=lambda i: (queue[i].priority, i))
    return worst if incoming.priority < queue[worst].priority else None


class ShedLowestPriority(AdmissionPolicy):
    """FIFO admission + shed-lowest-priority overload policy.

    When the bounded queue is full, an incoming request evicts the least
    important queued request (highest ``Request.priority`` number, newest
    within the class) if it strictly outranks it; otherwise the incoming
    request is rejected like the base policy.  The admission order itself
    stays FIFO — pair with ``PriorityAdmission`` (which inherits the same
    shed rule) to also reorder admission by class."""

    def shed(self, queue: Deque[Request], engine: "ServeEngine",
             incoming: Request) -> Optional[int]:
        return _lowest_priority_victim(queue, incoming)


class FIFOAdmission(AdmissionPolicy):
    """The explicit baseline: strict queue order, fixed constructor chunk.

    This is the engine's default policy, named so benchmarks and tests can
    select it against ``AdaptiveAdmission`` without relying on defaults.
    """


@dataclass(frozen=True)
class AdaptiveAdmission(AdmissionPolicy):
    """Occupancy-adaptive chunking + shortest-prompt-first under burst.

    *Chunk sizing*: the prefill chunk scales with **live-decode occupancy**
    (slots actively decoding / ``n_slots``).  Idle engine → ``max_chunk``
    (admit long prompts in as few ticks as possible — nobody is waiting on
    the device); fully hot engine → ``min_chunk`` (keep decode blocks
    flowing, amortize admission over many ticks).  Interpolation is
    geometric and the result is always a power of two, so the set of
    compiled prefill shapes stays O(log max_chunk/min_chunk).

    *Queue ordering*: while the queue depth is ≤ ``burst_depth`` admission
    is FIFO; past it (a burst), the next freed slot takes the
    shortest-prompt request — short requests stop inheriting the head-of-
    line blocking of long prompts, which is exactly the p99 TTFT the
    loadgen harness measures.

    Both knobs reorder scheduling only: per-request token streams are
    unchanged (test-enforced against the FIFO engine and the oracle).
    """
    min_chunk: int = 32
    max_chunk: int = 256
    burst_depth: int = 4

    def __post_init__(self):
        for name in ("min_chunk", "max_chunk"):
            v = getattr(self, name)
            if v < 1 or (v & (v - 1)) != 0:
                raise ValueError(f"{name} must be a power of two >= 1, "
                                 f"got {v}")
        if self.min_chunk > self.max_chunk:
            raise ValueError(
                f"min_chunk={self.min_chunk} > max_chunk={self.max_chunk}")

    def pick(self, queue: Deque[Request], engine: "ServeEngine") -> int:
        if len(queue) > self.burst_depth:
            return min(range(len(queue)),
                       key=lambda i: len(queue[i].prompt))
        return 0

    def chunk(self, engine: "ServeEngine") -> Optional[int]:
        occ = len(engine._live()) / max(engine.n_slots, 1)
        span = (self.max_chunk // self.min_chunk).bit_length() - 1
        return max(self.min_chunk, self.max_chunk >> round(occ * span))

    def chunk_cap(self, engine: "ServeEngine") -> Optional[int]:
        return self.max_chunk


@dataclass(frozen=True)
class PriorityAdmission(AdmissionPolicy):
    """Strict priority-class admission: lower ``Request.priority`` first,
    FIFO within a class.

    A freed slot always takes the oldest request of the numerically lowest
    priority class in the queue, so latency-sensitive requests stop
    inheriting head-of-line blocking from bulk work without any change to
    what is computed.  Pure queue reordering on the ``AdmissionPolicy``
    surface: chunk sizing is inherited from the base policy and per-request
    token streams are schedule-invariant (test-enforced against
    ``FIFOAdmission``).  Starvation of high-numbered classes under a
    sustained low-class stream is accepted by design — callers who need
    fairness should age priorities at submit time.
    """

    def pick(self, queue: Deque[Request], engine: "ServeEngine") -> int:
        return min(range(len(queue)),
                   key=lambda i: (queue[i].priority, i))

    def shed(self, queue: Deque[Request], engine: "ServeEngine",
             incoming: Request) -> Optional[int]:
        # priority admission sheds by the same ordering it admits by:
        # under overload the least important queued request makes room
        # for a strictly more important arrival (see ShedLowestPriority)
        return _lowest_priority_victim(queue, incoming)


class ServeEngine:
    """Continuous-batching engine over the fused on-device executables.

    ``fused`` selects the block-decode loop in ``run_until_drained``
    (False = the per-token oracle loop the tests compare against);
    ``decode_block`` caps the fused block length T (host work is O(1) per
    block).

    The fused loop is double-buffered: block k+1 is dispatched from the
    device-resident (token, pos, rem) carries *before* block k's token
    array is synced, so block k's host accounting overlaps block k+1's
    device compute.  A block may be left in flight between
    ``decode_block_step`` calls; its tokens are credited on the next call
    (or by ``flush()``).

    ``admission`` plugs the admission policy (queue ordering + prefill
    chunk sizing); the default ``FIFOAdmission`` reproduces the classic
    behaviour: strict queue order with the constructor ``prefill_chunk``
    (``None`` = whole-prompt prefill, the stall baseline).  See
    ``AdaptiveAdmission`` for occupancy-adaptive chunking and
    shortest-prompt-first admission under burst.
    """

    def __init__(self, cfg: ArchConfig, params, *, n_slots: int = 4,
                 max_seq: int = 256, dtype=jnp.float32,
                 exec_cfg: Optional[ops.ExecConfig] = None,
                 verify_plan: bool = True, fused: bool = True,
                 decode_block: int = 16,
                 eos_id: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 admission: Optional[AdmissionPolicy] = None,
                 quantize: bool = False,
                 plan_tiers: Optional[Sequence[float]] = None,
                 speculate_k: int = 0,
                 max_queue: Optional[int] = None,
                 clock: Optional[Callable[[], float]] = None,
                 nan_guard: bool = True,
                 deadline_demotion: bool = True,
                 demote_margin: float = 1.0):
        self.cfg, self.params = cfg, params
        self.n_slots, self.max_seq = n_slots, max_seq
        self.exec_cfg = exec_cfg
        self.fused = fused
        self.decode_block = decode_block
        # on-device stop token: a slot emitting eos_id goes inactive inside
        # the scanned block (None disables — budgets alone size requests)
        self.eos_id = eos_id
        # chunked prefill: feed admitted prompts in fixed-size chunks
        # interleaved with decode blocks, so a long prompt never stalls
        # live decodes (None = whole-prompt prefill in one call)
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, "
                             f"got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        self._prefill_rr = 0          # round-robin over mid-prefill slots
        if admission is not None and not isinstance(admission,
                                                    AdmissionPolicy):
            raise TypeError(f"admission must be an AdmissionPolicy, got "
                            f"{type(admission).__name__}")
        self.admission = admission if admission is not None \
            else FIFOAdmission()
        # async double-buffering state: dispatched-but-unsynced blocks
        # (oldest first; depth <= 2) and the device (token, pos, rem)
        # carries keyed by the (slot, uid) live set they were produced for
        self._inflight: List[_InflightBlock] = []
        self._carry: Optional[tuple] = None
        # ---- fault tolerance (ISSUE 10) ----
        # bounded queue: submit past max_queue consults admission.shed()
        # (None = unbounded, the pre-overload-aware behaviour)
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        self.max_queue = max_queue
        # injectable clock (deadlines, demotion pressure, fault tests use
        # a deterministic VirtualClock; production uses the monotonic one)
        self._clock = clock if clock is not None else time.monotonic
        # on-device NaN/Inf quarantine: decode_many / verify_block emit the
        # -2 sentinel for a row whose logits go non-finite; the host marks
        # that request ``failed`` and only that row stops
        self.nan_guard = bool(nan_guard)
        # deadline-pressure tier demotion: when a deadline can't be met at
        # the request's latency class and a cheaper plan tier exists,
        # demote instead of letting it expire (recorded per request and in
        # counters["demotions"])
        self.deadline_demotion = bool(deadline_demotion)
        self.demote_margin = float(demote_margin)
        # lifetime counters: one per terminal state, demotions, and the
        # work dispatched (admissions, prefill segments with their valid
        # tokens and padded device steps and how many ran as one forward
        # pass, fused decode / verify blocks with their scanned steps,
        # token-block syncs)
        self.counters = {s: 0 for s in TERMINAL_STATES}
        self.counters.update(demotions=0, admitted=0, prefill_segments=0,
                             prefill_tokens=0, prefill_steps=0,
                             prefill_forwards=0, decode_blocks=0,
                             decode_steps=0, syncs=0)
        # KV-only stacks prefill a segment in one forward pass, the rest
        # token by token (``model_lib.prefill_into_slot``)
        self._prefill_forward = transformer.kv_state_only(cfg)
        # MoE stacks: routing picks of the rows that commit, counted on the
        # device by every decode block and prefill segment
        # (``moe.decode_moe``): over all routed experts, and per held
        # expert.  A dispatch's counts are folded in once the device has
        # them (``_fold_moe_counts``), so health() never waits on it.
        self._moe_pending: List[jax.Array] = []
        if cfg.moe.enabled:
            self.counters.update(
                moe_assignments=0,
                moe_assignments_held=[0] * cfg.moe.experts_held)
        # terminal uid -> (status, credited output tokens, lifecycle
        # stamps), bounded so status(), results() and request_times()
        # outlive slot recycling without unbounded growth
        self._retired: "collections.OrderedDict[int, tuple]" = \
            collections.OrderedDict()
        # EMA of wall seconds per credited token — the demotion trigger's
        # service-rate estimate (None until two accounted blocks)
        self._tok_ema: Optional[float] = None
        self._last_account: Optional[float] = None
        self.state = model_lib.init_decode_state(cfg, n_slots, max_seq,
                                                 dtype=dtype)
        self.slots = [_Slot() for _ in range(n_slots)]
        self.queue: Deque[Request] = collections.deque()
        self._uid = 0
        self._mask_cache: Dict[tuple, jax.Array] = {}
        # int8 bring-up: quantize the matmul weights once here; the served
        # tree (``_serve_params``) carries QuantizedLinear leaves that the
        # plan attaches onto / the dispatch falls back on.  ``self.params``
        # keeps the original tree for plan rebuilds — quantize_params is
        # deterministic, so a rebuild re-quantizing it reproduces
        # ``_serve_params`` bitwise and attach verification stays valid.
        # An exec config built by decode_exec_config(quantize=True) implies
        # the knob even if the caller forgot it (the plan's payloads are
        # int8 — attaching them onto a bf16 tree would be incoherent).
        self.quantize = bool(quantize) or bool(getattr(exec_cfg, "quantize",
                                                       False))
        if self.quantize:
            from repro.quant.quantize import quantize_params
            self._serve_params, self.quant_stats = quantize_params(
                params, tie_embeddings=cfg.tie_embeddings)
        else:
            self._serve_params, self.quant_stats = params, None
        # weight-plan bring-up: attach precompiled CSB metadata into the
        # params pytree so the jitted step gets it as ordinary arrays.
        # verify_plan=False skips the coverage re-check (an extra
        # O(all-weights) host pass) when the plan was just compiled from
        # these exact params
        self.plan = getattr(exec_cfg, "plan", None)
        self._exec_params = (self.plan.attach(self._serve_params,
                                              verify=verify_plan)
                             if self.plan is not None
                             else self._serve_params)
        # elastic plan tiers: N pruned views of ONE weight set.  Tier 0 is
        # the engine's full plan (ratio 0.0, required); tier i > 0 prunes
        # the ratio-r weakest K-blocks per output tile out of the dispatch
        # metadata while sharing the payload/leaves — attach copies no
        # weights, so all tiers alias the same HBM-resident params.
        # ``Request.latency_class`` routes blocks to tiers; the *last*
        # (most aggressive) tier doubles as the self-speculation draft.
        if speculate_k < 0:
            raise ValueError(f"speculate_k must be >= 0, got {speculate_k}")
        self.speculate_k = int(speculate_k)
        self.tier_ratios = (tuple(float(r) for r in plan_tiers)
                            if plan_tiers is not None else (0.0,))
        if plan_tiers is not None:
            if self.plan is None or exec_cfg is None:
                raise ValueError(
                    "plan_tiers requires a planned engine (exec_cfg built "
                    "by decode_exec_config with params)")
            if not self.tier_ratios or self.tier_ratios[0] != 0.0:
                raise ValueError(
                    f"plan_tiers must start at ratio 0.0 (the full-quality "
                    f"tier every class-0 request decodes under), got "
                    f"{self.tier_ratios}")
            if any(b < a for a, b in zip(self.tier_ratios,
                                         self.tier_ratios[1:])):
                raise ValueError(
                    f"plan_tiers ratios must be non-decreasing, got "
                    f"{self.tier_ratios}")
        self._compile_tiers(verify=verify_plan)
        # speculative accounting: lifetime draft/accept counters
        self.spec_stats = {"drafted": 0, "accepted": 0, "emitted": 0,
                           "verify_blocks": 0}
        # speculative verify runs the whole k+1 window in ONE batched
        # forward only for families where that is bitwise-equal to k+1
        # sequential steps: plain dense-attention full-cache stacks.
        # Everything else (MoE, whose per-token expert layer the window
        # scorer lacks; recurrent state; sliding windows) has no exact-and-
        # cheaper parallel scorer, so ``_spec_k_for`` gates speculation
        # OFF for those families and they serve plain decode blocks —
        # ``speculate_k`` is then a no-op, not an approximation.
        # two_sided configs are gated for a substrate reason: the
        # activation-bitmap masked dot fuses differently at the window's
        # (B·W) row count than at decode's B rows on XLA:CPU, drifting the
        # scores by last-ulp f32 — enough to flip near-tied argmaxes, so
        # windowed verify cannot promise the sequential stream there
        # (dense and weight-planned dispatch measure bitwise-stable).
        self._spec_windowed = not (cfg.moe.enabled or cfg.ssm.enabled
                                   or cfg.rglru.enabled
                                   or cfg.encoder_decoder
                                   or cfg.window
                                   or cfg.sparsity.activation_threshold > 0)
        self._stats = (ops.SparsityStatsCollector()
                       if exec_cfg is not None and exec_cfg.collect_stats
                       else None)
        self._build_executables()

    def _compile_tiers(self, *, verify: bool = False):
        """(Re)compile the pruned plan tiers from ``tier_ratios`` and attach
        each onto the served params.  Tier 0 reuses ``self.plan`` /
        ``self._exec_params`` verbatim (ratio 0.0 compiles to a bitwise-
        identical plan — test-enforced — so the rebuild is skipped); every
        other tier compiles its own dispatch metadata over the SAME weight
        tree, sharing payload and leaves.  Called at bring-up and from
        ``maybe_recalibrate`` after a schedule swap."""
        if len(self.tier_ratios) <= 1 or self.plan is None:
            self.plan_tiers = [self.plan] if self.plan is not None else []
            self._tier_params = [self._exec_params]
            return
        from repro.core.sparsity import compile_weight_plan
        ref = 2 if self.quantize else None
        tiers = [self.plan]
        tier_params = [self._exec_params]
        for r in self.tier_ratios[1:]:
            p = compile_weight_plan(self._serve_params,
                                    self.exec_cfg.schedules,
                                    ref_elem_bytes=ref, prune_ratio=r)
            tiers.append(p)
            tier_params.append(p.attach(self._serve_params, verify=verify))
        self.plan_tiers = tiers
        self._tier_params = tier_params

    # ---- jitted executables ----
    def _scoped(self, fn):
        """Wrap a model function so the engine's exec config (descriptor
        table, plan, stats collector) is installed at trace time.  The
        wrapper keeps ``fn``'s name, which ``jax.jit`` turns into the
        executable's name in profiler traces (``jit_serve_prefill``)."""
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            if self.exec_cfg is None:
                return fn(*args, **kwargs)
            with contextlib.ExitStack() as scopes:
                scopes.enter_context(ops.exec_config(self.exec_cfg))
                if self._stats is not None:
                    scopes.enter_context(ops.sparsity_stats(self._stats))
                return fn(*args, **kwargs)
        return wrapped

    def _build_executables(self):
        """(Re)build the jitted entry points ``serve_decode``,
        ``serve_decode_many``, ``serve_prefill`` and ``serve_verify``
        (named ``jit_serve_*`` in profiler traces).  Called at bring-up
        and after ``maybe_recalibrate`` swaps the exec config — the new
        jits re-trace under the new descriptor table on their next call.

        The fused executables donate the decode-state argument (argnum 1,
        argnum 2 of the verify block): the KV / recurrent caches alias in
        place instead of being copied every block.  The per-token oracle
        stays undonated — it is the reference path, and keeping its inputs
        alive makes it safe to replay against held state copies in tests.
        """
        cfg = self.cfg
        eos_id = self.eos_id
        nan_guard = self.nan_guard

        def serve_decode(p, t, s, pos, live):
            # the oracle step masks state commits to live rows exactly like
            # the fused block does — done/mid-prefill rows stop writing
            # cache on both paths, and popcounts see live rows only
            return model_lib.masked_decode_step(p, cfg, t, s, pos, live)

        # both return the MoE routing counts last (None without experts)
        moe = cfg.moe.enabled

        def serve_decode_many(p, s, toks, pos, live, rem, temp, top_k,
                              seeds, n_steps):
            out = model_lib.decode_many(p, cfg, toks, s, pos, live, n_steps,
                                        rem=rem, eos_id=eos_id, temp=temp,
                                        top_k=top_k, seeds=seeds,
                                        nan_guard=nan_guard, moe_counts=moe)
            return out if moe else (*out, None)

        def serve_prefill(p, s, toks, valid, slot, slot_pos, start, reset):
            out = model_lib.prefill_into_slot(p, cfg, toks, valid, slot, s,
                                              slot_pos, start, reset,
                                              moe_counts=moe)
            return out if moe else (out, None)

        def serve_verify(p_full, p_draft, s, toks, pos, live, rem, temp,
                         top_k, seeds, k, windowed):
            # one fused speculative block: draft tier proposes k tokens,
            # the full (verify-tier) plan scores all k+1 positions, the
            # longest matching prefix is accepted and the draft's state is
            # discarded — return contract identical to decode_many with
            # T = k + 1 (−1 sentinels after the first rejection)
            return model_lib.verify_block(p_full, p_draft, cfg, toks, s,
                                          pos, live, k, rem=rem,
                                          eos_id=eos_id, temp=temp,
                                          top_k=top_k, seeds=seeds,
                                          windowed=windowed,
                                          nan_guard=nan_guard)

        self._decode = jax.jit(self._scoped(serve_decode))
        self._decode_many = jax.jit(self._scoped(serve_decode_many),
                                    static_argnums=(9,),
                                    donate_argnums=(1,))
        self._prefill = jax.jit(self._scoped(serve_prefill),
                                donate_argnums=(1,))
        self._verify = jax.jit(self._scoped(serve_verify),
                               static_argnums=(10, 11), donate_argnums=(2,))
        # stale-trace hygiene: the mask cache holds device arrays handed to
        # the retired executables — clear every per-engine cache alongside
        # the rebuild so nothing compiled against the old table survives
        # (the device carries likewise came out of the retired executables;
        # callers flush in-flight blocks before rebuilding)
        self._mask_cache.clear()
        self._carry = None

    def warmup(self):
        """Precompile every executable shape the serving loop can dispatch,
        so no compile stall lands inside live traffic: each power-of-two
        fused block length up to ``decode_block``, each power-of-two
        prefill segment length (up to ``prefill_chunk``, or ``max_seq``
        for whole-prompt prefill), and the per-token oracle step.  All
        dispatches run with every row masked inactive, so decode state is
        untouched (the donated calls re-thread it in place).  Prefill
        shapes are compiled up to the admission policy's ``chunk_cap``
        (``max_seq`` for the whole-prompt path).  Flushes any in-flight
        block first — warmup belongs off the serving clock."""
        self.flush()
        zero = np.zeros((self.n_slots,), np.int32)
        dead = np.zeros((self.n_slots,), bool)
        for tier_p in self._tier_params:
            t = 1
            while t <= self.decode_block:
                _, self.state, *_ = self._decode_many(
                    tier_p, self.state, zero, zero, dead, zero,
                    None, None, None, t)
                t *= 2
        self._decode(self._exec_params, zero[:, None], self.state, zero,
                     dead)
        if self.speculate_k and self._spec_windowed:
            # the greedy verify-block shape for every tier a block can
            # verify under (draft is baked into the same executable);
            # sampled verify compiles on first sampled dispatch
            for tier_p in self._tier_params[:-1] or self._tier_params:
                _, self.state, *_ = self._verify(
                    tier_p, self._tier_params[-1], self.state, zero, zero,
                    dead, zero, None, None, None, self.speculate_k,
                    self._spec_windowed)
        cap = _next_pow2(self.admission.chunk_cap(self) or self.max_seq)
        p = 1
        while p <= cap:
            self.state, _ = self._prefill(
                self._exec_params, self.state, np.zeros((p,), np.int32),
                np.zeros((p,), bool), np.int32(0), zero, np.int32(1),
                False)
            p *= 2
        jax.block_until_ready(self.state)

    # ---- density feedback ----
    def activation_densities(self) -> Dict[str, float]:
        """Measured per-site activation densities from runtime bitmap
        popcounts (requires ``ExecConfig.collect_stats``) — feed back into
        ``decode_exec_config(act_densities=...)`` to recalibrate the
        schedule selector's 0.5 prior.  Fused blocks emit one popcount per
        scanned step per site, so a T-step block accumulates the same
        window as T oracle steps.

        Popcount accumulation is masked to *active* rows (the mask
        ``masked_decode_step`` installs via ``ops.active_rows``): idle
        slots' token-0 filler rows and mid-prefill filler rows don't skew
        the measurement, so a 1-live-of-N engine measures the same density
        as a 1-slot engine."""
        if self._stats is None:
            return {}
        jax.effects_barrier()        # flush in-flight debug callbacks
        return self._stats.densities()

    def maybe_recalibrate(self, drift_threshold: float = 0.15, *,
                          recompile: bool = True
                          ) -> Optional[Dict[str, float]]:
        """Auto-recalibration policy (ROADMAP open item).

        When the measured per-site activation densities drift more than
        ``drift_threshold`` from the densities the current schedule was
        *selected under* (``ExecConfig.act_densities``; absent sites were
        selected under the 0.5 prior), recompile the descriptor table via
        ``decode_exec_config(act_densities=measured)`` and swap it into the
        engine — every jitted executable (per-token, fused block, prefill)
        is rebuilt and re-traces under the new table on its next call,
        decode state and in-flight requests carry over untouched.
        The weights didn't change, so the existing ``WeightSparsityPlan``
        (and the attached params) are *reused* whenever every planned
        site's block granularity survived the re-selection; only a site
        whose (bm, bn, bk) actually moved forces a full plan rebuild.

        Every probe with measurements consumes the popcount window, so
        drift is judged on traffic since the previous probe — a late shift
        is detected within one probe interval, not diluted by the lifetime
        average.

        Returns the measured densities when the drift tripped the
        threshold, else ``None``.  ``recompile=False`` answers only the
        trigger question (no schedule/plan rebuild) — the unit-testable
        half of the policy.

        Any async in-flight block is flushed first: its tokens are credited
        (and its popcounts land) before the window is judged, and the
        executable rebuild never strands an unsynced block.
        """
        if self.exec_cfg is None or self._stats is None:
            return None
        self.flush()
        measured = self.activation_densities()
        if not measured:
            return None
        # the ArchConfig the table was compiled from carries the sparsity
        # flags — the engine's own cfg may be the dense twin, and
        # recompiling from it would silently drop sparse dispatch.  Checked
        # *before* the window is consumed so the evidence survives the
        # error.
        if recompile and self.exec_cfg.arch_cfg is None:
            raise ValueError(
                "maybe_recalibrate(recompile=True) needs an ExecConfig "
                "built by decode_exec_config (arch_cfg is unset on this "
                "hand-built config) — pass recompile=False to only "
                "probe the trigger, or rebuild the config via "
                "decode_exec_config")
        # consume the window *in place* — the compiled step's callback
        # closed over this collector at trace time, so it must not be
        # swapped for a new object while that executable is live
        self._stats.reset()
        drift = activation_density_drift(self.exec_cfg.act_densities,
                                         measured)
        if drift <= drift_threshold:
            return None
        if recompile:
            old = self.exec_cfg
            new_ec = decode_exec_config(
                old.arch_cfg, self.n_slots,
                model_shards=old.model_shards,
                use_pallas=old.use_pallas, interpret=old.interpret,
                collect_stats=old.collect_stats,
                act_densities=measured, quantize=old.quantize,
                wt_densities=(self.plan.wt_densities()
                              if self.plan is not None and self.plan.entries
                              else None))
            plan_sites = ({e.site for e in self.plan.entries.values()}
                          if self.plan is not None else set())
            same_blocks = all(
                s in new_ec.schedules.sites and s in old.schedules.sites
                and (new_ec.schedules.sites[s].schedule.bm,
                     new_ec.schedules.sites[s].schedule.bn,
                     new_ec.schedules.sites[s].schedule.bk)
                == (old.schedules.sites[s].schedule.bm,
                    old.schedules.sites[s].schedule.bn,
                    old.schedules.sites[s].schedule.bk)
                for s in plan_sites)
            if self.plan is None or same_blocks:
                # same granularity everywhere → old plan + attached params
                # (and every pruned tier — tier metadata is tied to the
                # same block granularity) stay valid; skip the host-side
                # plan rebuild entirely
                self.exec_cfg = dataclasses.replace(new_ec, plan=self.plan)
            else:
                self.exec_cfg = decode_exec_config(
                    old.arch_cfg, self.n_slots,
                    model_shards=old.model_shards,
                    use_pallas=old.use_pallas, interpret=old.interpret,
                    params=self.params, collect_stats=old.collect_stats,
                    act_densities=measured, quantize=old.quantize)
                self.plan = self.exec_cfg.plan
                self._exec_params = (
                    self.plan.attach(self._serve_params, verify=False)
                    if self.plan is not None else self._serve_params)
                # a granularity move invalidates every tier's dispatch
                # metadata — rebuild ALL tiers from the new schedules so
                # draft/verify keep sharing the (unchanged) weight leaves
                self._compile_tiers()
            self._build_executables()
        return measured

    # ---- request management ----
    def _finish(self, req: Request, status: str = "done"):
        """Move a request to a terminal status — the ONLY place a request
        ends.  Idempotent (the first terminal status wins: a cancelled
        request can't be re-finished ``done`` by a late block sync), keeps
        the boolean ``done`` fast path in sync, bumps the lifetime counter
        and records the request in the bounded uid map ``status()``,
        ``results()`` and ``request_times()`` read after the slot is
        recycled."""
        if req.done:
            return
        req.status = status
        req.done = True
        req.finished = self._clock()
        self.counters[status] += 1
        self._retired[req.uid] = (status, req.out, req.times())
        while len(self._retired) > 4096:
            self._retired.popitem(last=False)

    def submit(self, prompt: np.ndarray, max_new: int = 16,
               sampling: Optional[SamplingParams] = None, *,
               latency_class: int = 0, priority: int = 0,
               deadline: Optional[float] = None) -> int:
        """Queue a request; returns its uid.

        ``latency_class`` routes the request's decode blocks to a plan
        tier: class 0 always decodes under the full plan; class c under
        tier min(c, n_tiers-1) — more aggressively pruned, faster, lower
        fidelity.  A mixed block decodes under the *least* aggressive live
        class so no request is served below its class.  ``priority`` is the
        ``PriorityAdmission`` ordering class (schedule-only).

        ``deadline`` is a completion budget in engine-clock seconds from
        now: a request not finished by then goes terminal
        ``deadline_missed`` (checked every tick, wherever the request is —
        queued, mid-prefill or mid-decode).  Under deadline pressure a
        tiered engine may first demote the request to a cheaper plan tier
        instead (see ``deadline_demotion``).

        With a bounded queue (``max_queue``) a submit that finds the queue
        full consults ``admission.shed(queue, engine, incoming)``: either
        a queued victim is evicted or the incoming request itself is
        rejected — the loser ends terminal ``"shed"`` (a rejected incoming
        request still gets a uid, so callers can observe
        ``status(uid) == "shed"``).

        Admission edge cases are rejected *here*, not deep in the decode
        loop: an empty prompt has no current token to decode from, and a
        prompt needing more cache positions than ``max_seq`` would make the
        prefill scatter write out-of-range positions that jit silently
        clamps — corrupted KV state instead of an error."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(
                f"prompt must be a non-empty 1-D token array, got shape "
                f"{prompt.shape}")
        if len(prompt) + 1 > self.max_seq:
            raise ValueError(
                f"prompt of {len(prompt)} tokens needs {len(prompt) + 1} "
                f"cache positions (prompt + first generated token) but "
                f"max_seq={self.max_seq}")
        if latency_class < 0:
            raise ValueError(
                f"latency_class must be >= 0, got {latency_class}")
        if deadline is not None and deadline <= 0:
            raise ValueError(f"deadline must be > 0 seconds, got {deadline}")
        self._uid += 1
        now = self._clock()
        req = Request(self._uid, prompt, max_new=max_new,
                      sampling=sampling,
                      latency_class=int(latency_class),
                      priority=int(priority),
                      deadline=(now + deadline
                                if deadline is not None else None),
                      submitted=now)
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            victim = self.admission.shed(self.queue, self, req)
            if victim is None:
                self._finish(req, "shed")
                return req.uid
            if not 0 <= victim < len(self.queue):
                raise ValueError(
                    f"shed() returned index {victim} for a queue of "
                    f"{len(self.queue)}")
            evicted = self.queue[victim]
            del self.queue[victim]
            self._finish(evicted, "shed")
        self.queue.append(req)
        return self._uid

    def cancel(self, uid: int) -> bool:
        """Cancel a request anywhere in its lifecycle; returns True when it
        was found non-terminal (queued, mid-prefill or mid-decode) and is
        now terminal ``cancelled``, False for unknown or already-terminal
        uids.

        Mid-decode cancellation rides the async machinery from PR 7 rather
        than going around it: marking the request terminal drops it out of
        ``_live()``, which invalidates the (slot, uid) carry key, so the
        next launch comes from host state, and any in-flight block synced
        after the cancel skips the row entirely (``_append_block`` never
        credits a terminal request) — a cancelled slot can't leak a
        speculative block's tokens into its successor.  No flush happens
        here: cancellation is O(queue) host work on the serving tick."""
        for idx, r in enumerate(self.queue):
            if r.uid == uid:
                del self.queue[idx]
                self._finish(r, "cancelled")
                return True
        for s in self.slots:
            if s.req is not None and s.req.uid == uid and not s.req.done:
                self._finish(s.req, "cancelled")
                return True
        return False

    def status(self, uid: int) -> Optional[str]:
        """Lifecycle status for a submitted uid — ``queued`` / ``prefill``
        / ``decode`` while live, one of ``TERMINAL_STATES`` after, or
        ``None`` for unknown (or very old, see the bounded terminal map)
        uids.  Snapshot semantics: under async dispatch a request may
        already be finished inside an unsynced block; ``flush()`` first for
        an exact answer."""
        for r in self.queue:
            if r.uid == uid:
                return r.status
        for s in self.slots:
            if s.req is not None and s.req.uid == uid:
                return s.req.status
        retired = self._retired.get(uid)
        return retired[0] if retired is not None else None

    def request_times(self, uid: int) -> Optional[Dict[str, Optional[float]]]:
        """Lifecycle stamps of a submitted uid on the engine clock:
        ``submitted``, ``admitted`` (given a slot), ``prefilled`` (last
        prefill segment dispatched), ``first_token`` (first token
        credited) and ``finished`` (terminal status reached), each None
        until reached; ``None`` for unknown uids.  Same retention and
        snapshot semantics as ``status()``."""
        for r in self.queue:
            if r.uid == uid:
                return r.times()
        for s in self.slots:
            if s.req is not None and s.req.uid == uid:
                return s.req.times()
        retired = self._retired.get(uid)
        return dict(retired[2]) if retired is not None else None

    def results(self) -> Dict[int, List[int]]:
        """Credited output tokens for every *terminal* request (any status:
        a cancelled/failed request reports the prefix it streamed before
        the fault).  Live requests are excluded — poll ``status()``.  Like
        ``status()``, bounded to the most recent 4096 terminals."""
        return {uid: out for uid, (_, out, _) in self._retired.items()}

    def _expire_deadlines(self) -> bool:
        """Terminal-mark every request whose deadline has passed on the
        engine clock — queued requests drop out of the queue, slot-bound
        ones (mid-prefill or mid-decode) free their slot exactly like a
        cancellation (same carry-invalidation + never-credit-terminal
        rules).  Returns True when anything expired.  Called at the top of
        every serving tick, so expiry is detected within one tick of the
        clock crossing the deadline."""
        now = self._clock()
        expired = False
        survivors = []
        for r in self.queue:
            if r.deadline is not None and r.deadline <= now:
                self._finish(r, "deadline_missed")
                expired = True
            else:
                survivors.append(r)
        if expired:
            self.queue = collections.deque(survivors)
        for s in self.slots:
            r = s.req
            if (r is not None and not r.done and r.deadline is not None
                    and r.deadline <= now):
                self._finish(r, "deadline_missed")
                expired = True
        return expired

    def _maybe_demote(self):
        """Deadline-pressure tier demotion: a live request whose remaining
        deadline budget can't cover its remaining tokens at the measured
        service rate (``_tok_ema`` seconds/token, scaled by
        ``demote_margin``) is demoted one latency class — routed to a
        cheaper pruned plan tier (PR 9) — instead of being left to expire.
        One class per tick per request, clamped to the tier count; each
        demotion is recorded on the request and in
        ``counters["demotions"]``.  Requires a tiered engine and at least
        one accounted block (no service-rate estimate, no demotion);
        ``deadline_demotion=False`` disables the policy (expiry then stays
        the only deadline response).

        Note the block tier is the *minimum* class across live rows — a
        demoted request speeds up its block only once every live row's
        class allows it — so demotion weakens the demoted request's own
        fidelity guarantee, never its batchmates'."""
        if (not self.deadline_demotion or len(self._tier_params) <= 1
                or self._tok_ema is None):
            return
        now = self._clock()
        hi = len(self._tier_params) - 1
        for i in self._live():
            r = self.slots[i].req
            if r.deadline is None or r.latency_class >= hi:
                continue
            need = ((r.max_new - len(r.out)) * self._tok_ema
                    * self.demote_margin)
            if need > r.deadline - now:
                r.latency_class += 1
                r.demotions += 1
                self.counters["demotions"] += 1

    def health(self) -> Dict[str, object]:
        """Engine health snapshot: queue depth, slot occupancy, in-flight
        speculation state, per-request lifecycle statuses for everything
        the engine currently tracks (queued + slot-bound), the lifetime
        ``counters`` (terminal statuses, demotions, admissions, prefill
        segments / tokens / steps, decode blocks / steps, syncs; on an MoE
        engine the routing picks ``moe_assignments`` and, one per held
        expert, ``moe_assignments_held``) and the speculative-decoding
        stats.

        Snapshot semantics — no flush, no device sync: figures reflect
        accounting up to the last synced block, and MoE counts up to the
        last dispatch the device has finished (``flush()`` first for
        exact-at-this-instant numbers).  Cheap enough to poll every tick.
        """
        self._fold_moe_counts()
        live = self._live()
        prefilling = self._prefilling()
        requests = {r.uid: r.status for r in self.queue}
        requests.update({s.req.uid: s.req.status for s in self.slots
                         if s.req is not None})
        return {
            "queue_depth": len(self.queue),
            "max_queue": self.max_queue,
            "free_slots": len(self._free_slots()),
            "decoding": len(live),
            "prefilling": len(prefilling),
            "inflight_blocks": len(self._inflight),
            "inflight_speculative": sum(1 for b in self._inflight
                                        if b.spec_k),
            "requests": requests,
            "counters": {k: list(v) if isinstance(v, list) else v
                         for k, v in self.counters.items()},
            "spec": dict(self.spec_stats),
            "tok_ema_s": self._tok_ema,
        }

    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots)
                if s.req is None or s.req.done]

    def _slot_positions(self) -> np.ndarray:
        return np.asarray([s.pos for s in self.slots], np.int32)

    @staticmethod
    def _feed_len(req: Request) -> int:
        """Prompt-feed length: ``prompt[:-1]`` (the last prompt token is the
        first decode input).  0 for a length-1 prompt — a prefill-free
        admit whose only prefill work is the slot zero-reset."""
        return len(req.prompt) - 1

    def _feed_prefill(self, i: int, start: int, count: int):
        """Feed ``count`` prompt-feed tokens from ``start`` into slot ``i``
        — one fused jitted call (``models.model.prefill_into_slot``): one
        forward pass of the segment for KV-only stacks, an on-device
        token-serial scan for the rest, so host dispatch is O(1) per
        segment instead of O(segment_len).

        State is written **only at the fed row for valid tokens** — live
        slots keep their rows bit-untouched (every per-layer state leaf
        carries batch at axis 1: (L, B, ...)), and on the first segment
        (``start == 0``) the row is zero-reset so recurrent families never
        inherit the previous occupant's state.  Segments are padded to
        power-of-two lengths; padded tokens write nothing, bounding traces
        at O(log max_seq)."""
        s = self.slots[i]
        feed = np.asarray(s.req.prompt[:-1], np.int32)
        seg = feed[start:start + count]
        padded = _next_pow2(max(len(seg), 1))
        toks = np.zeros((padded,), np.int32)
        toks[:len(seg)] = seg
        valid = np.arange(padded) < len(seg)
        self.counters["prefill_segments"] += 1
        self.counters["prefill_tokens"] += len(seg)
        self.counters["prefill_steps"] += padded
        self.counters["prefill_forwards"] += self._prefill_forward
        with TraceAnnotation("serve.prefill.dispatch", uid=s.req.uid,
                             tokens=len(seg), steps=padded):
            self.state, moe = self._prefill(self._exec_params, self.state,
                                            toks, valid, np.int32(i),
                                            self._slot_positions(),
                                            np.int32(start), start == 0)
        self._note_moe_counts(moe)
        s.prefill_cursor = start + len(seg)
        s.pos = s.prefill_cursor
        fed = s.prefill_cursor >= self._feed_len(s.req)
        if fed:
            s.req.prefilled = self._clock()
        # lifecycle: the slot is decode-ready once the whole feed landed
        if not s.req.done:
            s.req.status = "decode" if fed else "prefill"

    def _admit(self):
        """Move queued requests into free slots.  The ``admission`` policy
        picks *which* queued request each freed slot takes (FIFO by
        default) and sizes the prefill chunk.  Short prompts (feed fits one
        chunk, or the policy returns ``None``) prefill whole at admit;
        longer prompts feed their first chunk now (the zero-reset rides on
        it) and the rest via ``_advance_prefill`` interleaved with decode
        blocks, so a long prompt never stalls live decodes."""
        admitted = False
        for i in self._free_slots():
            if not self.queue:
                break
            idx = self.admission.pick(self.queue, self)
            req = self.queue[idx]
            del self.queue[idx]
            with TraceAnnotation("serve.admit", uid=req.uid, slot=i):
                req.admitted = self._clock()
                self.counters["admitted"] += 1
                self.slots[i] = _Slot(req=req, pos=0, prefill_cursor=0)
                feed_len = self._feed_len(req)
                chunk = self.admission.chunk(self)
                count = feed_len if chunk is None else min(feed_len, chunk)
                # feed_len == 0 (length-1 prompt): the call runs one fully
                # masked step whose only effect is the slot-row zero-reset
                self._feed_prefill(i, 0, count)
            admitted = True
        return admitted

    def _prefilling(self) -> List[int]:
        """Slots whose prompt feed is not fully prefilled yet (they ride
        decode blocks as masked filler rows until their last chunk lands).
        """
        return [i for i, s in enumerate(self.slots)
                if s.req is not None and not s.req.done
                and s.prefill_cursor < self._feed_len(s.req)]

    def _advance_prefill(self) -> bool:
        """Feed one pending prefill chunk (round-robin over mid-prefill
        slots) — the prefill half of the chunked-prefill / decode-block
        interleave.  Chunk size comes from the ``admission`` policy each
        tick (adaptive policies re-size per feed as occupancy moves).
        Returns True when a chunk was fed."""
        pend = self._prefilling()
        if not pend:
            return False
        i = pend[self._prefill_rr % len(pend)]
        self._prefill_rr += 1
        s = self.slots[i]
        chunk = self.admission.chunk(self)
        count = (self._feed_len(s.req) - s.prefill_cursor
                 if chunk is None else chunk)
        self._feed_prefill(i, s.prefill_cursor, count)
        return True

    # ---- decode ----
    def _live(self) -> List[int]:
        """Decode-ready slots: occupied, not done, prompt fully prefilled
        (mid-prefill slots stay masked out of decode until their last
        chunk)."""
        return [i for i, s in enumerate(self.slots)
                if s.req is not None and not s.req.done
                and s.prefill_cursor >= self._feed_len(s.req)]

    def _live_mask(self, live: List[int]) -> jax.Array:
        """Device-resident (n_slots,) bool mask for ``live`` (cached per
        live set — the mask is re-uploaded only when occupancy changes)."""
        key = tuple(live)
        if key not in self._mask_cache:
            m = np.zeros((self.n_slots,), bool)
            m[list(live)] = True
            self._mask_cache[key] = jnp.asarray(m)
        return self._mask_cache[key]

    def _current_tokens(self, live: List[int]) -> np.ndarray:
        toks = np.zeros((self.n_slots,), np.int32)
        for i in live:
            s = self.slots[i]
            hist = (list(s.req.prompt) + s.req.out)
            toks[i] = hist[s.pos] if s.pos < len(hist) else hist[-1]
        return toks

    def _finish_check(self, s: _Slot):
        """Request-completion policy, shared by the oracle and fused paths:
        done on EOS, on budget exhaustion, or on hitting the ``max_seq - 1``
        sequence wall (marked done, never silently truncated — the request
        keeps everything it generated)."""
        r = s.req
        if (self.eos_id is not None and r.out and r.out[-1] == self.eos_id) \
                or len(r.out) >= r.max_new or s.pos >= self.max_seq - 1:
            self._finish(r, "done")

    def _append_token(self, i: int, tok: int, out: Dict[int, int]):
        s = self.slots[i]
        if not s.req.out:
            s.req.first_token = self._clock()
        s.req.out.append(tok)
        s.pos += 1
        out[s.req.uid] = tok
        self._finish_check(s)

    def _append_block(self, live: List[int], block: np.ndarray,
                      t_block: int) -> Dict[int, List[int]]:
        """Credit a synced (T, n_slots) token block to its requests.

        A slot that went inactive mid-block (EOS hit, or ``rem`` budget
        drained) emits the -1 sentinel for its remaining steps — its column
        is truncated at the sentinel, so the slot is credited exactly the
        tokens the per-token oracle would have produced before stopping.
        The -2 quarantine sentinel (``nan_guard``) truncates the same way
        but marks the request ``failed``: the tokens before it are healthy
        and kept, everything at and after the poisoned step is discarded.

        A row whose request is already terminal (cancelled / expired /
        failed / finished by an earlier block) is skipped outright — late
        tokens from a deferred block are never credited past a terminal
        transition, so a cancelled slot can't leak a speculative block's
        tokens into its stream (or its successor's: the successor has a
        different uid and its own column)."""
        out: Dict[int, List[int]] = {}
        for i in live:
            s = self.slots[i]
            if s.req.done:
                continue
            toks_i = block[:t_block, i].tolist()
            quarantined = False
            for j, t in enumerate(toks_i):
                if t < 0:
                    quarantined = (t == model_lib.QUARANTINE_SENTINEL)
                    toks_i = toks_i[:j]
                    break
            if toks_i and not s.req.out:
                s.req.first_token = self._clock()
            s.req.out.extend(toks_i)
            s.pos += len(toks_i)
            out[s.req.uid] = toks_i
            if quarantined:
                self._finish(s.req, "failed")
            else:
                self._finish_check(s)
        return out

    def _sampling_arrays(self, live: List[int]):
        """Per-slot (temperature, top_k, seed) arrays for a decode dispatch,
        or ``None`` when every live slot is greedy — the all-greedy path
        then omits the sampling operands entirely (a distinct, cheaper jit
        trace with no PRNG work), preserving the pre-sampling executables
        bit-for-bit."""
        if all(self.slots[i].req.sampling is None
               or self.slots[i].req.sampling.temperature <= 0
               for i in live):
            return None
        temp = np.zeros((self.n_slots,), np.float32)
        topk = np.zeros((self.n_slots,), np.int32)
        seeds = np.zeros((self.n_slots,), np.int32)
        for i in live:
            sp = self.slots[i].req.sampling
            if sp is not None:
                temp[i] = sp.temperature
                topk[i] = sp.top_k
                seeds[i] = sp.seed
        return temp, topk, seeds

    def step(self) -> Dict[int, int]:
        """One decode step for every live slot; returns {uid: new_token}.

        The per-token reference oracle: a fused T-block is computation-
        identical to T of these steps (same per-slot position vectors, same
        token-0 filler rows for dead slots, same masked state commits, same
        position-keyed sampling).  The host syncs the logits and picks the
        token here — the cost the fused loop amortizes away.

        Any async in-flight block is flushed first (its tokens are credited
        to the requests but not returned here — this call's return is this
        step's tokens only).

        Failure semantics match the fused path: deadlines are expired at
        the top of the step and, under ``nan_guard``, a row whose logits
        go non-finite is marked ``failed`` with no token emitted (the
        host-side twin of the fused block's -2 sentinel — the oracle must
        implement the same state machine the chaos suite compares against).
        """
        self.flush()
        self._expire_deadlines()
        self._maybe_demote()
        self._admit()
        self._advance_prefill()
        live = self._live()
        if not live:
            return {}
        toks = self._current_tokens(live)[:, None]
        pos = self._slot_positions()
        logits, self.state = self._decode(
            self._tier_params[self._block_tier(live)], toks, self.state,
            pos, self._live_mask(live))
        lg = logits[:, 0, :]
        finite = (np.asarray(jnp.all(jnp.isfinite(lg), axis=-1))
                  if self.nan_guard else None)
        samp = self._sampling_arrays(live)
        if samp is None:
            nxt = np.asarray(jnp.argmax(lg, axis=-1))
        else:
            temp, topk, seeds = samp
            nxt = np.asarray(model_lib.sample_tokens(
                lg, jnp.asarray(temp), jnp.asarray(topk),
                jnp.asarray(seeds), jnp.asarray(pos)))
        out: Dict[int, int] = {}
        for i in live:
            if finite is not None and not finite[i]:
                self._finish(self.slots[i].req, "failed")
                continue
            self._append_token(i, int(nxt[i]), out)
        return out

    def _block_len(self, live: List[int], budget: int) -> int:
        """Fused block length: *max* live-slot remaining (request budget
        and sequence room), clamped to [1, budget].  One short request no
        longer shrinks everyone's block — the device-side ``rem`` budget
        carried through ``decode_many`` stops each row exactly at its own
        limit (emitting the -1 sentinel thereafter), so overshoot is
        impossible even when the block outlives a slot.

        The length is rounded *down* to a power of two: ``n_steps`` is a
        static jit argument (the scan length), so each distinct value is a
        full retrace+compile of the T-step executable — quantizing bounds
        the compile count at O(log decode_block), the same trick as the
        pow2-padded prefill feeds."""
        rem = max(
            max(min(s.req.max_new - len(s.req.out),
                    (self.max_seq - 1) - s.pos), 1)
            for s in (self.slots[i] for i in live))
        t = max(1, min(rem, budget))
        return 1 << (t.bit_length() - 1)       # largest pow2 <= t

    def _slot_budgets(self, live: List[int]) -> np.ndarray:
        """Per-slot device budget: steps each row may still take (request
        budget and sequence room); 0 for dead rows.  ``decode_many``
        decrements it in the scan and goes inactive at 0 — the device-side
        half of the no-overshoot invariant."""
        rem = np.zeros((self.n_slots,), np.int32)
        for i in live:
            s = self.slots[i]
            rem[i] = max(min(s.req.max_new - len(s.req.out),
                             (self.max_seq - 1) - s.pos), 0)
        return rem

    # ---- async double-buffered block machinery ----
    def _live_key(self, live: List[int]) -> tuple:
        """Occupancy identity for a live set: (slot, uid) pairs.  The carry
        / speculation validity key — slot indices alone would alias a slot
        recycled to a *different* request between blocks."""
        return tuple((i, self.slots[i].req.uid) for i in live)

    def _block_tier(self, live: List[int]) -> int:
        """Plan tier a block over ``live`` decodes/verifies under: the
        *minimum* (least aggressive) latency class among the live rows,
        clamped to the tier count — a mixed block never serves any request
        below its own class."""
        if len(self._tier_params) <= 1:
            return 0
        hi = len(self._tier_params) - 1
        return min(min(self.slots[i].req.latency_class, hi) for i in live)

    def _spec_k_for(self, t_block: int, tier: int) -> int:
        """Draft length for the next block, 0 to decode plain.  Speculate
        when enabled and the block has >= 2 steps of budget (a 1-step block
        is cheaper decoded directly), unless the verifying tier already IS
        the draft tier — drafting with the same plan it verifies under
        costs k extra steps for nothing.  A single-tier engine still
        speculates (self-drafting under the full plan, the always-accept
        test mode).  Families without a windowed-exact parallel scorer
        (``_spec_windowed`` False) never speculate — the sequential
        scorer saves nothing over plain decode."""
        if not self.speculate_k or not self._spec_windowed or t_block < 2:
            return 0
        n = len(self._tier_params)
        if n > 1 and tier >= n - 1:
            return 0
        return self.speculate_k

    def _dispatch_block(self, live: List[int], t_block: int, toks_in,
                        pos_in, rem_in) -> int:
        """Dispatch one fused block WITHOUT syncing its token array: the
        (T, n_slots) block is parked on ``_inflight`` and the device
        (token, pos, rem) carries are retained for the next launch.
        ``_account_one`` later pays the deferred host cost.

        Tier routing and the speculate-or-decode choice live here so both
        launch paths (host inputs, device carries) get them uniformly: a
        speculative launch dispatches ONE fused verify block (draft tier
        proposes ``speculate_k``, the block's tier scores all k+1
        positions) whose row length is spec_k + 1; a plain launch
        dispatches ``decode_many`` under the block's tier.  Both return
        carries with identical semantics, so verify and decode blocks
        interleave freely in the double-buffer.  Returns the dispatched
        row length (what the caller must count against its step budget)."""
        tier = self._block_tier(live)
        spec_k = self._spec_k_for(t_block, tier)
        samp = self._sampling_arrays(live)
        temp, topk, seeds = samp if samp is not None else (None, None, None)
        if spec_k:
            t_block = spec_k + 1
        self.counters["decode_blocks"] += 1
        self.counters["decode_steps"] += t_block
        with TraceAnnotation("serve.decode.dispatch", steps=t_block,
                             live=len(live)):
            moe = None
            if spec_k:
                block, self.state, dev_tok, dev_pos, dev_rem = self._verify(
                    self._tier_params[tier], self._tier_params[-1],
                    self.state, toks_in, pos_in, self._live_mask(live),
                    rem_in, temp, topk, seeds, spec_k, self._spec_windowed)
            else:
                block, self.state, dev_tok, dev_pos, dev_rem, moe = \
                    self._decode_many(
                        self._tier_params[tier], self.state, toks_in,
                        pos_in, self._live_mask(live), rem_in, temp, topk,
                        seeds, t_block)
        self._note_moe_counts(moe)
        key = self._live_key(live)
        self._carry = (key, dev_tok, dev_pos, dev_rem)
        self._inflight.append(_InflightBlock(key, list(live), t_block,
                                             block, spec_k=spec_k))
        return t_block

    def _launch(self, live: List[int], t_block: int) -> int:
        """Launch a block for ``live``: from the device carries when they
        match this exact occupancy (no host round-trip — the async fast
        path), else from host-built inputs (first block, or after an
        occupancy change invalidated the carries).  Returns the dispatched
        row length (spec blocks are ``speculate_k + 1`` rows regardless of
        the requested length; device budgets stop overshoot)."""
        if self._carry is not None and self._carry[0] == self._live_key(live):
            _, dev_tok, dev_pos, dev_rem = self._carry
            return self._dispatch_block(live, t_block, dev_tok, dev_pos,
                                        dev_rem)
        return self._dispatch_block(live, t_block,
                                    self._current_tokens(live),
                                    self._slot_positions(),
                                    self._slot_budgets(live))

    def _account_one(self, out: Optional[Dict[int, List[int]]] = None
                     ) -> bool:
        """Sync + credit the oldest in-flight block — the deferred host
        accounting (token-block sync, EOS/sentinel truncation, budget and
        ``max_seq``-wall completion checks).  Merges the credited tokens
        into ``out`` when given.  Returns True when any of the block's
        requests finished — the occupancy-change signal that invalidates a
        speculatively dispatched successor block's live set."""
        blk = self._inflight.pop(0)
        self.counters["syncs"] += 1
        with TraceAnnotation("serve.sync", steps=blk.t_block):
            block = np.asarray(blk.block)
        with TraceAnnotation("serve.account"):
            credited = self._append_block(blk.live, block, blk.t_block)
            self._fold_moe_counts()
        # service-rate EMA (seconds per credited token) between accounted
        # blocks — the deadline-pressure demotion trigger's estimate.  A
        # deterministic VirtualClock that never advances keeps this None/0,
        # so fault tests stay clock-exact.
        now = self._clock()
        n_tok = sum(len(t) for t in credited.values())
        if self._last_account is not None and n_tok:
            dt = now - self._last_account
            if dt > 0:
                per = dt / n_tok
                self._tok_ema = (per if self._tok_ema is None
                                 else 0.8 * self._tok_ema + 0.2 * per)
        self._last_account = now
        if blk.spec_k:
            # acceptance accounting: a row emitting n >= 1 tokens accepted
            # n-1 of its spec_k drafts (the last emit is the verify tier's
            # correction or bonus token); rows that emitted nothing were
            # inactive and drafted nothing useful
            self.spec_stats["verify_blocks"] += 1
            for uid, toks in credited.items():
                if not toks:
                    continue
                acc = len(toks) - 1
                self.spec_stats["drafted"] += blk.spec_k
                self.spec_stats["accepted"] += acc
                self.spec_stats["emitted"] += len(toks)
        if out is not None:
            for uid, toks in credited.items():
                out.setdefault(uid, []).extend(toks)
        return any(self.slots[i].req.done for i in blk.live)

    def flush(self) -> Dict[int, List[int]]:
        """Sync and credit every async in-flight block; returns the
        {uid: [tokens]} they produced (empty when nothing was pending).
        Call before inspecting request/slot state mid-traffic; the drain
        loops, ``step()``, ``warmup()`` and ``maybe_recalibrate()`` flush
        on their own.

        Safe and idempotent in every engine state: on a fresh engine that
        never dispatched, after a drain, or called repeatedly, it is a
        {}-returning no-op (regression-tested — see
        tests/test_fault_tolerance.py)."""
        out: Dict[int, List[int]] = {}
        while self._inflight:
            self._account_one(out)
        self._fold_moe_counts(wait=True)
        return out

    def _note_moe_counts(self, counts: Optional[jax.Array]):
        """Park a dispatch's on-device MoE routing counts until they are
        folded into ``counters``."""
        if counts is not None:
            self._moe_pending.append(counts)

    def _fold_moe_counts(self, wait: bool = False):
        """Add the parked MoE routing counts that the device has finished
        (all of them where ``wait``) to ``counters``."""
        keep = []
        for c in self._moe_pending:
            if not (wait or c.is_ready()):
                keep.append(c)
                continue
            c = np.asarray(c)
            self.counters["moe_assignments"] += int(c[0])
            held = self.counters["moe_assignments_held"]
            for j, n in enumerate(c[1:]):
                held[j] += int(n)
        self._moe_pending = keep

    def speculative_acceptance(self) -> float:
        """Lifetime draft acceptance rate: accepted drafts / proposed
        drafts over every verify block accounted so far (0.0 before any
        speculation).  Call ``flush()`` first to fold any in-flight verify
        block into the counters."""
        d = self.spec_stats["drafted"]
        return self.spec_stats["accepted"] / d if d else 0.0

    def _joinable(self) -> bool:
        """True when a request could join the live set this tick — a slot
        is mid-prefill, or the queue is non-empty with a free slot.
        Speculating past such a tick would pin the in-flight occupancy for
        one more block and make the joiner wait it out; skipping the
        speculation lets late joiners board the very next launch.  At full
        occupancy with no pending prefill (the steady-state decode regime)
        this is False and double-buffering runs uninhibited."""
        return bool(self._prefilling()
                    or (self.queue and self._free_slots()))

    def _block_len_ahead(self, live: List[int], budget: int,
                         inflight_t: int) -> int:
        """Block length for a *speculative* launch: host budgets are stale
        by exactly the ``inflight_t`` unaccounted steps of the pending
        block, so subtract them before sizing.  Returns 0 when every live
        row will have exhausted its budget inside the pending block —
        speculating would dispatch a pure-sentinel block (EOS can still
        stop rows earlier; that waste is bounded by one block)."""
        rem = max(
            min(s.req.max_new - len(s.req.out),
                (self.max_seq - 1) - s.pos) - inflight_t
            for s in (self.slots[i] for i in live))
        if rem <= 0:
            return 0
        t = max(1, min(rem, budget))
        return 1 << (t.bit_length() - 1)

    def decode_block_step(self, n_steps: Optional[int] = None
                          ) -> Dict[int, List[int]]:
        """One fused serving tick: admit, feed one pending prefill chunk,
        decode one T-step block on-device.  Returns {uid: [tokens]}.
        ``n_steps`` caps the block (default ``decode_block``); per-slot
        device budgets stop each row at its own limit, so no request
        overshoots.

        The tick is double-buffered across calls: block k launches from
        the device carries *before* block k-1's token sync, so the device
        never idles over the tick boundary and the returned tokens are the
        *previous* call's block (one block of latency; ``flush()`` collects
        the tail).  Two exceptions keep the
        deferral off the latency paths: a block carrying some live
        request's *first* token is synced in this call (first-token
        urgency — TTFT never pays the deferral) unless the call already
        credited tokens, and no block is speculated while a request could
        join the live set this tick (``_joinable``).  A call never holds
        tokens it has credited while it waits on a further block: if block
        k-1's accounting reveals an occupancy change, the call returns and
        the next one accounts the speculative block k — its tokens are
        still exact — and relaunches from host state; a first-token block
        launched after tokens were credited is synced by the next call,
        after it launches that block's successor.
        """
        with TraceAnnotation("serve.tick"):
            return self._tick(n_steps)

    def _tick(self, n_steps: Optional[int]) -> Dict[int, List[int]]:
        budget = max(1, self.decode_block if n_steps is None else n_steps)
        out: Dict[int, List[int]] = {}
        # failure-path bookkeeping runs first: expiring a request here
        # drops it out of _live(), which invalidates the carry key below —
        # the expired row is never speculated over, and its in-flight
        # tokens are discarded at sync (never credited past terminal)
        self._expire_deadlines()
        self._maybe_demote()
        launched = False
        if self._inflight:
            live = self._live()
            if live and not self._joinable() and self._carry is not None \
                    and self._carry[0] == self._live_key(live):
                t_spec = self._block_len_ahead(
                    live, budget, self._inflight[-1].t_block)
                if t_spec > 0:
                    self._launch(live, t_spec)
                    launched = True
            if self._account_one(out) and launched:
                # occupancy changed under the speculative block: return the
                # credited tokens now, a finishing request's last ones among
                # them, rather than hold them while it runs; the next tick
                # accounts it (finished rows emitted sentinels, its tokens
                # are exact) and relaunches from host state
                return out
        self._admit()
        self._advance_prefill()
        live = self._live()
        if not live or launched:
            return out
        t_block = self._block_len(live, budget)
        self._launch(live, t_block)
        # First-token urgency: deferral trades latency for throughput, and
        # a request that has not streamed its first token yet is paying
        # that latency straight into its TTFT.  Sync such blocks on the
        # spot; defer only in the steady state where every live request is
        # already streaming (the carry is still set, so the next tick
        # speculates from device state either way).  A tick that already
        # credited tokens returns them instead: waiting here would hold
        # them, a finishing request's last ones among them, for the whole
        # new block; the next tick syncs it after launching its successor.
        if not out and any(not self.slots[i].req.out for i in live):
            self._account_one(out)
        return out

    def _drained(self) -> bool:
        return (not self.queue and not self._prefilling()
                and all(s.req is None or s.req.done for s in self.slots))

    def run_until_drained(self, max_steps: int = 1024) -> Dict[int, List[int]]:
        """Serve until queue, slots and in-flight blocks drain (or
        ``max_steps`` decode steps), then flush.  ``fused=True`` runs
        serving ticks (``decode_block_step``), so a drain dispatches exactly
        what the served loop would; an iteration that dispatched no decode
        step and synced no block (prefill only) counts one step, so a stuck
        prefill cannot loop forever.  ``fused=False`` is the per-token
        oracle loop.

        Returns {uid: tokens} for every request that reached a terminal
        state during the drain, and for those finished before the call
        that still hold their slots (read from the record ``results()``
        reads, so bounded like it to the most recent 4096 terminals)."""
        c = self.counters
        held = {s.req.uid for s in self.slots
                if s.req is not None and s.req.done}
        before = set(self._retired) - held
        if not self.fused:
            for _ in range(max_steps):
                self.step()
                if self._drained():
                    break
        else:
            steps = 0
            while steps < max_steps and (self._inflight
                                         or not self._drained()):
                d0, s0 = c["decode_steps"], c["syncs"]
                self.decode_block_step(
                    min(self.decode_block, max_steps - steps))
                d = c["decode_steps"] - d0
                steps += d if d or c["syncs"] != s0 else 1
            self.flush()
        return {uid: out for uid, (_, out, _) in self._retired.items()
                if uid not in before}


def build_serve_step(cfg: ArchConfig):
    """The lowered serving step for the dry-run decode cells."""
    def serve_step(params, tokens, state, pos):
        return model_lib.decode_step(params, cfg, tokens, state, pos)
    return serve_step
