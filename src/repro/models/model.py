"""Top-level model API: ArchConfig → init / loss / prefill / decode.

Single entry point consumed by the trainer, the serving engine, the dry-run
launcher and the smoke tests.  All functions are pure (params are pytrees);
distribution happens outside via pjit shardings + the ``sharding.partition``
logical-axis constraints inside.

Frontend stubs (per the assignment spec): [vlm] archs take precomputed patch
embeddings ``vis_embeds`` that overwrite the leading token positions (plus
M-RoPE position streams); [audio] archs take precomputed frame embeddings
``frames`` feeding the encoder.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.kernels import ops
from repro.models import transformer
from repro.models.layers import (apply_norm, chunked_softmax_xent, embed,
                                 init_embedding, init_norm, logits_head)
from repro.models.unroll import maybe_unrolled_scan
from repro.sharding.partition import shard

Params = Dict[str, jax.Array]

N_VIS_STUB = 1024       # patch-embedding prefix length for [vlm] (stub)


def n_vis(cfg: ArchConfig, seq_len: int) -> int:
    if cfg.frontend != "vision":
        return 0
    return min(N_VIS_STUB, seq_len // 4)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(cfg: ArchConfig, rng, dtype=jnp.bfloat16) -> Params:
    k_emb, k_stack, k_head = jax.random.split(rng, 3)
    p: Params = {
        "embed": init_embedding(cfg, k_emb, dtype),
        "stack": transformer.init_stack(cfg, k_stack, dtype),
        "final_norm": init_norm(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = init_embedding(cfg, k_head, dtype)
    return p


def head_matrix(p: Params, cfg: ArchConfig) -> jax.Array:
    """The (V, D) logits matrix — ``embed`` when tied, else ``lm_head``.

    Under an attached ``WeightSparsityPlan`` the untied ``lm_head`` leaf is
    a ``PlannedWeight`` (consumed by ``ops.head_matmul``); the tied head is
    always the raw ``embed`` leaf — the plan never wraps it, because
    ``embed()`` gathers rows from the same tensor.
    """
    return p["embed"] if cfg.tie_embeddings else p["lm_head"]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward_hidden(p: Params, cfg: ArchConfig, batch: Dict[str, jax.Array], *,
                   remat: str = "none", q_chunk: int = 512) -> jax.Array:
    """Token/frontend inputs → final-norm hidden states (B, S, D)."""
    if cfg.encoder_decoder:
        x = embed(cfg, p["embed"], batch["tokens"])
        b, s, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        x = transformer.apply_stack(p["stack"], cfg, x, positions=positions,
                                    remat=remat, q_chunk=q_chunk,
                                    frames=batch["frames"])
        return apply_norm(p["final_norm"], cfg, x)

    tokens = batch["tokens"]
    b, s = tokens.shape
    x = embed(cfg, p["embed"], tokens)
    if cfg.frontend == "vision" and "vis_embeds" in batch:
        nv = batch["vis_embeds"].shape[1]
        x = jax.lax.dynamic_update_slice(
            x, batch["vis_embeds"].astype(x.dtype), (0, 0, 0))
        del nv
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = transformer.apply_stack(
        p["stack"], cfg, x, positions=positions, remat=remat,
        q_chunk=q_chunk, mrope_positions=batch.get("mrope_positions"))
    return apply_norm(p["final_norm"], cfg, x)


def train_loss(p: Params, cfg: ArchConfig, batch: Dict[str, jax.Array], *,
               remat: str = "none", loss_chunk: int = 512,
               q_chunk: int = 512) -> jax.Array:
    x = forward_hidden(p, cfg, batch, remat=remat, q_chunk=q_chunk)
    return chunked_softmax_xent(cfg, head_matrix(p, cfg), x, batch["labels"],
                                chunk=loss_chunk)


# ---------------------------------------------------------------------------
# Prefill (cache-filling) + decode
# ---------------------------------------------------------------------------

def prefill(p: Params, cfg: ArchConfig, batch: Dict[str, jax.Array], *,
            q_chunk: int = 512) -> jax.Array:
    """Prompt pass returning last-position logits (B, 1, V).

    For encoder-decoder archs this is the *encoder* pass (the assigned
    ``prefill_32k`` cell lowers the encoder; see DESIGN.md §5), returning
    pooled encoder logits-shaped hidden for shape-compat.
    """
    if cfg.encoder_decoder:
        mem = transformer.encode(p["stack"], cfg, batch["frames"],
                                 q_chunk=q_chunk)
        return mem[:, -1:, :]
    x = forward_hidden(p, cfg, batch, q_chunk=q_chunk)
    return logits_head(cfg, head_matrix(p, cfg), x[:, -1:, :])


def init_decode_state(cfg: ArchConfig, batch: int, max_seq: int,
                      dtype=jnp.bfloat16) -> Params:
    return transformer.init_decode_state(cfg, batch, max_seq, dtype)


def decode_step(p: Params, cfg: ArchConfig, tokens: jax.Array, state: Params,
                pos: jax.Array, commit: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Params]:
    """One new token for every sequence.  tokens (B, 1) → logits (B, 1, V).

    ``pos`` is a scalar (lockstep) or a (B,) vector of per-sequence
    positions (see ``attention.decode_step``).  ``commit`` (B,) bool masks
    which rows commit state (None: every row; see ``masked_decode_step``).
    """
    logits, state, _ = _decode_step_counted(p, cfg, tokens, state, pos,
                                            commit)
    return logits, state


def _decode_step_counted(p: Params, cfg: ArchConfig, tokens: jax.Array,
                         state: Params, pos: jax.Array,
                         commit: Optional[jax.Array] = None):
    """``decode_step`` that also returns the MoE routing counts of the
    committing rows (``transformer.decode_stack``; None for stacks
    without experts)."""
    x = embed(cfg, p["embed"], tokens)
    x, state, counts = transformer.decode_stack(p["stack"], cfg, x, state,
                                                pos, commit)
    x = apply_norm(p["final_norm"], cfg, x)
    return logits_head(cfg, head_matrix(p, cfg), x), state, counts


def _batch_mask(mask: jax.Array, leaf: jax.Array) -> jax.Array:
    """Broadcast a (B,) bool mask over a stacked state leaf (L, B, ...)."""
    return mask.reshape((1, mask.shape[0]) + (1,) * (leaf.ndim - 2))


def masked_decode_step(p: Params, cfg: ArchConfig, tokens: jax.Array,
                       state: Params, pos: jax.Array, active: jax.Array
                       ) -> Tuple[jax.Array, Params]:
    """``decode_step`` that only commits state for ``active`` (B,) rows.

    Inactive rows (dead slots, EOS-done rows, mid-prefill rows running as
    filler) keep their state bit-untouched: a mid-prefill slot's partially
    written KV/recurrent prefix must survive the decode blocks interleaved
    between its chunks, and a done row stops writing cache.  The mask is
    also installed as the popcount row filter (``ops.active_rows``) so
    runtime activation densities count live rows only.

    Commits are masked per row, in place, inside the layer scan
    (``transformer.decode_stack``): each layer writes every row's K/V slot
    into the stacked cache, attends, and puts back the slot of each
    inactive row (so filler rows compute exactly what they did under a
    whole-state select); recurrent leaves (SSM, RG-LRU) are selected per
    row on each layer's slice, and read-only leaves (the encoder-decoder
    ``memory``) pass through.  No step computes a whole new state to
    select from.
    """
    logits, state, _ = _masked_decode_step_counted(p, cfg, tokens, state,
                                                   pos, active)
    return logits, state


def _masked_decode_step_counted(p: Params, cfg: ArchConfig,
                                tokens: jax.Array, state: Params,
                                pos: jax.Array, active: jax.Array):
    """``masked_decode_step`` plus the MoE routing counts of the
    ``active`` rows (None for stacks without experts)."""
    with ops.active_rows(active):
        return _decode_step_counted(p, cfg, tokens, state, pos, active)


def sample_tokens(logits: jax.Array, temp: jax.Array, top_k: jax.Array,
                  seeds: jax.Array, pos: jax.Array) -> jax.Array:
    """Per-row temperature / top-k sampling over (B, V) logits.

    ``temp`` (B,) float: 0 selects greedy argmax for that row (bit-equal to
    the plain argmax path — the fused-vs-oracle token-for-token guarantees
    live on greedy rows).  ``top_k`` (B,) int: keep the k highest logits
    (0 or ≥ V disables).  Randomness is *position-keyed*: row r at sequence
    position p draws from ``fold_in(PRNGKey(seeds[r]), p)``, so a sampled
    stream is a pure function of (seed, position) — reproducible across
    runs and invariant to how the serving loop blocks its decode steps
    (a T-step fused block samples exactly what T oracle steps would).
    """
    v = logits.shape[-1]
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    k = jnp.clip(top_k, 1, v)
    top_desc = -jnp.sort(-lg, axis=-1)
    thresh = jnp.take_along_axis(top_desc, (k - 1)[:, None], axis=-1)
    use_k = (top_k > 0) & (top_k < v)
    masked = jnp.where(use_k[:, None] & (lg < thresh), -jnp.inf, lg)
    keys = jax.vmap(lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t)
                    )(seeds.astype(jnp.uint32), pos.astype(jnp.uint32))
    gumbel = jax.vmap(lambda key: jax.random.gumbel(key, (v,), jnp.float32)
                      )(keys)
    sampled = jnp.argmax(masked / jnp.maximum(temp, 1e-6)[:, None] + gumbel,
                         axis=-1).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)


# On-device row-stop sentinels emitted by ``decode_many`` / ``verify_block``
# token blocks: -1 marks a benign stop (EOS hit or budget drained — the host
# truncates and the request completes normally), QUARANTINE_SENTINEL (-2)
# marks an on-device NaN/Inf quarantine under ``nan_guard`` — the host
# truncates at it and marks the request *failed*.  Both sit below every
# valid token id, so sentinel scans are a single ``tok < 0`` test.
QUARANTINE_SENTINEL = -2


def decode_many(p: Params, cfg: ArchConfig, tokens: jax.Array, state: Params,
                pos: jax.Array, live: jax.Array, n_steps: int, *,
                rem: Optional[jax.Array] = None,
                eos_id: Optional[int] = None,
                temp: Optional[jax.Array] = None,
                top_k: Optional[jax.Array] = None,
                seeds: Optional[jax.Array] = None,
                nan_guard: bool = False,
                moe_counts: bool = False,
                ) -> Tuple[jax.Array, ...]:
    """Fused multi-token decode: ``n_steps`` decode steps in one
    ``lax.scan``, with on-device token selection feeding the next token.

    The serving hot loop: host work becomes O(1) per *block* of tokens
    instead of per token — only the (T, B) token block crosses back to the
    host.  ``tokens`` (B,) holds each sequence's current input token
    (prompt tail or last generated), ``pos`` (B,) the per-sequence position
    and ``live`` (B,) which rows decode.

    Per-row stopping runs **on device**: ``rem`` (B,) int32 is each row's
    remaining token budget (None = unbounded) and ``eos_id`` the stop
    token (static; None disables).  A row is *active* while live with
    budget left; emitting ``eos_id`` zeroes its budget.  Inactive rows
    feed token-0 filler, stop writing cache (state commits are masked to
    active rows via ``masked_decode_step``), never advance their token /
    position carries, and emit a ``-1`` sentinel — the host truncates each
    slot's block column at its sentinel, so one short request no longer
    forces the whole batch onto its block length.

    ``temp`` / ``top_k`` / ``seeds`` (all (B,), or all None for pure
    greedy) select per-row sampling (see ``sample_tokens``); randomness is
    position-keyed, so sampled streams are block-boundary invariant too.

    ``nan_guard`` adds on-device NaN/Inf quarantine: a row whose logits go
    non-finite at some step is deactivated *at that step* — it emits the
    distinct ``QUARANTINE_SENTINEL`` (-2), its budget is zeroed (so any
    speculatively dispatched successor block sees it inactive) and its
    token/position carries stay frozen at the last healthy step.  Only the
    poisoned row stops; every other row's stream is bit-unchanged (the
    guard is a per-row select on integer carries — when no row is
    poisoned, the emitted block is identical to the unguarded one).  The
    host distinguishes -2 from the -1 EOS/budget sentinel to mark the
    request ``failed`` rather than ``done``.  Note the poisoned row's
    state row may hold non-finite values from the detection step; rows
    are state-decoupled and the serving layer zero-resets a slot on
    re-admission, so the poison never crosses rows.

    Returns (token block (T, B) int32, new state, final token carry (B,),
    final position carry (B,), final remaining-budget carry (B,)).  The
    carries let a serving loop chain blocks *device-to-device*: as long as
    the live set is unchanged, the next block's ``tokens``/``pos`` inputs
    are exactly these outputs — no host round-trip or re-upload between
    blocks.

    The carries also make **speculative dispatch** safe: a block launched
    from them before the previous block's tokens reach the host is always
    token-exact, even when host accounting later shrinks the live set —
    a row that finished (EOS / budget) inside the previous block enters
    this one with ``rem == 0``, so it emits only ``-1`` sentinels, never
    commits state, and the host simply truncates it to zero tokens.
    Speculation can waste device steps on such rows, but never corrupts
    a stream (see ``repro.serve.engine`` async dispatch).

    ``moe_counts`` appends a sixth result: the MoE routing counts of the
    active rows summed over the block's steps (see ``moe.decode_moe``;
    None for stacks without experts).
    """
    live = live.astype(bool)
    b = tokens.shape[0]
    if rem is None:
        rem = jnp.full((b,), jnp.iinfo(jnp.int32).max // 2, jnp.int32)
    eos = jnp.int32(-1 if eos_id is None else eos_id)
    sample = temp is not None

    def step(carry, _):
        tok, st, ps, rm, cnt = carry
        active = live & (rm > 0)
        feed = jnp.where(active, tok, 0).astype(jnp.int32)[:, None]
        logits, st, c = _masked_decode_step_counted(p, cfg, feed, st, ps,
                                                    active)
        if cnt is not None:
            cnt = cnt + c
        lg = logits[:, 0, :]
        if sample:
            nxt = sample_tokens(lg, temp, top_k, seeds, ps)
        else:
            nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        if nan_guard:
            bad = active & ~jnp.all(jnp.isfinite(lg), axis=-1)
            good = active & ~bad
            emit = jnp.where(bad, QUARANTINE_SENTINEL,
                             jnp.where(active, nxt, -1))
            rm = jnp.where(bad, 0,
                           jnp.where(active,
                                     jnp.where(nxt == eos, 0, rm - 1), rm))
            tok = jnp.where(good, nxt, tok)
            ps = jnp.where(good, ps + 1, ps)
        else:
            emit = jnp.where(active, nxt, -1)
            rm = jnp.where(active, jnp.where(nxt == eos, 0, rm - 1), rm)
            tok = jnp.where(active, nxt, tok)
            ps = jnp.where(active, ps + 1, ps)
        return (tok, st, ps, rm, cnt), emit

    (tok, state, pos, rem, cnt), toks = maybe_unrolled_scan(
        step, (tokens.astype(jnp.int32), state, pos.astype(jnp.int32),
               rem.astype(jnp.int32), _counts_init(cfg, moe_counts)),
        None, length=n_steps)
    if moe_counts:
        return toks, state, tok, pos, rem, cnt
    return toks, state, tok, pos, rem


def _counts_init(cfg: ArchConfig, on: bool) -> Optional[jax.Array]:
    """Zero MoE routing counts ((1 + experts_held,) int32) where ``on``
    and the stack has experts, else None."""
    if not (on and cfg.moe.enabled):
        return None
    return jnp.zeros((1 + cfg.moe.experts_held,), jnp.int32)


def verify_window(p: Params, cfg: ArchConfig, tokens: jax.Array,
                  state: Params, pos: jax.Array, active: jax.Array
                  ) -> Tuple[jax.Array, Params]:
    """Score W consecutive tokens per row in ONE batched forward.

    tokens (B, W); ``pos`` (B,) the position of each row's first token;
    ``active`` (B,) masks which rows commit state (inactive rows ride as
    filler, state bit-untouched — same contract as ``masked_decode_step``).
    Returns logits (B, W, V) for every window position and the new state
    with K/V written for **all** W positions of active rows.

    Stale-KV safety (the rollback half of the speculative contract): when
    the caller accepts only ``n ≤ W`` tokens, slots past ``pos + n`` hold
    K/V the stream will never have produced — but the attention validity
    mask excludes every slot above the query's position, and the next
    block (decode or verify) starts at ``pos + n`` and re-writes each slot
    *before* any query attends it, so stale entries are dead weight, never
    an input.  Plain dense full-cache stacks only (see
    ``transformer.decode_stack_window``).
    """
    x = embed(cfg, p["embed"], tokens)
    with ops.active_rows(active):
        x, new = transformer.decode_stack_window(p["stack"], cfg, x,
                                                 state, pos)
    state = jax.tree.map(
        lambda old, nw: jnp.where(_batch_mask(active, old), nw, old),
        state, new)
    x = apply_norm(p["final_norm"], cfg, x)
    return logits_head(cfg, head_matrix(p, cfg), x), state


def verify_block(p_full: Params, p_draft: Params, cfg: ArchConfig,
                 tokens: jax.Array, state: Params, pos: jax.Array,
                 live: jax.Array, k: int, *,
                 rem: Optional[jax.Array] = None,
                 eos_id: Optional[int] = None,
                 temp: Optional[jax.Array] = None,
                 top_k: Optional[jax.Array] = None,
                 seeds: Optional[jax.Array] = None,
                 windowed: bool = True,
                 nan_guard: bool = False,
                 ) -> Tuple[jax.Array, Params, jax.Array, jax.Array,
                            jax.Array]:
    """Self-speculative decode block: draft ``k`` tokens with the pruned
    tier ``p_draft``, score all ``k + 1`` positions with the full plan
    ``p_full``, accept the longest matching prefix.

    Same signature family and **identical return contract** as
    ``decode_many`` with ``n_steps = k + 1`` — (token block (k+1, B) int32
    with ``-1`` sentinels past each row's acceptance point, new state,
    token/pos/rem carries) — so a serving loop treats a verify block as an
    ordinary decode block (sentinel truncation, carry chaining, async
    deferral all unchanged).

    Exactness: the emitted stream is token-for-token the full-plan stream.
    Position ``i`` of the window feeds exactly what the full-plan oracle
    would have fed *as long as every earlier draft token matched the
    full-plan choice*; the first mismatch position is scored with the
    full plan anyway, so its emitted token is the oracle's correction, and
    everything past it emits sentinels.  A fully-matching window emits
    ``k + 1`` tokens (the k drafts + the bonus token from the last scored
    position).  Sampled rows use the position-keyed PRNG
    (``sample_tokens``), making the draft's proposal and the oracle's
    choice the same deterministic function of (seed, position, logits) —
    acceptance degenerates to exact token equality, and the stream still
    equals the full-plan sampled stream.

    Draft state is **provisional by construction**: the draft runs
    ``decode_many`` on a copy of the carries and its returned state is
    discarded — rollback is free in a functional framework.  The verify
    pass commits through the masked paths: ``windowed=True`` (plain dense
    full-cache stacks) scores in one batched ``verify_window`` forward —
    the throughput win — while ``windowed=False`` scans
    ``masked_decode_step`` with commits gated on the still-matching mask,
    leaving the state exactly the accepted prefix's.

    The sequential scorer is exact for every family whose rows are
    decoupled (the served MoE layer is: ``moe.decode_moe`` routes each
    row alone), but k+1 sequential full-plan steps save nothing over
    plain decode, which is why ``ServeEngine`` gates speculation to
    windowed-exact families and serves everything else plain blocks.

    ``nan_guard`` quarantines rows whose *verify-tier* logits go
    non-finite, exactly as in ``decode_many``: the row emits
    ``QUARANTINE_SENTINEL`` (-2) at the poisoned position, freezes its
    carries there and zeroes its budget.  The draft pass runs unguarded —
    its tokens are proposals; a poisoned draft either disagrees with the
    healthy verify scores (rejected as usual) or the verify scores are
    poisoned too, which is what the guard detects.
    """
    live = live.astype(bool)
    b = tokens.shape[0]
    if rem is None:
        rem = jnp.full((b,), jnp.iinfo(jnp.int32).max // 2, jnp.int32)
    eos = jnp.int32(-1 if eos_id is None else eos_id)
    sample = temp is not None

    # --- draft: k speculative tokens from the aggressive tier.  No budget
    # or EOS stopping (the verify loop re-applies both exactly), state and
    # carries discarded — only the proposed tokens survive.
    d_toks, _, _, _, _ = decode_many(
        p_draft, cfg, tokens, state, pos, live, k,
        temp=temp, top_k=top_k, seeds=seeds)
    # (B, k+1) feed window: current token, then the k draft proposals
    # (sanitized: dead rows draft -1 sentinels, which must not hit embed)
    win = jnp.concatenate(
        [jnp.where(live, tokens.astype(jnp.int32), 0)[:, None],
         jnp.maximum(d_toks.T, 0)], axis=1)

    tok = tokens.astype(jnp.int32)
    ps = pos.astype(jnp.int32)
    rm = rem.astype(jnp.int32)
    active0 = live & (rm > 0)

    if windowed:
        feed = jnp.where(active0[:, None], win, 0)
        logits, state = verify_window(p_full, cfg, feed, state, ps, active0)

    ok = live                   # prefix-still-matching (AND live)
    emits = []
    for i in range(k + 1):
        act = ok & (rm > 0)
        if windowed:
            lg = logits[:, i, :]
        else:
            feed = jnp.where(act, win[:, i], 0)[:, None]
            lg_i, state = masked_decode_step(p_full, cfg, feed, state,
                                             ps, act)
            lg = lg_i[:, 0, :]
        if sample:
            nxt = sample_tokens(lg, temp, top_k, seeds, ps)
        else:
            nxt = jnp.argmax(lg.astype(jnp.float32),
                             axis=-1).astype(jnp.int32)
        if nan_guard:
            bad = act & ~jnp.all(jnp.isfinite(lg), axis=-1)
            good = act & ~bad
            emits.append(jnp.where(bad, QUARANTINE_SENTINEL,
                                   jnp.where(act, nxt, -1)))
            rm = jnp.where(bad, 0,
                           jnp.where(act,
                                     jnp.where(nxt == eos, 0, rm - 1), rm))
            tok = jnp.where(good, nxt, tok)
            ps = jnp.where(good, ps + 1, ps)
            if i < k:
                ok = ok & ~bad & (win[:, i + 1] == nxt)
        else:
            emits.append(jnp.where(act, nxt, -1))
            rm = jnp.where(act, jnp.where(nxt == eos, 0, rm - 1), rm)
            tok = jnp.where(act, nxt, tok)
            ps = jnp.where(act, ps + 1, ps)
            if i < k:
                ok = ok & (win[:, i + 1] == nxt)

    return jnp.stack(emits), state, tok, ps, rm


def _reset_row(state: Params, row: jax.Array, reset: jax.Array) -> Params:
    """Zero batch row ``row`` of every stacked state leaf (L, B, ...) where
    ``reset`` holds: one row slab per leaf, written in place with
    ``dynamic_update_slice``.

    Admission resets the row: recurrent families (SSM / RG-LRU) carry
    state across tokens, and the freed slot's old trajectory must not
    bleed into the new request.  KV slots past the position are masked,
    but a masked slot still enters the value sum with weight 0, and
    0 * NaN is NaN: a row quarantined by ``nan_guard`` would poison its
    slot's next request."""
    reset = jnp.asarray(reset, bool)

    def one(a):
        start = (0, row) + (0,) * (a.ndim - 2)
        old = jax.lax.dynamic_slice(a, start, (a.shape[0], 1) + a.shape[2:])
        return jax.lax.dynamic_update_slice(
            a, jnp.where(reset, jnp.zeros_like(old), old), start)
    return jax.tree.map(one, state)


def prefill_into_slot(p: Params, cfg: ArchConfig, tokens: jax.Array,
                      valid: jax.Array, slot: jax.Array, state: Params,
                      slot_pos: jax.Array, start: jax.Array = 0,
                      reset: jax.Array = True, *, moe_counts: bool = False):
    """Feed one admitted prompt (or one *chunk* of it) into one decode-state
    slot in a single fused pass — uniform across dense / MoE / SSM / hybrid
    state families.

    ``tokens`` (P,) is the prompt feed segment (zero-padded to a static
    length), ``valid`` (P,) marks real positions, ``slot`` the batch row
    being filled, ``slot_pos`` (B,) every slot's current position.
    ``start`` is the sequence position of the segment's first token —
    chunked prefill feeds ``feed[c : c+chunk]`` with ``start = c`` so a
    long prompt admits across several calls interleaved with decode
    blocks.  ``reset`` zero-resets the admitted row before feeding (True on
    the whole-prompt path and on chunk 0; later chunks must NOT re-reset
    the prefix they already wrote).

    Stacks whose decode state is only full-length KV caches
    (``transformer.kv_state_only``: dense without a window, MoE) run the
    segment as **one forward pass** of the admitted row's P tokens
    (``transformer.prefill_stack``): each layer writes the valid tokens'
    K/V into the row in place and attends them causally over it; padded
    tokens write nothing and, under MoE, neither route nor count.  No
    logits are computed.  Every other family (ring caches, SSM, RG-LRU,
    encoder-decoder) carries state token to token and scans the
    token-serial ``prefill_serial``.  On both paths every other row's
    state is bit-untouched, and ``moe_counts`` appends the routing counts
    of the valid tokens (None for stacks without experts).

    Because the other rows are untouched, a prefill chunk may run while a
    ``decode_many`` block is still in flight on other slots: the chunk's
    stale view of those slots' ``slot_pos`` is harmless, so chunked
    prefill composes with the engine's async double-buffered dispatch
    without a drain.
    """
    if not transformer.kv_state_only(cfg):
        return prefill_serial(p, cfg, tokens, valid, slot, state, slot_pos,
                              start, reset, moe_counts=moe_counts)
    state = _reset_row(state, slot, reset)
    valid = valid.astype(bool)
    x = embed(cfg, p["embed"], tokens.astype(jnp.int32)[None])
    with ops.active_rows(valid):
        state, cnt = transformer.prefill_stack(
            p["stack"], cfg, x, state, slot, jnp.asarray(start, jnp.int32),
            valid)
    if moe_counts:
        return state, cnt
    return state


def prefill_serial(p: Params, cfg: ArchConfig, tokens: jax.Array,
                   valid: jax.Array, slot: jax.Array, state: Params,
                   slot_pos: jax.Array, start: jax.Array = 0,
                   reset: jax.Array = True, *, moe_counts: bool = False):
    """``prefill_into_slot`` token by token, for every state family: scans
    ``masked_decode_step`` over the P positions with per-slot positions,
    committing state **only at the admitted row on valid steps** (the
    other rows run as masked filler).  Every per-layer state leaf carries
    batch at axis 1: (L, B, ...)."""
    b = slot_pos.shape[0]
    onehot = jnp.arange(b) == slot
    state = _reset_row(state, slot, reset)
    start = jnp.asarray(start, jnp.int32)

    def step(carry, inp):
        st, cnt = carry
        t, tok, ok = inp
        merge = onehot & ok
        feed = jnp.where(merge, tok, 0).astype(jnp.int32)[:, None]
        ps = jnp.where(onehot, start + t, slot_pos).astype(jnp.int32)
        _, st, c = _masked_decode_step_counted(p, cfg, feed, st, ps, merge)
        if cnt is not None:
            cnt = cnt + c
        return (st, cnt), None

    n = tokens.shape[0]
    (state, cnt), _ = maybe_unrolled_scan(
        step, (state, _counts_init(cfg, moe_counts)),
        (jnp.arange(n, dtype=jnp.int32), tokens.astype(jnp.int32),
         valid.astype(bool)))
    if moe_counts:
        return state, cnt
    return state


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins for the dry-run)
# ---------------------------------------------------------------------------

def input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, object]:
    """ShapeDtypeStructs for every model input of the (arch, shape) cell.

    No device allocation — these lower through ``jax.jit(...).lower()``.
    """
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    bf16 = jnp.bfloat16
    S = jax.ShapeDtypeStruct

    if shape.kind == "train":
        specs = {"tokens": S((b, s), i32), "labels": S((b, s), i32)}
        if cfg.encoder_decoder:
            specs["frames"] = S((b, s, cfg.d_model), bf16)
        if cfg.frontend == "vision":
            specs["vis_embeds"] = S((b, n_vis(cfg, s), cfg.d_model), bf16)
            specs["mrope_positions"] = S((3, b, s), i32)
        return specs

    if shape.kind == "prefill":
        if cfg.encoder_decoder:
            return {"frames": S((b, s, cfg.d_model), bf16)}
        specs = {"tokens": S((b, s), i32)}
        if cfg.frontend == "vision":
            specs["vis_embeds"] = S((b, n_vis(cfg, s), cfg.d_model), bf16)
            specs["mrope_positions"] = S((3, b, s), i32)
        return specs

    # decode: one new token against a seq_len-deep state
    state = jax.eval_shape(
        lambda: init_decode_state(cfg, b, s))
    return {
        "tokens": S((b, 1), i32),
        "state": state,
        "pos": S((), i32),
    }
