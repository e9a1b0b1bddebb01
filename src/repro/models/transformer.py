"""Decoder stacks (scan-over-layers) and the Whisper encoder-decoder.

One init/apply/decode triple per layer *kind*:

  dense  : attn + gated MLP              (yi, gemma, chatglm, stablelm, qwen2-vl)
  moe    : attn + routed experts         (deepseek-moe, llama4-scout)
  ssm    : Mamba-2 SSD block             (mamba2)
  rec    : RG-LRU recurrent block + MLP  (recurrentgemma)
  enc/dec: Whisper encoder / decoder layers

Stacks scan over vmap-stacked layer weights (DESIGN.md D1): 80-layer models
compile one layer body; roofline terms are corrected per-layer by the
dry-run methodology.  Heterogeneous stacks decompose into homogeneous scans
(leading dense layers for DeepSeek-MoE; (rec,rec,attn) groups + trailing rec
layers for RecurrentGemma).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention, moe as moe_mod, rglru, ssm as ssm_mod
from repro.models.layers import (apply_mlp, apply_norm, init_mlp, init_norm)

Params = Dict[str, jax.Array]


def _stack_init(init_fn, rng, n: int):
    """Stack n independently-initialized layer param trees along axis 0."""
    return jax.vmap(init_fn)(jax.random.split(rng, n))


# Dry-run hook (see models.unroll): small-L lowerings unroll every loop so
# cost_analysis sees exact per-layer costs; production lowerings keep scans.
from repro.models.unroll import maybe_unrolled_scan as _lax_scan, scan_unroll  # noqa: E402,F401


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)          # "full": save only layer inputs


# ---------------------------------------------------------------------------
# Layer kinds
# ---------------------------------------------------------------------------

def init_dense_layer(cfg: ArchConfig, rng, dtype=jnp.bfloat16) -> Params:
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": init_norm(cfg, cfg.d_model, dtype),
        "attn": attention.init_attention(cfg, k1, dtype),
        "ln2": init_norm(cfg, cfg.d_model, dtype),
        "mlp": init_mlp(cfg, k2, cfg.d_model, cfg.d_ff, dtype),
    }


def apply_dense_layer(p: Params, cfg: ArchConfig, x: jax.Array, *,
                      positions: jax.Array, window: int = 0,
                      mrope_positions=None, q_chunk: int = 512) -> jax.Array:
    h = apply_norm(p["ln1"], cfg, x)
    x = x + attention.attention_forward(
        p["attn"], cfg, h, positions=positions, window=window,
        mrope_positions=mrope_positions, q_chunk=q_chunk)
    h = apply_norm(p["ln2"], cfg, x)
    return x + apply_mlp(p["mlp"], cfg, h)


def decode_dense_layer(p: Params, cfg: ArchConfig, x, cache, attend):
    """One dense layer over the stacked KV caches.  ``attend(attn_params,
    h, cache) -> (out, cache)`` is its attention: ``attention.decode_step``
    for one token of each row, ``attention.prefill_step`` for a prompt
    chunk of one row."""
    h = apply_norm(p["ln1"], cfg, x)
    o, cache = attend(p["attn"], h, cache)
    x = x + o
    h = apply_norm(p["ln2"], cfg, x)
    return x + apply_mlp(p["mlp"], cfg, h), cache


def init_moe_layer(cfg: ArchConfig, rng, dtype=jnp.bfloat16) -> Params:
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": init_norm(cfg, cfg.d_model, dtype),
        "attn": attention.init_attention(cfg, k1, dtype),
        "ln2": init_norm(cfg, cfg.d_model, dtype),
        "moe": moe_mod.init_moe(cfg, k2, dtype),
    }


def apply_moe_layer(p: Params, cfg: ArchConfig, x: jax.Array, *,
                    positions: jax.Array, q_chunk: int = 512,
                    mrope_positions=None) -> jax.Array:
    h = apply_norm(p["ln1"], cfg, x)
    x = x + attention.attention_forward(
        p["attn"], cfg, h, positions=positions, q_chunk=q_chunk,
        mrope_positions=mrope_positions)
    h = apply_norm(p["ln2"], cfg, x)
    return x + moe_mod.apply_moe(p["moe"], cfg, h)


def decode_moe_layer(p: Params, cfg: ArchConfig, x, cache, attend, rows):
    """``decode_dense_layer`` with the dropless expert layer in place of
    the MLP, one token a row; also returns the routing counts of the
    tokens that ``rows`` (None: all) marks (``moe.decode_moe``)."""
    h = apply_norm(p["ln1"], cfg, x)
    o, cache = attend(p["attn"], h, cache)
    x = x + o
    h = apply_norm(p["ln2"], cfg, x)
    b, t, d = h.shape
    y, counts = moe_mod.decode_moe(p["moe"], cfg, h.reshape(b * t, 1, d),
                                   rows=rows)
    return x + y.reshape(x.shape), cache, counts


def init_ssm_layer(cfg: ArchConfig, rng, dtype=jnp.bfloat16) -> Params:
    return {
        "ln1": init_norm(cfg, cfg.d_model, dtype),
        "ssm": ssm_mod.init_ssm(cfg, rng, dtype),
    }


def apply_ssm_layer(p: Params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    h = apply_norm(p["ln1"], cfg, x)
    return x + ssm_mod.ssd_forward(cfg, p["ssm"], h)


def decode_ssm_layer(p: Params, cfg: ArchConfig, x, state):
    h = apply_norm(p["ln1"], cfg, x)
    o, state = ssm_mod.ssd_decode_step(cfg, p["ssm"], h, state)
    return x + o, state


def init_rec_layer(cfg: ArchConfig, rng, dtype=jnp.bfloat16) -> Params:
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": init_norm(cfg, cfg.d_model, dtype),
        "rglru": rglru.init_rglru(cfg, k1, dtype),
        "ln2": init_norm(cfg, cfg.d_model, dtype),
        "mlp": init_mlp(cfg, k2, cfg.d_model, cfg.d_ff, dtype),
    }


def apply_rec_layer(p: Params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    h = apply_norm(p["ln1"], cfg, x)
    x = x + rglru.rglru_forward(p["rglru"], cfg, h)
    h = apply_norm(p["ln2"], cfg, x)
    return x + apply_mlp(p["mlp"], cfg, h)


def decode_rec_layer(p: Params, cfg: ArchConfig, x, state):
    h = apply_norm(p["ln1"], cfg, x)
    o, state = rglru.rglru_decode_step(p["rglru"], cfg, h, state)
    x = x + o
    h = apply_norm(p["ln2"], cfg, x)
    return x + apply_mlp(p["mlp"], cfg, h), state


# ---------------------------------------------------------------------------
# Homogeneous-stack assembly per family
# ---------------------------------------------------------------------------

def griffin_layout(cfg: ArchConfig) -> Tuple[int, int]:
    """(n_groups, n_trailing_rec) for the 1:2 attn:rec pattern."""
    glen = len(cfg.rglru.block_pattern)     # 3 for (rec, rec, attn)
    return cfg.n_layers // glen, cfg.n_layers % glen


def init_griffin_group(cfg: ArchConfig, rng, dtype=jnp.bfloat16) -> Params:
    ks = jax.random.split(rng, len(cfg.rglru.block_pattern))
    group = {}
    for i, (kind, k) in enumerate(zip(cfg.rglru.block_pattern, ks)):
        init = init_rec_layer if kind == "rec" else init_dense_layer
        group[f"b{i}_{kind}"] = init(cfg, k, dtype)
    return group


def apply_griffin_group(p: Params, cfg: ArchConfig, x, *, positions,
                        q_chunk: int = 512) -> jax.Array:
    for i, kind in enumerate(cfg.rglru.block_pattern):
        lp = p[f"b{i}_{kind}"]
        if kind == "rec":
            x = apply_rec_layer(lp, cfg, x)
        else:
            x = apply_dense_layer(lp, cfg, x, positions=positions,
                                  window=cfg.window, q_chunk=q_chunk)
    return x


def decode_griffin_group(p: Params, cfg: ArchConfig, x, kv, rec, pos, layer,
                         *, commit=None):
    """One (rec, rec, attn) group: ``kv`` holds the stacked caches of its
    attention blocks, ``rec`` this group's slice of its recurrent states."""
    kv, rec = dict(kv), dict(rec)
    for i, kind in enumerate(cfg.rglru.block_pattern):
        key = f"b{i}_{kind}"
        if kind == "rec":
            x, new = decode_rec_layer(p[key], cfg, x, rec[key])
            rec[key] = _commit_rows(commit, new, rec[key])
        else:
            x, kv[key] = decode_dense_layer(
                p[key], cfg, x, kv[key],
                lambda pa, h, c: attention.decode_step(
                    pa, cfg, h, c, pos, layer, window=cfg.window,
                    commit=commit))
    return x, kv, rec


def init_stack(cfg: ArchConfig, rng, dtype=jnp.bfloat16) -> Params:
    """Stacked layer weights for the arch's family."""
    if cfg.encoder_decoder:
        k1, k2 = jax.random.split(rng)
        return {
            "encoder": _stack_init(
                lambda r: init_dense_layer(cfg, r, dtype), k1, cfg.n_layers),
            "decoder": _stack_init(
                lambda r: init_whisper_dec_layer(cfg, r, dtype), k2,
                cfg.n_layers),
        }
    if cfg.ssm.enabled:
        return {"layers": _stack_init(
            lambda r: init_ssm_layer(cfg, r, dtype), rng, cfg.n_layers)}
    if cfg.rglru.enabled:
        n_groups, n_trail = griffin_layout(cfg)
        k1, k2 = jax.random.split(rng)
        p = {"groups": _stack_init(
            lambda r: init_griffin_group(cfg, r, dtype), k1, n_groups)}
        if n_trail:
            p["trailing"] = _stack_init(
                lambda r: init_rec_layer(cfg, r, dtype), k2, n_trail)
        return p
    if cfg.moe.enabled:
        n_moe = cfg.n_layers - cfg.moe.first_dense_layers
        k1, k2 = jax.random.split(rng)
        p = {"layers": _stack_init(
            lambda r: init_moe_layer(cfg, r, dtype), k1, n_moe)}
        if cfg.moe.first_dense_layers:
            p["dense_layers"] = _stack_init(
                lambda r: init_dense_layer(cfg, r, dtype), k2,
                cfg.moe.first_dense_layers)
        return p
    return {"layers": _stack_init(
        lambda r: init_dense_layer(cfg, r, dtype), rng, cfg.n_layers)}


def apply_stack(p: Params, cfg: ArchConfig, x: jax.Array, *,
                positions: jax.Array, remat: str = "none",
                q_chunk: int = 512, mrope_positions=None,
                frames: Optional[jax.Array] = None) -> jax.Array:
    """Run the full stack.  ``frames`` feeds the Whisper encoder."""
    if cfg.encoder_decoder:
        memory = encode(p, cfg, frames, remat=remat, q_chunk=q_chunk)
        return _scan(p["decoder"],
                     lambda lp, h: apply_whisper_dec_layer(
                         lp, cfg, h, memory=memory, positions=positions,
                         q_chunk=q_chunk),
                     x, remat)
    if cfg.ssm.enabled:
        return _scan(p["layers"],
                     lambda lp, h: apply_ssm_layer(lp, cfg, h), x, remat)
    if cfg.rglru.enabled:
        x = _scan(p["groups"],
                  lambda lp, h: apply_griffin_group(
                      lp, cfg, h, positions=positions, q_chunk=q_chunk),
                  x, remat)
        if "trailing" in p:
            x = _scan(p["trailing"],
                      lambda lp, h: apply_rec_layer(lp, cfg, h), x, remat)
        return x
    if cfg.moe.enabled:
        if "dense_layers" in p:
            x = _scan(p["dense_layers"],
                      lambda lp, h: apply_dense_layer(
                          lp, cfg, h, positions=positions, q_chunk=q_chunk),
                      x, remat)
        return _scan(p["layers"],
                     lambda lp, h: apply_moe_layer(
                         lp, cfg, h, positions=positions, q_chunk=q_chunk),
                     x, remat)
    return _scan(p["layers"],
                 lambda lp, h: apply_dense_layer(
                     lp, cfg, h, positions=positions, window=cfg.window,
                     q_chunk=q_chunk, mrope_positions=mrope_positions),
                 x, remat)


def _scan(stacked: Params, body, x: jax.Array, remat: str) -> jax.Array:
    fn = _remat(lambda h, lp: (body(lp, h), None), remat)
    x, _ = _lax_scan(fn, x, stacked)
    return x


# ---------------------------------------------------------------------------
# Whisper encoder-decoder specifics
# ---------------------------------------------------------------------------

def init_whisper_dec_layer(cfg: ArchConfig, rng, dtype=jnp.bfloat16) -> Params:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "ln1": init_norm(cfg, cfg.d_model, dtype),
        "attn": attention.init_attention(cfg, k1, dtype),
        "lnx": init_norm(cfg, cfg.d_model, dtype),
        "xattn": attention.init_attention(cfg, k2, dtype, cross=True),
        "ln2": init_norm(cfg, cfg.d_model, dtype),
        "mlp": init_mlp(cfg, k3, cfg.d_model, cfg.d_ff, dtype),
    }


def apply_whisper_dec_layer(p: Params, cfg: ArchConfig, x, *, memory,
                            positions, q_chunk: int = 512) -> jax.Array:
    h = apply_norm(p["ln1"], cfg, x)
    x = x + attention.attention_forward(p["attn"], cfg, h,
                                        positions=positions, causal=True,
                                        q_chunk=q_chunk)
    h = apply_norm(p["lnx"], cfg, x)
    x = x + attention.attention_forward(p["xattn"], cfg, h,
                                        positions=positions, causal=False,
                                        kv_x=memory, q_chunk=q_chunk)
    h = apply_norm(p["ln2"], cfg, x)
    return x + apply_mlp(p["mlp"], cfg, h)


def encode(p: Params, cfg: ArchConfig, frames: jax.Array, *,
           remat: str = "none", q_chunk: int = 512) -> jax.Array:
    """Whisper encoder over precomputed frame embeddings (frontend stub)."""
    from repro.models.rope import sinusoidal_positions
    b, s, d = frames.shape
    x = frames + sinusoidal_positions(s, d).astype(frames.dtype)[None]
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    return _scan(p["encoder"],
                 lambda lp, h: _enc_layer(lp, cfg, h, positions, q_chunk),
                 x, remat)


def _enc_layer(lp: Params, cfg: ArchConfig, h: jax.Array,
               positions: jax.Array, q_chunk: int = 512) -> jax.Array:
    """Encoder layer: bidirectional self-attention + MLP."""
    y = apply_norm(lp["ln1"], cfg, h)
    h = h + attention.attention_forward(lp["attn"], cfg, y,
                                        positions=positions, causal=False,
                                        q_chunk=q_chunk)
    y = apply_norm(lp["ln2"], cfg, h)
    return h + apply_mlp(lp["mlp"], cfg, y)


# ---------------------------------------------------------------------------
# Decode over the stacked layers
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ArchConfig, batch: int, max_seq: int,
                      dtype=jnp.bfloat16) -> Params:
    """Stacked per-layer decode state (KV caches / SSM states / LRU states)."""
    def stack(n, one):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (n,) + a.shape), one)

    if cfg.encoder_decoder:
        kvh, hd = cfg.n_kv_heads, cfg.head_dim
        return {
            "self": stack(cfg.n_layers,
                          attention.init_cache(cfg, batch, max_seq, dtype)),
            # cross-attention memory (k/v per layer) filled by prefill
            "memory": {
                "k": jnp.zeros((cfg.n_layers, batch, max_seq, kvh, hd), dtype),
                "v": jnp.zeros((cfg.n_layers, batch, max_seq, kvh, hd), dtype),
            },
        }
    if cfg.ssm.enabled:
        return {"layers": stack(cfg.n_layers,
                                ssm_mod.init_ssm_state(cfg, batch))}
    if cfg.rglru.enabled:
        n_groups, n_trail = griffin_layout(cfg)
        one_group = {}
        for i, kind in enumerate(cfg.rglru.block_pattern):
            key = f"b{i}_{kind}"
            one_group[key] = (rglru.init_rglru_state(cfg, batch, dtype)
                              if kind == "rec" else
                              attention.init_cache(cfg, batch, max_seq, dtype))
        st = {"groups": stack(n_groups, one_group)}
        if n_trail:
            st["trailing"] = stack(n_trail,
                                   rglru.init_rglru_state(cfg, batch, dtype))
        return st
    st = {"layers": stack(cfg.n_layers - cfg.moe.first_dense_layers
                          if cfg.moe.enabled else cfg.n_layers,
                          attention.init_cache(cfg, batch, max_seq, dtype))}
    if cfg.moe.enabled and cfg.moe.first_dense_layers:
        st["dense_layers"] = stack(cfg.moe.first_dense_layers,
                                   attention.init_cache(cfg, batch, max_seq,
                                                        dtype))
    return st


def _commit_rows(commit: Optional[jax.Array], new: Params, old: Params
                ) -> Params:
    """Per-row select of one layer's recurrent state (B, ...) leaves: rows
    outside ``commit`` keep ``old`` bit-for-bit (None commits every row)."""
    if commit is None:
        return new
    return jax.tree.map(
        lambda n, o: jnp.where(
            commit.reshape(commit.shape + (1,) * (o.ndim - 1)), n, o),
        new, old)


def _scan_layers(step, x: jax.Array, params: Params, kv: Params, xs=None):
    """Scan ``step(lp, h, kv, xs_l, layer) -> (h, kv, ys_l)`` over stacked
    layers.  The stacked KV caches ``kv`` ride whole in the carry, so each
    layer writes its rows into one buffer at its own ``layer`` index and
    nothing is restacked; ``xs`` (recurrent state, read-only memory) is
    sliced per layer and ``ys`` restacked, as small leaves afford."""
    n = jax.tree.leaves(params)[0].shape[0]

    def body(carry, inp):
        h, kv = carry
        lp, xl, layer = inp
        h, kv, yl = step(lp, h, kv, xl, layer)
        return (h, kv), yl
    (x, kv), ys = _lax_scan(body, (x, kv),
                            (params, xs, jnp.arange(n, dtype=jnp.int32)))
    return x, kv, ys


def decode_stack(p: Params, cfg: ArchConfig, x: jax.Array, state: Params,
                 pos: jax.Array, commit: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Params, Optional[jax.Array]]:
    """One-token step through the full stack.  x (B,1,D).  Returns the new
    hidden state, the new decode state and, for MoE stacks, the routing
    counts of the committing rows summed over the MoE layers
    ((1 + experts_held,) int32, see ``moe.decode_moe``; None otherwise).

    ``pos`` is a scalar or a (B,) per-sequence position vector — it flows
    unchanged to ``attention.decode_step`` (the only consumer); recurrent
    families (SSM / RG-LRU) are position-free.  Per-slot vectors are what
    the serving engine's continuous batching passes (staggered admits), and
    the whole stack body is what ``model.decode_many`` scans over T steps —
    every state leaf returned here threads through that scan carry, so
    state layouts must stay (L, B, ...) with batch at axis 1.

    ``commit`` (B,) bool masks which rows commit state (None: every row),
    by leaf kind: KV caches are written in place, per row, at each layer's
    index of the stacked buffer; recurrent state (SSM, RG-LRU) is selected
    per row on each layer's slice; the encoder-decoder's cross-attention
    ``memory`` is read-only and passes through untouched.
    """
    if cfg.encoder_decoder:
        def dec_step(lp, h, kv, mem, layer):
            y = apply_norm(lp["ln1"], cfg, h)
            o, kv = attention.decode_step(lp["attn"], cfg, y, kv, pos, layer,
                                          commit=commit)
            h = h + o
            y = apply_norm(lp["lnx"], cfg, h)
            b = y.shape[0]
            q = (y @ lp["xattn"]["wq"]).reshape(
                b, 1, cfg.n_kv_heads, cfg.q_per_kv, cfg.head_dim)
            o = attention.dense_attention(q, mem["k"], mem["v"], None)
            h = h + o.reshape(b, 1, -1) @ lp["xattn"]["wo"]
            y = apply_norm(lp["ln2"], cfg, h)
            return h + apply_mlp(lp["mlp"], cfg, y), kv, None
        x_out, kv, _ = _scan_layers(dec_step, x, p["decoder"], state["self"],
                                    state["memory"])
        return x_out, {"self": kv, "memory": state["memory"]}, None

    def rec_step(decode_layer):
        def step(lp, h, kv, st, layer):
            h, new = decode_layer(lp, cfg, h, st)
            return h, kv, _commit_rows(commit, new, st)
        return step

    if cfg.ssm.enabled:
        x_out, _, st = _scan_layers(rec_step(decode_ssm_layer), x,
                                    p["layers"], {}, state["layers"])
        return x_out, {"layers": st}, None

    if cfg.rglru.enabled:
        attn = {f"b{i}_{kind}" for i, kind in enumerate(cfg.rglru.block_pattern)
                if kind != "rec"}
        groups = state["groups"]

        def g_step(lp, h, kv, rec, layer):
            return decode_griffin_group(lp, cfg, h, kv, rec, pos, layer,
                                        commit=commit)
        x_out, kv, rec = _scan_layers(
            g_step, x, p["groups"],
            {k: v for k, v in groups.items() if k in attn},
            {k: v for k, v in groups.items() if k not in attn})
        new = {"groups": {**rec, **kv}}
        if "trailing" in p:
            x_out, _, new["trailing"] = _scan_layers(
                rec_step(decode_rec_layer), x_out, p["trailing"], {},
                state["trailing"])
        return x_out, new, None

    def attend(pa, h, kv, layer):
        return attention.decode_step(pa, cfg, h, kv, pos, layer,
                                     window=cfg.window, commit=commit)
    return _kv_stack(p, cfg, x, state, attend, commit)


def _kv_stack(p: Params, cfg: ArchConfig, x: jax.Array, state: Params,
              attend, rows: Optional[jax.Array]
              ) -> Tuple[jax.Array, Params, Optional[jax.Array]]:
    """The layer scans of a stack whose state is KV caches alone (dense;
    MoE after its leading dense layers), shared by the decode step and the
    one-pass prefill.  ``attend(attn_params, h, kv, layer)`` is each
    layer's attention over the stacked caches, ``rows`` the tokens that
    route and count in the expert layers.  Returns the hidden state, the
    new decode state and, for MoE stacks, the routing counts summed over
    the MoE layers (None otherwise)."""
    def dense_step(lp, h, kv, _, layer):
        h, kv = decode_dense_layer(
            lp, cfg, h, kv, lambda pa, y, c: attend(pa, y, c, layer))
        return h, kv, None

    def moe_step(lp, h, kv, _, layer):
        return decode_moe_layer(
            lp, cfg, h, kv, lambda pa, y, c: attend(pa, y, c, layer), rows)

    new = {}
    if "dense_layers" in p:
        x, new["dense_layers"], _ = _scan_layers(
            dense_step, x, p["dense_layers"], state["dense_layers"])
    x, new["layers"], counts = _scan_layers(
        moe_step if cfg.moe.enabled else dense_step, x, p["layers"],
        state["layers"])
    return x, new, None if counts is None else counts.sum(0)


def kv_state_only(cfg: ArchConfig) -> bool:
    """Whether the stack's decode state is only full-length KV caches
    (dense without ``window``; MoE, with its leading dense layers): no
    recurrent state, ring cache or encoder memory."""
    return not (cfg.encoder_decoder or cfg.ssm.enabled or cfg.rglru.enabled
                or cfg.window)


def prefill_stack(p: Params, cfg: ArchConfig, x: jax.Array, state: Params,
                  row: jax.Array, start: jax.Array, valid: jax.Array
                  ) -> Tuple[Params, Optional[jax.Array]]:
    """One forward pass of W prompt tokens of batch row ``row`` through a
    ``kv_state_only`` stack.  x (1,W,D) holds the tokens at positions
    ``start …``; ``valid`` (W,) marks the real ones.

    The layers are the decode step's (``_kv_stack``) with
    ``attention.prefill_step`` for attention: the stacked KV caches ride
    whole in the layer scan's carry, and each layer writes the valid
    tokens' K/V into its row in place.  Returns the new decode state and,
    for MoE stacks, the routing counts of the valid tokens (None
    otherwise).  No final norm or head: the caller keeps no logits."""
    def attend(pa, h, kv, layer):
        return attention.prefill_step(pa, cfg, h, kv, layer, row, start,
                                      valid)
    _, new, counts = _kv_stack(p, cfg, x, state, attend, valid)
    return new, counts


def decode_stack_window(p: Params, cfg: ArchConfig, x: jax.Array,
                        state: Params, pos: jax.Array
                        ) -> Tuple[jax.Array, Params]:
    """W-token batched decode through a plain dense stack — the speculative
    verify scorer (``model.verify_window``).  x (B, W, D); ``pos`` (B,) the
    position of each row's first window token.

    Dense full-cache stacks only: MoE is excluded (this scorer has no
    expert layer, and ``ServeEngine`` keeps speculation off for MoE), as
    are the recurrent families (SSM / RG-LRU carry state token-to-token;
    a batched window cannot reproduce the k-th step's carry without
    scanning).
    Those families verify with the sequential scorer in
    ``model.verify_block`` instead.
    """
    assert not (cfg.encoder_decoder or cfg.ssm.enabled or cfg.rglru.enabled
                or cfg.moe.enabled) and not cfg.window, \
        "decode_stack_window: plain dense full-cache stacks only"

    def body(h, inp):
        lp, st = inp
        y = apply_norm(lp["ln1"], cfg, h)
        o, st = attention.decode_window(lp["attn"], cfg, y, st, pos)
        h = h + o
        y = apply_norm(lp["ln2"], cfg, h)
        return h + apply_mlp(lp["mlp"], cfg, y), st

    x_out, st = _lax_scan(body, x, (p["layers"], state["layers"]))
    return x_out, {"layers": st}
