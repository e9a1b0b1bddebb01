"""Attention: MHA / GQA / MQA, causal + sliding-window, KV caches.

The long-sequence path is a chunked online-softmax (flash-style) written in
pure JAX — it is both the memory-feasible XLA execution path (32k-token
prefill would otherwise materialize S² score tensors) and the oracle for the
Pallas ``flash_attention`` kernel.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import rope
from repro.models.unroll import maybe_unrolled_map, maybe_unrolled_scan
from repro.sharding.partition import shard

Params = Dict[str, jax.Array]
NEG_INF = -1e30


def init_attention(cfg: ArchConfig, rng, dtype=jnp.bfloat16,
                   cross: bool = False) -> Params:
    d, hd = cfg.d_model, cfg.head_dim
    kq, kk, ko = jax.random.split(rng, 3)
    s = d ** -0.5
    p = {
        "wq": (jax.random.normal(kq, (d, cfg.n_heads * hd)) * s).astype(dtype),
        "wkv": (jax.random.normal(kk, (d, 2 * cfg.n_kv_heads * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ko, (cfg.n_heads * hd, d)) * s).astype(dtype),
    }
    return p


def _project_qkv(p: Params, cfg: ArchConfig, x: jax.Array,
                 kv_x: Optional[jax.Array] = None):
    """x (B,S,D) -> q (B,S,KVH,G,hd), k/v (B,Skv,KVH,hd)."""
    b, s, _ = x.shape
    hd, kvh = cfg.head_dim, cfg.n_kv_heads
    g = cfg.q_per_kv
    q = ops.flex_matmul(x, p["wq"], site="attn.q").reshape(b, s, kvh, g, hd)
    src = x if kv_x is None else kv_x
    kv = ops.flex_matmul(src, p["wkv"], site="attn.kv")
    kv = kv.reshape(b, src.shape[1], 2, kvh, hd)
    k, v = kv[:, :, 0], kv[:, :, 1]
    return q, k, v


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: Optional[jax.Array]) -> jax.Array:
    """q (B,Sq,KVH,G,hd), k/v (B,Skv,KVH,hd), mask (B,1,1,Sq,Skv) bool."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k).astype(jnp.float32)
    scores = scores * (hd ** -0.5)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", w, v)


def cache_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: jax.Array) -> jax.Array:
    """``dense_attention`` over a decode cache whose rows are flat:
    q (B,Sq,KVH,G,hd), k/v (B,Skv,KVH·hd), mask (B,1,1,Sq,Skv) bool.

    The cache is read in the layout it is written in: splitting a flat
    row into (KVH, hd) would relayout the whole cache on TPU.  Instead the
    queries form a block-diagonal (KVH·hd, KVH·G·Sq) matrix, so each
    head's scores contract only its own hd block of the row (the other
    blocks meet exact zeros), and the values' product keeps only each
    head's own block.  Same products and f32 accumulation as
    ``dense_attention``, with bf16 scores and output."""
    b, sq, kvh, g, hd = q.shape
    n = kvh * g * sq
    own = jnp.eye(kvh, dtype=bool)[None, :, None, :, None, None]
    qt = jnp.transpose(q, (0, 4, 2, 3, 1))[:, None]     # (B,1,hd,KVH,G,Sq)
    qbd = jnp.where(own, qt, jnp.zeros((), q.dtype)).reshape(b, kvh * hd, n)
    scores = jnp.einsum("bsf,bfn->bns", k, qbd).astype(jnp.float32)
    scores = scores.reshape(b, kvh, g, sq, -1) * (hd ** -0.5)
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype).reshape(b, n, -1)
    out = jnp.einsum("bns,bsf->bnf", w, v).reshape(b, kvh, g, sq, kvh, hd)
    out = jnp.diagonal(out, axis1=1, axis2=4)            # (B,G,Sq,hd,KVH)
    return jnp.transpose(out, (0, 2, 4, 1, 3))


class _Carry(NamedTuple):
    m: jax.Array       # running max      (B,KVH,G,Qc)
    l: jax.Array       # running sum      (B,KVH,G,Qc)
    acc: jax.Array     # weighted values  (B,KVH,G,Qc,hd)


def _online_block(carry: _Carry, qc, kc, vc, mask_blk, scale) -> _Carry:
    s = jnp.einsum("bqkgh,bskh->bkgqs", qc, kc).astype(jnp.float32) * scale
    s = jnp.where(mask_blk, s, NEG_INF)
    m_new = jnp.maximum(carry.m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(carry.m - m_new)
    l_new = carry.l * alpha + p.sum(axis=-1)
    acc_new = carry.acc * alpha[..., None] \
        + jnp.einsum("bkgqs,bskh->bkgqh", p.astype(qc.dtype), vc)
    return _Carry(m_new, l_new, acc_new)


def flash_attention_xla(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool, q_chunk: int = 512,
                        kv_chunk: int = 512) -> jax.Array:
    """Chunked online-softmax attention; never materializes S×S scores."""
    b, sq, kvh, g, hd = q.shape
    skv = k.shape[1]
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq, nk = sq // q_chunk, skv // kv_chunk
    scale = hd ** -0.5

    qs = jnp.moveaxis(q.reshape(b, nq, q_chunk, kvh, g, hd), 1, 0)
    ks = jnp.moveaxis(k.reshape(b, nk, kv_chunk, kvh, hd), 1, 0)
    vs = jnp.moveaxis(v.reshape(b, nk, kv_chunk, kvh, hd), 1, 0)

    def per_q(qi, qc):
        init = _Carry(
            m=jnp.full((b, kvh, g, q_chunk), NEG_INF, jnp.float32),
            l=jnp.zeros((b, kvh, g, q_chunk), jnp.float32),
            acc=jnp.zeros((b, kvh, g, q_chunk, hd), jnp.float32))

        def kv_body(carry, inp):
            ki, kc, vc = inp
            if causal:
                qpos = qi * q_chunk + jnp.arange(q_chunk)
                kpos = ki * kv_chunk + jnp.arange(kv_chunk)
                mask = (qpos[:, None] >= kpos[None, :])[None, None, None]
            else:
                mask = jnp.ones((1, 1, 1, q_chunk, kv_chunk), bool)
            return _online_block(carry, qc, kc, vc, mask, scale), None

        out, _ = maybe_unrolled_scan(kv_body, init,
                                     (jnp.arange(nk), ks, vs))
        o = out.acc / jnp.maximum(out.l[..., None], 1e-30)
        return jnp.moveaxis(o, 3, 1).astype(q.dtype)   # (B,Qc,KVH,G,hd)

    outs = maybe_unrolled_map(lambda t: per_q(t[0], t[1]),
                              (jnp.arange(nq), qs))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, kvh, g, hd)


def windowed_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       window: int, q_chunk: int = 512) -> jax.Array:
    """Causal sliding-window attention with O(S·window) compute: each query
    chunk attends only to the [pos-window, pos] slice of K/V."""
    b, sq, kvh, g, hd = q.shape
    q_chunk = min(q_chunk, sq)
    nq = sq // q_chunk
    span = window + q_chunk
    scale = hd ** -0.5
    qs = jnp.moveaxis(q.reshape(b, nq, q_chunk, kvh, g, hd), 1, 0)

    def per_q(qi, qc):
        start = jnp.maximum(qi * q_chunk + q_chunk - span, 0)
        kc = jax.lax.dynamic_slice_in_dim(k, start, min(span, sq), axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, min(span, sq), axis=1)
        qpos = qi * q_chunk + jnp.arange(q_chunk)
        kpos = start + jnp.arange(kc.shape[1])
        mask = ((qpos[:, None] >= kpos[None, :])
                & (qpos[:, None] - kpos[None, :] < window))[None, None, None]
        s = jnp.einsum("bqkgh,bskh->bkgqs", qc, kc).astype(jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1).astype(qc.dtype)
        o = jnp.einsum("bkgqs,bskh->bqkgh", w, vc)
        return o

    outs = maybe_unrolled_map(lambda t: per_q(t[0], t[1]),
                              (jnp.arange(nq), qs))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, kvh, g, hd)


def attention_forward(p: Params, cfg: ArchConfig, x: jax.Array, *,
                      positions: jax.Array, causal: bool = True,
                      window: int = 0, kv_x: Optional[jax.Array] = None,
                      q_chunk: int = 512,
                      mrope_positions: Optional[jax.Array] = None,
                      use_flash: Optional[bool] = None,
                      return_kv: bool = False) -> jax.Array:
    """Full-sequence attention (train / prefill).

    ``return_kv=True`` additionally returns the (post-RoPE) k, v used —
    consumed by the cache-filling prefill path in ``models.model``.
    """
    b, s, d = x.shape
    q, k, v = _project_qkv(p, cfg, x, kv_x)
    if kv_x is None:   # self-attention: rotary on q and k
        qf = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
        qf = rope.apply_rope(qf, positions, kind=cfg.rope,
                             theta=cfg.rope_theta,
                             mrope_positions=mrope_positions)
        q = qf.reshape(q.shape)
        k = rope.apply_rope(k, positions[:, :k.shape[1]], kind=cfg.rope,
                            theta=cfg.rope_theta,
                            mrope_positions=mrope_positions)
    q = shard(q, "batch", None, "kv_heads", None, None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)

    if use_flash is None:
        use_flash = s > 2048
    if window and causal and s > window:
        o = windowed_attention(q, k, v, window=window, q_chunk=q_chunk)
    elif use_flash:
        # kv chunk tracks the q chunk (≥512) so coarse-chunked lowerings
        # (roofline unroll) stay O((S/c)²) blocks, not O(S²/(512·c))
        o = flash_attention_xla(q, k, v, causal=causal, q_chunk=q_chunk,
                                kv_chunk=max(q_chunk, 512))
    else:
        if causal:
            qpos = positions
            kpos = positions[:, :k.shape[1]]
            mask = (qpos[:, :, None] >= kpos[:, None, :])
            if window:
                mask &= (qpos[:, :, None] - kpos[:, None, :]) < window
            mask = mask[:, None, None]
        else:
            mask = None
        o = dense_attention(q, k, v, mask)
    o = o.reshape(b, s, cfg.n_heads * cfg.head_dim)
    out = ops.flex_matmul(o, p["wo"], site="attn.out")
    out = shard(out, "batch", "seq", "embed")   # pin the residual stream (SP-aware)
    if return_kv:
        return out, (k, v)
    return out


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16) -> Params:
    """Rolling cache for windowed layers (size=window), else full length.

    K and V are held flat, (B, C, KVH·hd): one slot is one contiguous row,
    which a decode step writes with a single scatter, and at a width of
    128 lanes or more the row is stored unpadded on TPU in the same layout
    every executable keeps (see ``cache_attention``)."""
    size = min(cfg.window, max_seq) if cfg.window else max_seq
    shape = (batch, size, cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_step(p: Params, cfg: ArchConfig, x: jax.Array, cache: Params,
                pos: jax.Array, layer: jax.Array, *, window: int = 0,
                commit: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, Params]:
    """One-token decode through one layer.  x (B,1,D); cache k/v are the
    whole stack's (L,B,C,KVH·hd) buffers and ``layer`` this layer's index.

    The layer writes its new K/V rows into the stacked buffers at
    ``(layer, row, slot)`` and then attends over its own layer slice of
    the updated buffers.  Carried through the layer scan (see
    ``transformer.decode_stack``), the buffers are updated in place: a
    step writes each row's slot in each layer and copies nothing else.

    ``pos`` is either a scalar (every sequence at the same depth — the
    original lockstep serving path and the dry-run decode cells) or a (B,)
    vector of per-sequence positions (the continuous-batching engine, where
    staggered admits leave every slot at its own depth).  The scalar path
    writes one ``dynamic_update_slice``; the vector path a per-row scatter,
    and the validity mask becomes per-row position bounds.

    ``commit`` (B,) bool masks what the step commits (None: every row).
    Every row writes its slot before the attention, so each row attends
    over its own token exactly as when the whole state was computed and
    then selected; a row outside ``commit`` then gets its slot's old
    value back, so its cache stays bit-untouched.  Windowed layers keep a
    ring of ``window`` slots (``pos % size``).
    """
    b = x.shape[0]
    hd = cfg.head_dim
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    q, k_new, v_new = _project_qkv(p, cfg, x)
    posb = (pos[:, None] if per_slot
            else jnp.broadcast_to(pos[None, None], (b, 1))).astype(jnp.int32)
    qf = q.reshape(b, 1, cfg.n_heads, hd)
    qf = rope.apply_rope(qf, posb, kind=cfg.rope, theta=cfg.rope_theta)
    q = qf.reshape(q.shape)
    k_new = rope.apply_rope(k_new, posb, kind=cfg.rope, theta=cfg.rope_theta)

    size = cache["k"].shape[2]
    slot = (pos % size) if window > 0 else jnp.minimum(pos, size - 1)
    news = {"k": k_new.reshape(b, -1), "v": v_new.reshape(b, -1)}
    if commit is not None:
        kept = {name: _read_rows(cache[name], layer, slot) for name in news}
    cache = {name: _write_rows(cache[name], news[name], layer, slot)
             for name in news}
    k = jax.lax.dynamic_index_in_dim(cache["k"], layer, 0, keepdims=False)
    v = jax.lax.dynamic_index_in_dim(cache["v"], layer, 0, keepdims=False)

    # validity mask over cache slots; per-row when pos is a vector
    idx = jnp.arange(size)[None] if per_slot else jnp.arange(size)
    posm = pos[:, None] if per_slot else pos
    if window > 0:
        age = posm - _slot_position(idx, posm, size)
        valid = (age >= 0) & (age < jnp.minimum(window, posm + 1))
    else:
        valid = idx <= posm
    mask = (valid[:, None, None, None, :] if per_slot
            else valid[None, None, None, None, :])
    o = cache_attention(q, k, v, mask)
    if commit is not None:
        # the barrier orders the put-back after every read of the slice
        o, kept = jax.lax.optimization_barrier((o, kept))
        cache = {name: _write_rows(cache[name], kept[name], layer, slot,
                                   ~commit) for name in cache}
    cache = {name: shard(a, None, "cache_batch", "cache_seq", None)
             for name, a in cache.items()}
    o = o.reshape(b, 1, cfg.n_heads * hd)
    out = ops.flex_matmul(o, p["wo"], site="attn.out")
    return out, cache


def prefill_step(p: Params, cfg: ArchConfig, x: jax.Array, cache: Params,
                 layer: jax.Array, row: jax.Array, start: jax.Array,
                 valid: jax.Array) -> Tuple[jax.Array, Params]:
    """W prompt tokens of one sequence through one layer.  x (1,W,D) holds
    the tokens at positions ``start … start+W-1`` of batch row ``row``;
    cache k/v are the whole stack's full-length (L,B,C,KVH·hd) buffers and
    ``layer`` this layer's index.

    The ``valid`` (W,) tokens' K/V are scattered into ``(layer, row,
    start+i)`` in place; a padded token's index is out of range and the
    scatter drops it, so it never overwrites a slot, clamped or not.  The
    W queries then attend causally (slot ≤ own position) over that row's
    layer slice, split into heads: ``dense_attention``, with the same
    products and rounding points as the decode step's ``cache_attention``
    and none of its zero blocks.  Other rows and layers are untouched.
    """
    _, w, _ = x.shape
    hd = cfg.head_dim
    q, k_new, v_new = _project_qkv(p, cfg, x)
    posw = (jnp.asarray(start, jnp.int32)
            + jnp.arange(w, dtype=jnp.int32))[None]                # (1, W)
    qf = q.reshape(1, w, cfg.n_heads, hd)
    qf = rope.apply_rope(qf, posw, kind=cfg.rope, theta=cfg.rope_theta)
    q = qf.reshape(q.shape)
    k_new = rope.apply_rope(k_new, posw, kind=cfg.rope, theta=cfg.rope_theta)

    size = cache["k"].shape[2]
    slots = jnp.where(valid, jnp.minimum(posw[0], size - 1), size)
    news = {"k": k_new, "v": v_new}
    cache = {name: cache[name].at[layer, row, slots].set(
                 news[name].reshape(w, -1).astype(cache[name].dtype),
                 mode="drop")
             for name in news}

    def row_heads(buf):                           # (1, C, KVH, hd)
        one = jax.lax.dynamic_slice(buf, (layer, row, 0, 0),
                                    (1, 1) + buf.shape[2:])
        return one.reshape(1, size, cfg.n_kv_heads, hd)
    mask = jnp.arange(size)[None, :] <= posw[0][:, None]            # (W, C)
    o = dense_attention(q, row_heads(cache["k"]), row_heads(cache["v"]),
                        mask[None, None, None])
    cache = {name: shard(a, None, "cache_batch", "cache_seq", None)
             for name, a in cache.items()}
    o = o.reshape(1, w, cfg.n_heads * hd)
    return ops.flex_matmul(o, p["wo"], site="attn.out"), cache


def _read_rows(buf: jax.Array, layer: jax.Array, slot: jax.Array
               ) -> jax.Array:
    """Each row's (KVH·hd) value at its ``slot`` of the stacked cache
    ``buf`` (L,B,C,KVH·hd) at ``layer``: (B, KVH·hd)."""
    if slot.ndim == 1:
        return buf[layer, jnp.arange(buf.shape[1]), slot]
    return jax.lax.dynamic_slice(
        buf, (layer, 0, slot, 0), (1, buf.shape[1], 1, buf.shape[3]))[0, :, 0]


def _write_rows(buf: jax.Array, new: jax.Array, layer: jax.Array,
                slot: jax.Array, rows: Optional[jax.Array] = None
                ) -> jax.Array:
    """Write one row per sequence, ``new`` (B, KVH·hd), into the stacked
    cache ``buf`` (L,B,C,KVH·hd) at ``layer``, for the (B,) ``rows`` mask
    (None: every row).

    A (B,) ``slot`` scatters each row to its own slot, and a row outside
    ``rows`` gets an out-of-range row index, which the scatter drops.  A
    scalar ``slot`` writes the (1,B,1,KVH·hd) slab with
    ``dynamic_update_slice``, putting back the old value of the rows
    outside ``rows``."""
    b = new.shape[0]
    new = new.astype(buf.dtype)
    if slot.ndim == 1:
        idx = jnp.arange(b)
        if rows is not None:
            idx = jnp.where(rows, idx, b)
        return buf.at[layer, idx, slot].set(new, mode="drop")
    upd = new[None, :, None]
    start = (layer, 0, slot, 0)
    if rows is not None:
        old = jax.lax.dynamic_slice(buf, start, upd.shape)
        upd = jnp.where(rows[None, :, None, None], upd, old)
    return jax.lax.dynamic_update_slice(buf, upd, start)


def decode_window(p: Params, cfg: ArchConfig, x: jax.Array, cache: Params,
                  pos: jax.Array) -> Tuple[jax.Array, Params]:
    """W-position batched decode — the speculative-verify scorer.

    x (B, W, D) holds W consecutive tokens per row, ``pos`` (B,) the
    sequence position of each row's *first* window token; cache k/v are
    one layer's (B,C,KVH·hd).  Full-length caches only
    (``cfg.window == 0``): all W K/V pairs are scattered into
    the cache first, then every query attends the whole cache under a
    per-(row, query) validity mask ``idx <= pos + i`` — causal over the
    prefix *and* within the window (query i sees keys ≤ its own position,
    which were just written).  One forward scores W positions for the cost
    of one batched attention instead of W sequential steps.

    Rows whose positions are stale (inactive rows riding the batch) write
    garbage K/V at their clamped slots; callers mask those rows out of the
    state commit (``model.verify_window``), so the garbage never lands.
    """
    b, w, _ = x.shape
    hd = cfg.head_dim
    pos = jnp.asarray(pos, jnp.int32)
    q, k_new, v_new = _project_qkv(p, cfg, x)
    posw = pos[:, None] + jnp.arange(w, dtype=jnp.int32)[None]   # (B, W)
    qf = q.reshape(b, w, cfg.n_heads, hd)
    qf = rope.apply_rope(qf, posw, kind=cfg.rope, theta=cfg.rope_theta)
    q = qf.reshape(q.shape)
    k_new = rope.apply_rope(k_new, posw, kind=cfg.rope, theta=cfg.rope_theta)

    size = cache["k"].shape[1]
    slots = jnp.minimum(posw, size - 1)
    rows = jnp.arange(b)[:, None]
    k = cache["k"].at[rows, slots].set(
        k_new.reshape(b, w, -1).astype(cache["k"].dtype))
    v = cache["v"].at[rows, slots].set(
        v_new.reshape(b, w, -1).astype(cache["v"].dtype))
    k = shard(k, "cache_batch", "cache_seq", None)
    v = shard(v, "cache_batch", "cache_seq", None)

    idx = jnp.arange(size)
    valid = idx[None, None, :] <= posw[:, :, None]               # (B, W, C)
    o = cache_attention(q, k, v, valid[:, None, None])
    o = o.reshape(b, w, cfg.n_heads * hd)
    return ops.flex_matmul(o, p["wo"], site="attn.out"), {"k": k, "v": v}


def _slot_position(idx: jax.Array, pos: jax.Array, size: int) -> jax.Array:
    """Original sequence position stored in rolling slot ``idx`` at ``pos``."""
    cur_slot = pos % size
    offset = (idx - cur_slot + size) % size
    return jnp.where(offset == 0, pos, pos - size + offset)
