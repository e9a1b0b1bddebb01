"""Mixture-of-Experts: routed top-k + shared experts.

Routing: ``p = softmax(x W_r)`` over the ``n_experts`` routed experts in
float32, the top ``top_k`` of ``p`` as gates, renormalised to sum to 1
unless ``MoEConfig.norm_topk_prob`` is False (DeepSeek-MoE keeps the raw
probabilities); ``y = sum_{e in top-k} p_e FFN_e(x) + FFN_shared(x)``.

A layer may hold only a share of the routed experts
(``MoEConfig.experts_held`` from ``expert_offset``): one chip's share of an
expert-parallel deployment.  It routes over all ``n_experts`` and adds
only its own experts' part; the shared experts are held whole.

Two kinds of path:

  * **serving** (``decode_moe``): dropless and per-token, for the decode
    step and the one-pass prefill of a prompt chunk (one row a token).
    Every held expert runs on every row and each row keeps the outputs
    of the held experts among its top-k, weighted by their gates: a
    row's output depends on its own hidden state alone, never on its
    batch neighbours or on the filler rows of idle slots.  At serving
    batch sizes (a few rows, or a 128-token chunk) this exact form reads
    each held expert's weights once a call, which is what a grouped
    kernel would read too, and its compute stays under that read.
    Returns on-device routing counts for the rows that commit
    (``ServeEngine`` sums them into its counters).
  * **forward / training** (``apply_moe``): capacity-bounded and
    *sort-based* (no (T, E, C) one-hot tensors -- those are O(T*E*C) and
    unlowerable at production shapes).  The router bitmap plays the CSB
    role of FlexNN's two-sided sparsity logic: only routed token x expert
    pairs are fetched and computed (DESIGN.md §5).  Tokens over an
    expert's capacity are dropped, so a token's output depends on the
    other tokens of the call.  It needs every routed expert held.

    - oracle (``apply_moe_gshard``): the classic GShard one-hot einsum
      dispatch.  O(T*E*C) -- smoke scale only; semantic reference for tests.
    - local sort-based (``_apply_moe_local``): argsort tokens by expert,
      gather into a capacity-padded (E, C, D) buffer, batched expert
      matmuls, scatter-add combine.  Used without a mesh.  Expert weights
      stay EP-sharded (E -> "model"); XLA turns the gathers into local
      slices.
    - expert-parallel shard_map (``_apply_moe_ep``): tokens enter
      sequence-sharded over the EP axis (SP), each device routes its local
      tokens, buckets them by destination shard, exchanges via
      ``all_to_all``, computes its local experts, and returns outputs via
      the reverse ``all_to_all`` -- the standard DeepSpeed-MoE/GShard EP
      pipeline, here as an explicit collective schedule (the FlexTree
      "choose your combine" idea applied to expert dispatch).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.sparsity import PlannedWeight
from repro.kernels import ops
from repro.quant.quantize import QuantizedLinear, dequantize_leaf
from repro.sharding.partition import current_rules, shard

Params = Dict[str, jax.Array]


def _dense_w(w):
    """Unwrap a PlannedWeight / QuantizedLinear to its dense
    contraction-oriented array (for paths that manage their own
    sharding/collectives, e.g. shard_map)."""
    if isinstance(w, PlannedWeight):
        return w.w_kn
    if isinstance(w, QuantizedLinear):
        return dequantize_leaf(w, jnp.float32)
    return w


def init_moe(cfg: ArchConfig, rng, dtype=jnp.bfloat16) -> Params:
    """The router over all ``n_experts``, the ``experts_held`` routed
    experts of this share and the shared experts."""
    d = cfg.d_model
    m = cfg.moe
    e, f = m.experts_held, m.expert_d_ff
    ks = jax.random.split(rng, 5)
    s_in, s_ff = d ** -0.5, f ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (d, m.n_experts)) * s_in
                   ).astype(jnp.float32),
        "experts_in": (jax.random.normal(ks[1], (e, d, f)) * s_in
                       ).astype(dtype),
        "experts_gate": (jax.random.normal(ks[2], (e, d, f)) * s_in
                         ).astype(dtype),
        "experts_out": (jax.random.normal(ks[3], (e, f, d)) * s_ff
                        ).astype(dtype),
    }
    if m.n_shared:
        f = m.expert_d_ff * m.n_shared
        k1, k2, k3 = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_in": (jax.random.normal(k1, (d, f)) * s_in).astype(dtype),
            "w_gate": (jax.random.normal(k2, (d, f)) * s_in).astype(dtype),
            "w_out": (jax.random.normal(k3, (f, d)) * s_ff).astype(dtype),
        }
    return p


# ---------------------------------------------------------------------------
# Routing + sort-based dispatch primitives
# ---------------------------------------------------------------------------

def _top_k_gates(probs: jax.Array, k: int, norm: bool
                 ) -> Tuple[jax.Array, jax.Array]:
    """probs (T, E) -> (gates (T, k), idx (T, k) i32): the top k, each
    row's gates renormalised to sum to 1 where ``norm``."""
    gate_vals, gate_idx = jax.lax.top_k(probs, k)
    if norm:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    return gate_vals, gate_idx


def _route(router: jax.Array, xt: jax.Array, k: int, norm: bool = True
           ) -> Tuple[jax.Array, jax.Array]:
    """xt (T, D) -> (gates (T, k) f32, idx (T, k) i32).

    The router matmul is a planned dispatch site (``moe.router``) like any
    other — under a sparse descriptor it runs the block-sparse path, which
    skips only true-zero blocks and stays numerically identical to dense.
    """
    logits = ops.flex_matmul(xt.astype(jnp.float32), router,
                             site="moe.router",
                             precision=jax.lax.Precision.HIGHEST)
    return _top_k_gates(jax.nn.softmax(logits, axis=-1), k, norm)


def _dispatch_indices(fid: jax.Array, n_bins: int, capacity: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """Group flat assignments by bin with a per-bin capacity.

    fid (F,) int32 bin ids (entries >= n_bins are sentinels and never
    dispatched).  Returns (f_sel (n_bins, C) indices into F, valid bool).
    First-come capacity policy: within a bin, lower flat index wins.
    """
    f = fid.shape[0]
    order = jnp.argsort(fid, stable=True)
    counts = jnp.bincount(fid, length=n_bins)               # sentinels dropped
    start = jnp.cumsum(counts) - counts
    slot = start[:, None] + jnp.arange(capacity)[None]      # (n_bins, C)
    valid = jnp.arange(capacity)[None] < counts[:, None]
    f_sel = order[jnp.clip(slot, 0, f - 1)]
    return f_sel, valid


def _expert_ffn(xe: jax.Array, p: Params) -> jax.Array:
    """Batched expert MLP: (E, C, D) -> (E, C, D).

    Every expert einsum routes through ``ops.flex_expert_matmul`` — the
    ``moe.experts_*`` descriptor sites — so the expert contractions accept
    per-expert ``PlannedWeight`` metadata and block-sparse dispatch exactly
    like the 2-D matmul leaves.  Dense sites fall back to the batched
    einsum, bit-identical to the pre-dispatch path.
    """
    h = ops.flex_expert_matmul(xe, p["experts_in"], site="moe.experts_in")
    g = ops.flex_expert_matmul(xe, p["experts_gate"],
                               site="moe.experts_gate")
    return ops.flex_expert_matmul(jax.nn.silu(g) * h, p["experts_out"],
                                  site="moe.experts_out")


def _expert_ffn_dense(xe: jax.Array, p: Params) -> jax.Array:
    """Plain-einsum expert MLP — the gshard oracle's reference path, kept
    independent of the dispatch machinery under test."""
    h = jnp.einsum("ecd,edf->ecf", xe, _dense_w(p["experts_in"]))
    g = jnp.einsum("ecd,edf->ecf", xe, _dense_w(p["experts_gate"]))
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h,
                      _dense_w(p["experts_out"]))


def _scatter_rows(n_rows: int, idx: jax.Array, valid: jax.Array,
                  rows: jax.Array) -> jax.Array:
    """Rows (..., D) scattered to (n_rows, D); invalid slots dropped."""
    d = rows.shape[-1]
    flat_idx = jnp.where(valid, idx, n_rows).reshape(-1)
    return jnp.zeros((n_rows, d), rows.dtype).at[flat_idx].set(
        rows.reshape(-1, d), mode="drop")


# ---------------------------------------------------------------------------
# Local sort-based path (no collectives; EP via sharded batched matmuls)
# ---------------------------------------------------------------------------

def _capacity(tokens: int, k: int, n_bins: int, cf: float) -> int:
    return min(int(tokens * k / n_bins * cf) + 1, tokens * k)


def _apply_moe_local(p: Params, cfg: ArchConfig, xt: jax.Array) -> jax.Array:
    t, d = xt.shape
    m = cfg.moe
    gates, gate_idx = _route(p["router"], xt, m.top_k, m.norm_topk_prob)
    f = t * m.top_k
    fid = gate_idx.reshape(f)
    cap = _capacity(t, m.top_k, m.n_experts, m.capacity_factor)

    f_sel, valid = _dispatch_indices(fid, m.n_experts, cap)
    xe = jnp.where(valid[..., None], xt[f_sel // m.top_k], 0)   # (E, C, D)
    xe = shard(xe, "expert", None, None)
    ye = _expert_ffn(xe, p)
    ye = shard(ye, "expert", None, None)

    out_flat = _scatter_rows(f, f_sel, valid, ye)               # (F, D)
    y = (out_flat.reshape(t, m.top_k, d)
         * gates[..., None].astype(out_flat.dtype)).sum(axis=1)
    return y.astype(xt.dtype)


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path (SP in → a2a dispatch → a2a combine → SP out)
# ---------------------------------------------------------------------------

def _apply_moe_ep(p: Params, cfg: ArchConfig, x: jax.Array, rules
                  ) -> jax.Array:
    mesh = rules.mesh
    m = cfg.moe
    ep_axis = rules.logical.get("expert") or "model"
    batch_axes = rules.logical.get("batch")
    ep = mesh.shape[ep_axis]
    e_loc = m.n_experts // ep
    b, s, d = x.shape
    cf = m.capacity_factor

    def body(xb, router, w_in, w_gate, w_out):
        bl, sl, _ = xb.shape                     # local (b/dp, s/ep, d)
        t_l = bl * sl
        xt = xb.reshape(t_l, d)
        gates, gate_idx = _route(router, xt, m.top_k, m.norm_topk_prob)
        f = t_l * m.top_k
        fid = gate_idx.reshape(f)
        gflat = gates.reshape(f)

        # ---- bucket by destination shard, exchange ----
        dest = fid // e_loc
        c_send = _capacity(t_l, m.top_k, ep, cf)
        f_sel, valid = _dispatch_indices(dest, ep, c_send)
        send_x = jnp.where(valid[..., None], xt[f_sel // m.top_k], 0)
        send_le = jnp.where(valid, fid[f_sel] % e_loc, e_loc)   # sentinel
        recv_x = jax.lax.all_to_all(send_x, ep_axis, 0, 0, tiled=True)
        recv_le = jax.lax.all_to_all(send_le, ep_axis, 0, 0, tiled=True)

        # ---- local expert compute ----
        n_recv = ep * c_send
        rf = recv_x.reshape(n_recv, d)
        le = recv_le.reshape(n_recv)
        c_loc = min(int(t_l * m.top_k / e_loc * cf) + 1, n_recv)
        r_sel, valid2 = _dispatch_indices(le, e_loc, c_loc)
        xe = jnp.where(valid2[..., None], rf[r_sel], 0)         # (E_l, C, D)
        pl = {"experts_in": w_in, "experts_gate": w_gate,
              "experts_out": w_out}
        ye = _expert_ffn(xe, pl)

        # ---- return outputs to their source shard, combine ----
        out_rf = _scatter_rows(n_recv, r_sel, valid2, ye)
        back = jax.lax.all_to_all(out_rf.reshape(ep, c_send, d),
                                  ep_axis, 0, 0, tiled=True)
        contrib = jnp.where(valid[..., None],
                            back * gflat[f_sel][..., None].astype(back.dtype),
                            0)
        y = jnp.zeros((t_l, d), back.dtype).at[
            (f_sel // m.top_k).reshape(-1)].add(contrib.reshape(-1, d))
        return y.reshape(bl, sl, d).astype(xb.dtype)

    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes, ep_axis, None), P(),
                  P(ep_axis, None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None)),
        out_specs=P(batch_axes, ep_axis, None),
        check_vma=False,
    )
    # shard_map specs address raw arrays: planned weights are unwrapped here
    # and the sparse dispatch (if any) re-derives metadata inside the body
    return smapped(x, _dense_w(p["router"]), _dense_w(p["experts_in"]),
                   _dense_w(p["experts_gate"]), _dense_w(p["experts_out"]))


def _ep_applicable(cfg: ArchConfig, x: jax.Array, rules) -> bool:
    if rules is None or rules.mesh is None:
        return False
    ep_axis = rules.logical.get("expert")
    if ep_axis is None or ep_axis not in rules.mesh.axis_names:
        return False
    ep = rules.mesh.shape[ep_axis]
    if ep <= 1 or cfg.moe.n_experts % ep:
        return False
    b, s, _ = x.shape
    batch_axes = rules.logical.get("batch")
    axes = (batch_axes,) if isinstance(batch_axes, str) else (batch_axes or ())
    dp = 1
    for a in axes:
        dp *= rules.mesh.shape[a]
    # need a distinct token block per device: batch over dp, seq over ep
    return b % dp == 0 and s % ep == 0 and (b // dp) * (s // ep) >= 1


# ---------------------------------------------------------------------------
# Public entries
# ---------------------------------------------------------------------------

def _shared_ffn(sp: Params, xt: jax.Array) -> jax.Array:
    """The shared experts, one SwiGLU over every row: (T, D) -> (T, D).
    Ordinary dispatch sites (``moe.shared_*``)."""
    hs = (jax.nn.silu(ops.flex_matmul(xt, sp["w_gate"],
                                      site="moe.shared_gate"))
          * ops.flex_matmul(xt, sp["w_in"], site="moe.shared_in"))
    hs = shard(hs, "batch", "ffn")
    return ops.flex_matmul(hs, sp["w_out"], site="moe.shared_out")


def _require_all_experts(cfg: ArchConfig) -> None:
    m = cfg.moe
    if m.partial:
        raise ValueError(
            f"{cfg.name}: the capacity-bounded MoE path needs every routed "
            f"expert, but this layer holds {m.experts_held} of "
            f"{m.n_experts}; a chip's share of the experts is served "
            f"through decode_moe only")


def apply_moe(p: Params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """x (B, S, D) -> (B, S, D): routed experts + shared experts."""
    _require_all_experts(cfg)
    b, s, d = x.shape
    rules = current_rules()
    if _ep_applicable(cfg, x, rules):
        y = _apply_moe_ep(p, cfg, x, rules)
    else:
        y = _apply_moe_local(p, cfg, x.reshape(b * s, d)).reshape(b, s, d)

    y = shard(y, "batch", "seq", "embed")       # pin the residual stream (SP-aware)
    if "shared" in p:
        ys = _shared_ffn(p["shared"], x.reshape(b * s, d))
        y = y + shard(ys.reshape(b, s, d), "batch", None, "embed")
    return y


def decode_moe(p: Params, cfg: ArchConfig, x: jax.Array,
               rows: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """Dropless, per-token MoE layer for serving: x (B, S, D) -> (y (B, S,
    D), counts (1 + experts_held,) int32).

    Routes every token over all ``n_experts`` (float32 softmax, top-k,
    gates as ``norm_topk_prob`` says) and adds ``sum p_e FFN_e(x)`` over
    the held experts among its top-k, plus the shared experts.  Every held
    expert runs on every row, weighted by its gate or by zero, so a row's
    output depends only on its own hidden state.

    ``counts`` are the routing picks of the rows in ``rows`` (B,) bool
    (None: every row): ``counts[0]`` the picks over all ``n_experts``
    (``top_k`` a token), ``counts[1 + j]`` the picks of held expert j.
    """
    b, s, d = x.shape
    m = cfg.moe
    t = b * s
    xt = x.reshape(t, d)
    with jax.named_scope("moe.router"):
        gates, idx = _route(p["router"], xt, m.top_k, m.norm_topk_prob)
        # one_hot of an out-of-range index is all zeros: picks of experts
        # held elsewhere add nothing here
        pick = jax.nn.one_hot(idx - m.expert_offset, m.experts_held,
                              dtype=jnp.int32)              # (T, k, E_h)
        # elementwise products and sums, not dots: a float32 dot may
        # round its operands to bf16 on the TPU
        weight = (gates[..., None] * pick).sum(1).T         # (E_h, T) f32
        live = (jnp.ones((t,), bool) if rows is None
                else jnp.repeat(rows.astype(bool), s))
        counts = jnp.concatenate([
            (live.sum() * m.top_k)[None],
            (pick * live[:, None, None]).sum((0, 1))]).astype(jnp.int32)
    with jax.named_scope("moe.experts"):
        xe = shard(jnp.broadcast_to(xt[None], (m.experts_held, t, d)),
                   "expert", None, None)
        ye = shard(_expert_ffn(xe, p), "expert", None, None)  # (E_h, T, D)
        y = (ye.astype(jnp.float32) * weight[..., None]).sum(0)
    if "shared" in p:
        with jax.named_scope("moe.shared"):
            y = y + _shared_ffn(p["shared"], xt).astype(jnp.float32)
    return y.astype(x.dtype).reshape(b, s, d), counts


# ---------------------------------------------------------------------------
# GShard one-hot oracle (smoke scale; semantic reference for tests)
# ---------------------------------------------------------------------------

def _top_k_gating(logits: jax.Array, k: int, capacity: int,
                  norm: bool = True) -> Tuple[jax.Array, jax.Array]:
    """logits (T, E) -> (dispatch (T, E, C), combine (T, E, C)).

    First-come capacity policy over the *flat (token, slot)* order — token-
    major, slot-minor — matching ``_dispatch_indices`` exactly.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = _top_k_gates(probs, k, norm)      # (T, k)

    # flat assignment order (t-major, slot-minor), position within expert
    onehot = jax.nn.one_hot(gate_idx, e, dtype=jnp.int32)   # (T, k, E)
    flat = onehot.reshape(t * k, e)
    pos = jnp.cumsum(flat, axis=0) * flat                   # 1-based
    pos = (pos.sum(-1) - 1).reshape(t, k)                   # (T, k)
    keep = pos < capacity
    oh_cap = jax.nn.one_hot(jnp.where(keep, pos, capacity), capacity + 1,
                            dtype=probs.dtype)[..., :capacity]  # (T, k, C)
    d_slot = onehot.astype(probs.dtype)[..., None] * oh_cap[:, :, None, :]
    dispatch = d_slot.sum(axis=1)                           # (T, E, C)
    combine = (d_slot * gate_vals[..., None, None]).sum(axis=1)
    return dispatch, combine


def apply_moe_gshard(p: Params, cfg: ArchConfig, x: jax.Array) -> jax.Array:
    """O(T·E·C) einsum dispatch — oracle for the sort-based paths.

    Deliberately bypasses the site dispatch everywhere (raw einsums /
    matmuls, dense weights) so it stays a semantic reference the sparse
    paths are tested *against*.
    """
    _require_all_experts(cfg)
    b, s, d = x.shape
    m = cfg.moe
    t = b * s
    xt = x.reshape(t, d)
    capacity = _capacity(t, m.top_k, m.n_experts, m.capacity_factor)

    logits = (xt.astype(jnp.float32) @ _dense_w(p["router"]))    # (T, E)
    dispatch, combine = _top_k_gating(logits, m.top_k, capacity,
                                      m.norm_topk_prob)

    xe = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), xt)
    ye = _expert_ffn_dense(xe, p)
    y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), ye)

    if "shared" in p:
        sp = p["shared"]
        hs = (jax.nn.silu(xt @ _dense_w(sp["w_gate"]))
              * (xt @ _dense_w(sp["w_in"])))
        y = y + hs @ _dense_w(sp["w_out"])
    return y.reshape(b, s, d)


def load_balance_loss(logits: jax.Array, dispatch: jax.Array) -> jax.Array:
    """Auxiliary load-balancing loss (Switch §2.2)."""
    probs = jax.nn.softmax(logits, axis=-1)
    e = probs.shape[-1]
    frac_tokens = dispatch.sum((0, 2)) / jnp.maximum(dispatch.sum(), 1e-9)
    frac_probs = probs.mean(0)
    return e * jnp.sum(frac_tokens * frac_probs)
