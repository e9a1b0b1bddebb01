"""INT8 weight quantization for serving (FlexNN's native precision, §III-A).

FlexNN executes INT8/U8 natively; edge deployment quantizes weights (and
the paper's NNCF flow uses QAT INT8).  Here the serving-side analogue:
per-output-channel symmetric INT8 weights with f32 scales, halving (vs
bf16) the weight HBM footprint and the TP-only decode working set — the
resolution of the §Perf decode finding (72B weights at TP=16: 9 GiB bf16 →
4.5 GiB int8, which fits beside the 32k KV cache).

Matmul sites consume the quantized weights three ways:

  * **Planned sparse** — ``core.sparsity.compile_weight_plan`` on a
    quantized tree stores the int8 payload + scales inside each
    ``PlannedWeight``; dispatch fuses the dequant into the block-sparse
    epilogue (ZVC skipping and int8 bytes *compound* — the paper's central
    claim that data movement dominates).
  * **Dense Pallas** — ``kernels.int8_matmul`` (int8 tiles dequantized
    in-register next to the MXU).
  * **Dense XLA** — dequantize-then-dot (CPU tests / dry-run); XLA fuses
    the dequant into the dot's operand read.

Quantization is *zero-preserving*: a zero element quantizes to exactly 0
(round(0/scale) == 0), so ZVC bitmaps — and therefore a weight plan's
block metadata — are unchanged by quantization (property-tested).

Orientation: scales are per *output channel of the contraction* so they are
K-invariant and can scale the f32 accumulator once at the end (exact — the
``int8_matmul`` epilogue trick).  For ordinary (..., K, N) leaves that is
axis -1; the embedding-shaped ``lm_head`` (V, D) leaf contracts transposed
(x @ headᵀ), so it is quantized *on the transposed (D, V) view* — its
``QuantizedLinear`` is already contraction-oriented with per-vocab-row
scales (``dequantize_params`` transposes back, so the round-trip is a
structural identity).  Under ``tie_embeddings`` the head is the ``embed``
leaf and is never quantized, mirroring the plan's tied-head guard.
"""
from __future__ import annotations

import functools
import re
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp


class QuantizedLinear(NamedTuple):
    """Per-output-channel symmetric int8 weight (contraction-oriented)."""
    q: jax.Array          # (..., K, N) int8
    scale: jax.Array      # (..., N) f32 — per output channel


def quantize_weight(w: jax.Array) -> QuantizedLinear:
    """(K, N) float → int8 + per-N scale (symmetric, round-to-nearest).

    All-zero columns get the epsilon scale and quantize to exactly 0, so
    zero elements (and therefore ZVC bitmaps) survive the round-trip.
    """
    wf = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(wf), axis=0) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(wf / scale[None, :]), -127, 127).astype(jnp.int8)
    return QuantizedLinear(q=q, scale=scale)


def dequantize_weight(qw: QuantizedLinear, dtype=jnp.bfloat16) -> jax.Array:
    return (qw.q.astype(jnp.float32) * qw.scale[None, :]).astype(dtype)


def dequantize_leaf(qw: QuantizedLinear, dtype=jnp.bfloat16) -> jax.Array:
    """Dequantize a (possibly stacked) QuantizedLinear of any rank —
    q (..., K, N) with scale (..., N) — via a broadcast (no vmap)."""
    return (qw.q.astype(jnp.float32)
            * qw.scale[..., None, :]).astype(dtype)


# weight leaves that hold (in, out) matmul matrices — quantization targets.
# Kept in parity with the plannable-site coverage (``core.sparsity``
# ``_PLAN_SITE_KEYS`` / ``_PLAN_TOP_SITE_KEYS``): every leaf the planner can
# compile must be quantizable, test-enforced against ``matmul_sites``.
_MATMUL_LEAF = re.compile(
    r".*(wq|wkv|wo|w_in|w_gate|w_out|w_x|in_proj|out_proj|experts_in|"
    r"experts_gate|experts_out|router|lm_head)$")

# leaves stored (N, K) — quantized on the transposed view so scales sit on
# the contraction's output channels (per vocab row for the logits matmul)
_TRANSPOSED_LEAF = re.compile(r".*lm_head$")


def _is_matmul_leaf(path: str, leaf) -> bool:
    return bool(_MATMUL_LEAF.match(path)) and getattr(leaf, "ndim", 0) >= 2


@functools.lru_cache(maxsize=None)
def _stacked_quantizer(n_lead: int):
    """``quantize_weight`` over ``n_lead`` leading stack axes, compiled as
    one pass: eager ops would hold several float32 copies of a large
    expert stack at once."""
    q = quantize_weight
    for _ in range(n_lead):
        q = jax.vmap(q)
    return jax.jit(q)


def quantize_params(params, *, tie_embeddings: bool = False
                    ) -> Tuple[Dict, Dict]:
    """Pytree → (same-structure tree with QuantizedLinear at matmul leaves,
    stats dict).  Embeddings/norms/vectors stay in their original dtype.

    Stacked leaves (L, K, N) and expert leaves (L, E, K, N) quantize per
    (leading..., N) channel via vmap over the leading dims.  The ``lm_head``
    (V, D) leaf is quantized on its transposed (D, V) view (see module
    docstring); ``tie_embeddings`` skips it entirely — the tied head is the
    embedding table, which ``embed()`` gathers from (the same guard the
    weight planner applies).
    """
    stats = {"quantized_bytes": 0, "original_bytes": 0, "n_quantized": 0}

    def qleaf(kp, leaf):
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        if not _is_matmul_leaf(path, leaf):
            return leaf
        if _TRANSPOSED_LEAF.match(path):
            if tie_embeddings:
                return leaf
            leaf_kn = jnp.swapaxes(leaf, -1, -2)
        else:
            leaf_kn = leaf
        out = _stacked_quantizer(leaf_kn.ndim - 2)(leaf_kn)
        stats["n_quantized"] += 1
        stats["original_bytes"] += leaf.size * leaf.dtype.itemsize
        stats["quantized_bytes"] += out.q.size + out.scale.size * 4
        return out

    return jax.tree_util.tree_map_with_path(qleaf, params), stats


def dequantize_params(qparams, dtype=jnp.bfloat16):
    """Inverse of quantize_params (QuantizedLinear leaves → dense).

    Leading stack axes compose (vmap per axis): 3-D (L, K, N) stacks and
    4-D (L, E, K, N) expert leaves both round-trip.  The transposed
    ``lm_head`` leaf is transposed back to its stored (V, D) orientation,
    so the output tree is structurally identical to the pre-quantization
    params.
    """
    def deq(kp, leaf):
        if not isinstance(leaf, QuantizedLinear):
            return leaf
        d = functools.partial(dequantize_weight, dtype=dtype)
        for _ in range(leaf.q.ndim - 2):
            d = jax.vmap(d)
        out = d(leaf)
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        if _TRANSPOSED_LEAF.match(path):
            out = jnp.swapaxes(out, -1, -2)
        return out
    return jax.tree_util.tree_map_with_path(
        deq, qparams, is_leaf=lambda x: isinstance(x, QuantizedLinear))
