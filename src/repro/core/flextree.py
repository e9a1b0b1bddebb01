"""FlexTree — schedule-aware flexible-depth psum reduction (FlexNN §III-B).

Two levels, per DESIGN.md §2:

1. **Cycle model** of the hardware adder tree: flexible output tap points at
   every level (`IC_P ∈ {1..16}`, non-powers-of-2 zero-padded) vs (a) a
   neighbor-to-neighbor psum chain and (b) a fixed root-only tree.

2. **Mesh-level reduction strategies** for the JAX framework: the K/expert
   contraction partitioned ``ic_p`` ways across a mesh axis, combined by a
   selectable algorithm — ``allreduce`` (lax.psum), ``scatter``
   (psum_scatter, halves link traffic when the consumer is sharded) or
   ``tree`` (log-depth ppermute schedule — FlexTree verbatim).  Used inside
   ``shard_map`` regions.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

MAX_EXTRACT_PER_ROUND = 4     # ≤4 OF points drained from FlexTree per round
TREE_FANIN = 16               # 16 PEs per column feed the tree


# ---------------------------------------------------------------------------
# 1. Hardware cycle model
# ---------------------------------------------------------------------------

def _tap_points(ic_p: int) -> int:
    """Output tap points per round for a given IC_P (§III-B: [8,8,4,2,1]
    for IC_P = [1,2,4,8,16])."""
    ic_p_pow2 = 1 << max(0, math.ceil(math.log2(max(ic_p, 1))))
    return max(TREE_FANIN // max(ic_p_pow2, 2), 1)


def flextree_cycles(n_outputs: int, ic_p: int) -> float:
    """Cycles to reduce+drain ``n_outputs`` OF points with IC_P-deep taps."""
    per_round = min(_tap_points(ic_p), MAX_EXTRACT_PER_ROUND)
    depth = math.ceil(math.log2(max(ic_p, 2)))
    rounds = math.ceil(n_outputs / per_round)
    return rounds + depth          # pipelined: depth fills once


def fixed_tree_cycles(n_outputs: int, ic_p: int) -> float:
    """Fixed root-only tree: every output serializes through the single
    root tap and re-traverses the full depth (no level taps, no multi-
    extract) — the fixed-depth baseline of §III-B whose layer-level gap is
    the paper's 4–16× band."""
    depth = math.ceil(math.log2(TREE_FANIN))
    return n_outputs * (depth + 1)


def neighbor_chain_cycles(n_outputs: int, ic_p: int) -> float:
    """Neighbor-to-neighbor psum forwarding (Eyeriss-style), pipelined:
    successive outputs overlap their IC_P hops, so the chain drains one
    output per cycle after an IC_P-cycle fill."""
    return n_outputs + max(ic_p, 1)


def flextree_speedup_vs_fixed(n_outputs: int, ic_p: int) -> float:
    return fixed_tree_cycles(n_outputs, ic_p) / flextree_cycles(n_outputs, ic_p)


def flextree_speedup_vs_chain(n_outputs: int, ic_p: int) -> float:
    return neighbor_chain_cycles(n_outputs, ic_p) / flextree_cycles(n_outputs, ic_p)


# ---------------------------------------------------------------------------
# 2. Mesh-level reduction strategies (shard_map collectives)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ReduceConfig:
    axis_name: str
    ic_p: int                     # devices participating (1 = no reduction)
    strategy: str = "allreduce"   # allreduce | scatter | tree


def reduce_psum(x: jax.Array, cfg: ReduceConfig,
                scatter_dim: int = 0) -> jax.Array:
    """Combine partial sums across ``cfg.axis_name`` per the strategy.

    Must be called inside a ``shard_map`` region whose mesh binds
    ``cfg.axis_name``.  ``tree`` implements FlexTree's log-depth combine as a
    recursive-halving schedule of collective_permutes.
    """
    if cfg.ic_p <= 1:
        return x
    if cfg.strategy == "allreduce":
        return jax.lax.psum(x, cfg.axis_name)
    if cfg.strategy == "scatter":
        return jax.lax.psum_scatter(x, cfg.axis_name,
                                    scatter_dimension=scatter_dim,
                                    tiled=True)
    if cfg.strategy == "tree":
        return _tree_allreduce(x, cfg.axis_name, cfg.ic_p)
    raise ValueError(f"unknown strategy {cfg.strategy!r}")


def _tree_allreduce(x: jax.Array, axis_name: str, size: int) -> jax.Array:
    """Log-depth recursive-doubling all-reduce via collective_permute.

    depth = ceil(log2(size)) rounds; round d exchanges with the partner at
    XOR distance 2^d — the ICI rendering of the adder-tree levels in Fig 7.
    Non-power-of-2 sizes fall back to lax.psum (the zero-padding analogue).
    """
    if size & (size - 1):
        return jax.lax.psum(x, axis_name)
    idx = jax.lax.axis_index(axis_name)
    del idx  # partner pairs are static permutations
    depth = int(math.log2(size))
    for d in range(depth):
        stride = 1 << d
        perm = []
        for i in range(size):
            perm.append((i, i ^ stride))
        x = x + jax.lax.ppermute(x, axis_name, perm)
    return x


def link_bytes(strategy: str, payload_bytes: float, ic_p: int) -> float:
    """Per-device ICI traffic of each combine strategy (napkin model used by
    the schedule optimizer and recorded in the §Perf log)."""
    if ic_p <= 1:
        return 0.0
    g = ic_p
    if strategy == "allreduce":      # ring: 2·(g-1)/g
        return 2.0 * payload_bytes * (g - 1) / g
    if strategy == "scatter":        # reduce-scatter half of the ring
        return payload_bytes * (g - 1) / g
    if strategy == "tree":           # recursive doubling: log2(g) full sends
        return payload_bytes * math.ceil(math.log2(g))
    raise ValueError(strategy)


def best_strategy(payload_bytes: float, ic_p: int,
                  consumer_sharded: bool) -> str:
    """FlexTree's depth selection re-targeted: pick the cheapest combine."""
    if ic_p <= 1:
        return "allreduce"
    candidates = ["allreduce", "tree"]
    if consumer_sharded:
        candidates.append("scatter")
    return min(candidates, key=lambda s: link_bytes(s, payload_bytes, ic_p))
