"""Logical-axis sharding rules (DP / FSDP / TP / EP / SP).

The model code annotates activations with *logical* axes via ``shard(x,
"batch", None, "heads", None)``; a rule set maps logical names to mesh axes.
Parameters get ``PartitionSpec``s from path-pattern rules.  This mirrors the
FlexNN framing: loop *partitioning* across the PE array becomes tensor-dim
partitioning across the device mesh (DESIGN.md §2).

Rule sets are per-(shape-kind); the schedule optimizer / hillclimb can
override individual entries (a "beyond-paper" lever recorded in §Perf).
"""
from __future__ import annotations

import contextlib
import re
import threading
from dataclasses import dataclass, field, replace
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]

_state = threading.local()


@dataclass(frozen=True)
class Rules:
    """logical axis -> mesh axis (or tuple), plus param path rules."""
    logical: Dict[str, MeshAxes]
    # (regex over param path, PartitionSpec) — first match wins
    params: Tuple[Tuple[str, P], ...]
    mesh: Optional[Mesh] = None

    def axis(self, name: Optional[str]) -> MeshAxes:
        if name is None:
            return None
        return self.logical.get(name)

    def spec(self, *logical_axes: Optional[str]) -> P:
        return P(*[self.axis(a) for a in logical_axes])


def current_rules() -> Optional[Rules]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[Rules]):
    prev = current_rules()
    _state.rules = rules
    try:
        yield rules
    finally:
        _state.rules = prev


def shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Constrain ``x``'s sharding if rules are active; no-op otherwise.

    Axes whose mesh size does not divide the dim are dropped (e.g. the
    "seq" axis on a single-token decode step)."""
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return x
    if x.ndim != len(logical_axes):
        return x
    spec = _sanitize(rules.spec(*logical_axes), x.shape, rules.mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, spec))


# ---------------------------------------------------------------------------
# Rule sets
# ---------------------------------------------------------------------------

def _batch_axes(mesh: Mesh) -> MeshAxes:
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


def _div(n: int, mesh: Mesh, axis: str = "model") -> bool:
    return axis in mesh.axis_names and n % mesh.shape[axis] == 0


def make_rules(mesh: Mesh, *, kind: str, n_heads: int, n_kv_heads: int,
               seq_shard: bool = False, fsdp: bool = True) -> Rules:
    """Build the rule set for one (arch, shape-kind, mesh) combination.

    kind:        train | prefill | decode
    seq_shard:   SP — shard the KV-cache/sequence dim over "model" (decode
                 cells with huge caches; DESIGN.md §5 D5).
    fsdp:        shard the parameter "embed" (d_model) dim over the batch
                 axes (reduce-scatter/all-gather FSDP).
    """
    batch = _batch_axes(mesh)
    heads = "model" if _div(n_heads, mesh) else None
    kv_heads = "model" if _div(n_kv_heads, mesh) else None
    fsdp_axis: MeshAxes = batch if fsdp else None

    logical: Dict[str, MeshAxes] = {
        "batch": batch,
        "seq": "model" if seq_shard else None,
        "embed": None,                 # activation d_model stays unsharded
        "heads": heads,
        "kv_heads": kv_heads,
        "head_dim": None,
        "ffn": "model",
        "vocab": "model",
        "expert": "model",
        "param_embed": fsdp_axis,      # FSDP dim on weights
        "param_ffn": "model",          # TP dim on weights
        "param_vocab": "model",
        "param_heads": "model",
        "cache_seq": "model" if seq_shard else None,
        "cache_batch": batch,
    }

    params: Tuple[Tuple[str, P], ...] = (
        # embeddings / lm head: vocab over model (chunked-CE), FSDP on d
        (r".*(embed|lm_head|emb)$", P("model", fsdp_axis)),
        # attention projections: (d_model, heads*hd) / out: (heads*hd, d)
        (r".*attn.*(wq|wkv|wk|wv)$", P(fsdp_axis, "model")),
        (r".*attn.*wo$", P("model", fsdp_axis)),
        # dense MLP: in (d, ff) / out (ff, d)
        (r".*(mlp|ffn).*(w_in|w_gate)$", P(fsdp_axis, "model")),
        (r".*(mlp|ffn).*w_out$", P("model", fsdp_axis)),
        # MoE experts: (E, d, ff)-style — experts over model (EP)
        (r".*experts.*", P("model", fsdp_axis, None)),
        (r".*router.*", P(fsdp_axis, None)),
        (r".*shared.*w_(in|gate)$", P(fsdp_axis, "model")),
        (r".*shared.*w_out$", P("model", fsdp_axis)),
        # SSM / RG-LRU: channel-parallel over model
        (r".*(ssm|rglru).*(in_proj|w_x|w_gate|in)$", P(fsdp_axis, "model")),
        (r".*(ssm|rglru).*(out_proj|w_out|out)$", P("model", fsdp_axis)),
        (r".*(ssm|rglru).*(conv|dt_bias|A_log|D|lambda|b_a|b_x).*", P("model")),
        (r".*(norm|ln|scale|bias).*", P()),          # replicated small
        (r".*", P()),                                # default: replicated
    )
    return Rules(logical=logical, params=params, mesh=mesh)


def leading_stack_dim(spec: P) -> P:
    """Prefix a PartitionSpec with None for the scan-stacked layer dim."""
    return P(*((None,) + tuple(spec)))


def param_spec(path: str, rules: Rules, stacked: bool) -> P:
    for pat, spec in rules.params:
        if re.match(pat, path):
            return leading_stack_dim(spec) if stacked else spec
    return P()


def tree_paths(tree) -> Dict[str, jax.ShapeDtypeStruct]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for kp, leaf in flat:
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        out[path] = leaf
    return out


STACKED_SEGMENTS = ("layers", "blocks", "encoder", "decoder", "groups",
                    "trailing", "dense_layers")


def partition_params(params_shapes, rules: Rules,
                     stacked_prefixes: Sequence[str] = STACKED_SEGMENTS,
                     ) -> "jax.tree_util.PyTreeDef":
    """ShapeDtypeStruct tree -> NamedSharding tree (same structure).

    A leaf is *stacked* (carries a leading scan-layer dim) when any non-leaf
    segment of its path is a stacked-collection name (``stack/layers/...``).
    """
    def assign(kp, leaf):
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        stacked = any(seg in stacked_prefixes
                      for seg in path.split("/")[:-1]) and leaf.ndim >= 1
        spec = param_spec(path, rules, stacked)
        # drop axes that exceed rank or don't divide
        spec = _sanitize(spec, leaf.shape, rules.mesh)
        return NamedSharding(rules.mesh, spec)
    return jax.tree_util.tree_map_with_path(assign, params_shapes)


def _axis_size(mesh: Mesh, ax: MeshAxes) -> int:
    if ax is None:
        return 1
    if isinstance(ax, tuple):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


def _sanitize(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    axes = list(spec) + [None] * (len(shape) - len(spec))
    axes = axes[:len(shape)]
    out = []
    for dim, ax in zip(shape, axes):
        out.append(ax if ax is not None and dim % _axis_size(mesh, ax) == 0
                   else None)
    return P(*out)


def named(mesh: Mesh, *axes) -> NamedSharding:
    return NamedSharding(mesh, P(*axes))


# ---------------------------------------------------------------------------
# Batch-input and decode-state shardings
# ---------------------------------------------------------------------------

# model-input name -> logical spec ("batch" resolved per mesh)
_BATCH_INPUT_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    "tokens": ("batch", None),
    "labels": ("batch", None),
    "vis_embeds": ("batch", None, None),
    "frames": ("batch", None, None),
    "mrope_positions": (None, "batch", None),
    "pos": (),
}

# decode-state param-path patterns (leading layer-stack dim prepended):
#   kv caches   (B, C, KVH·hd)  : batch, cache_seq, -
#   cross-attention memory (B, C, KVH, hd) : batch, cache_seq, -, -
#   ssm state   (B, H, P, N)    : batch, model(heads), -, -
#   conv state  (B, K-1, C)     : batch, -, model(channels)
#   rglru h     (B, W)          : batch, model
_STATE_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r".*memory.*/(k|v)$", ("batch", "cache_seq", None, None)),
    (r".*(self|layers|groups).*/(k|v)$", ("batch", "cache_seq", None)),
    (r".*ssm$", ("batch", "heads", None, None)),
    (r".*conv$", ("batch", None, "ffn")),
    (r".*/h$", ("batch", "ffn")),
)


def batch_shardings(specs, mesh: Mesh, *, seq_shard: bool = False):
    """NamedShardings for a model-input dict (incl. nested decode state)."""
    batch = _batch_axes(mesh)
    logical = {"batch": batch,
               "cache_seq": "model" if seq_shard else None,
               "heads": "model", "ffn": "model"}

    def resolve(axes, shape):
        mesh_axes = [logical.get(a, None) if isinstance(a, str) else None
                     for a in axes]
        return _sanitize(P(*mesh_axes), shape, mesh)

    def assign(kp, leaf):
        path = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)
        top = path.split("/")[0]
        if top in _BATCH_INPUT_AXES:
            return NamedSharding(mesh, resolve(_BATCH_INPUT_AXES[top],
                                               leaf.shape))
        for pat, axes in _STATE_RULES:
            if re.match(pat, path):
                # decode states carry a leading stacked-layer dim
                full = (None,) + axes if len(axes) < leaf.ndim else axes
                return NamedSharding(mesh, resolve(full, leaf.shape))
        return NamedSharding(mesh, P())
    return jax.tree_util.tree_map_with_path(assign, specs)
