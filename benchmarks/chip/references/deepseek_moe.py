"""Plain float32 DeepSeek-MoE forward pass for one chip's share of the
routed experts, and the benchmark's weights.

The reference follows the published architecture (hf
deepseek-ai/deepseek-moe-16b-base, ``config.json``; arXiv:2401.06066):

* sizes from the configuration: ``n_layers`` blocks of width ``d_model``,
  ``n_heads`` MHA heads of ``head_dim``, full rotary embedding
  (rotate-half convention, base ``rope_theta``), RMSNorm with eps 1e-6,
  vocabulary ``vocab``, untied output head;
* every block is ``x + attn(rms(x))``, then ``+ mlp(rms(.))``; a SwiGLU is
  ``down(silu(gate x) * up x)``;
* the first blocks (``first_k_dense_replace``, 1) have a dense SwiGLU of
  width ``d_ff`` (10944);
* the others are MoE: ``p = softmax(x W_r)`` over the routed experts,
  computed in float32; the top ``TOP_K`` (6) of ``p`` as gates, not
  renormalised (``norm_topk_prob: false``, ``scoring_func: softmax``);
  ``y = sum_{e in top-6} p_e FFN_e(x) + FFN_shared(x)``, the routed
  experts of width 1408 and the shared experts one SwiGLU of width
  2 x 1408.

One chip's share: the parameters hold ``E_h`` of the ``E`` routed experts,
from ``EXPERT_OFFSET``; the router keeps all ``E`` outputs.  Routing is
over all ``E``, and only the held experts among a token's top-6 add their
part; what the experts held on other chips would add is left out, as in
the program.  ``E`` and ``E_h`` are read from the parameters' shapes (the
benchmark passes ``logits`` only the configuration's scalar keys);
``TOP_K``, ``NORM_TOPK_PROB`` and ``EXPERT_OFFSET`` are constants of the
architecture and of the benchmark's deployment, and a test ties them to
the configuration file's ``moe`` group.  It imports nothing of the
program under test.  Departures of the served model from the published
one are listed in the configuration file (``departures``).

Every matmul runs in float32 at ``Precision.HIGHEST``; weights are cast to
float32 one layer at a time inside the layer scan, and sequences go
through one at a time, so the pass fits beside the served weights.  Each
held expert runs on every token and is weighted by its gate or by zero.

``make_params`` draws the weights from the seed on the device, in the
dtype they are served in, laid out as the program's parameter tree takes
them: ``stack.dense_layers`` (the leading dense blocks) and
``stack.layers`` (the MoE blocks), each stacked over its blocks;
``attn.wkv`` holds [K | V] along its output axis; ``mlp.w_in`` /
``experts_in`` / ``shared.w_in`` are up projections and ``w_gate`` /
``experts_gate`` the gates; ``moe.router`` is float32 (D, E).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
RMS_EPS = 1e-6
TOP_K = 6
NORM_TOPK_PROB = False
EXPERT_OFFSET = 0


def make_params(model: Dict, key: jax.Array, dtype) -> Dict:
    """Seeded weights for ``model`` (the configuration's ``model`` group,
    with its ``moe`` group).  Matmul weights are normal with std
    fan_in^-1/2, embeddings 0.02, the router float32; the RMSNorm scales
    are 1 + 0.1 N(0, 1), float32."""
    d, v, nl = model["d_model"], model["vocab"], model["n_layers"]
    moe = model["moe"]
    n_dense = moe["first_dense_layers"]
    n_moe = nl - n_dense
    e_all, e_h = moe["n_experts"], moe["experts_held"]
    f, fd = moe["expert_d_ff"], model["d_ff"]
    fs = f * moe["n_shared"]
    hd = model.get("head_dim") or d // model["n_heads"]
    qd, kvd = model["n_heads"] * hd, model["n_kv_heads"] * hd
    ks = iter(jax.random.split(key, 32))

    def normal(shape, std, dt=dtype):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dt)

    def norm(*lead):
        return {"scale": 1.0 + normal(lead + (d,), 0.1, jnp.float32)}

    def attn(n):
        return {"wq": normal((n, d, qd), d ** -0.5),
                "wkv": normal((n, d, 2 * kvd), d ** -0.5),
                "wo": normal((n, qd, d), qd ** -0.5)}

    def swiglu(lead, width):
        return {"w_in": normal(lead + (d, width), d ** -0.5),
                "w_gate": normal(lead + (d, width), d ** -0.5),
                "w_out": normal(lead + (width, d), width ** -0.5)}

    dense = {"ln1": norm(n_dense), "attn": attn(n_dense),
             "ln2": norm(n_dense), "mlp": swiglu((n_dense,), fd)}
    experts = swiglu((n_moe, e_h), f)
    layers = {
        "ln1": norm(n_moe), "attn": attn(n_moe), "ln2": norm(n_moe),
        "moe": {"router": normal((n_moe, d, e_all), d ** -0.5, jnp.float32),
                "experts_in": experts["w_in"],
                "experts_gate": experts["w_gate"],
                "experts_out": experts["w_out"],
                "shared": swiglu((n_moe,), fs)},
    }
    return {"embed": normal((v, d), 0.02),
            "stack": {"dense_layers": dense, "layers": layers},
            "final_norm": norm(), "lm_head": normal((v, d), 0.02)}


def _rmsnorm(p, x):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + RMS_EPS) \
        * p["scale"]


def _rotary(x, positions, theta: float):
    """Rotate all features of x (S, H, hd), rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    half = hd // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def _swiglu(p, x):
    up = jnp.dot(x, p["w_in"], precision=HI)
    gate = jnp.dot(x, p["w_gate"], precision=HI)
    return jnp.dot(jax.nn.silu(gate) * up, p["w_out"], precision=HI)


def _attention(model: Dict, p, x, positions):
    s, d = x.shape
    h_, kvh = model["n_heads"], model["n_kv_heads"]
    hd = model.get("head_dim") or d // h_
    q = jnp.dot(x, p["wq"], precision=HI).reshape(s, h_, hd)
    kv = jnp.dot(x, p["wkv"], precision=HI).reshape(s, 2, kvh, hd)
    k, v = kv[:, 0], kv[:, 1]
    q = _rotary(q, positions, model["rope_theta"])
    k = _rotary(k, positions, model["rope_theta"])
    k = jnp.repeat(k, h_ // kvh, axis=1)
    v = jnp.repeat(v, h_ // kvh, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * hd ** -0.5
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w, v, precision=HI).reshape(s, h_ * hd)
    return jnp.dot(o, p["wo"], precision=HI)


def expert_share(p, x):
    """The routed experts' part of an MoE layer's output from the experts
    in ``p`` (held from ``EXPERT_OFFSET``), for x (S, D) float32."""
    e_h = p["experts_in"].shape[0]
    probs = jax.nn.softmax(jnp.dot(x, p["router"], precision=HI), axis=-1)
    gates, idx = jax.lax.top_k(probs, TOP_K)
    if NORM_TOPK_PROB:
        gates = gates / gates.sum(-1, keepdims=True)
    y = jnp.zeros_like(x)
    for j in range(e_h):
        weight = jnp.where(idx == EXPERT_OFFSET + j, gates, 0.0).sum(-1)
        expert = {"w_in": p["experts_in"][j], "w_gate": p["experts_gate"][j],
                  "w_out": p["experts_out"][j]}
        y = y + weight[:, None] * _swiglu(expert, x)
    return y


def _block(model: Dict, lp, x, positions, mlp):
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    x = x + _attention(model, lp["attn"], _rmsnorm(lp["ln1"], x), positions)
    return x + mlp(lp, _rmsnorm(lp["ln2"], x))


def _dense_mlp(lp, y):
    return _swiglu(lp["mlp"], y)


def _moe_mlp(lp, y):
    return expert_share(lp["moe"], y) + _swiglu(lp["moe"]["shared"], y)


def logits(params, model: Dict, tokens: jax.Array) -> jax.Array:
    """tokens (S,) int32 at positions 0..S-1 -> float32 logits (S, V)."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)
    for kind, mlp in (("dense_layers", _dense_mlp), ("layers", _moe_mlp)):
        def body(h, lp, mlp=mlp):
            return _block(model, lp, h, positions, mlp), None
        x, _ = jax.lax.scan(body, x, params["stack"][kind])
    fn = jax.tree.map(lambda a: a.astype(jnp.float32), params["final_norm"])
    x = _rmsnorm(fn, x)
    return jnp.dot(x, params["lm_head"].astype(jnp.float32).T, precision=HI)
