"""Plain float32 StableLM-2 forward pass, and the benchmark's weights.

The reference follows the published architecture (hf
stabilityai/stablelm-2-1_6b, ``StableLmForCausalLM``): pre-LayerNorm
blocks (eps 1e-5) with a sequential residual, multi-head attention whose
rotary embedding covers the first quarter of each head (rotate-half
convention, base ``rope_theta``), a SwiGLU MLP ``down(silu(gate(x)) *
up(x))``, a final LayerNorm and an untied output head.  It imports nothing
of the program under test.  Departures of the served model from the
published one are listed in the configuration file (``departures``).

Every matmul runs in float32 at ``Precision.HIGHEST``; weights are cast to
float32 one layer at a time inside the layer scan, and sequences go
through one at a time, so the pass fits beside the served weights.

``make_params`` draws the weights from the seed on the device, in the
dtype they are served in, laid out as the program's parameter tree takes
them: ``attn.wkv`` holds [K | V] along its output axis, each of
``n_kv_heads * head_dim`` columns; ``mlp.w_in`` is the up projection and
``mlp.w_gate`` the gate.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


def make_params(model: Dict, key: jax.Array, dtype) -> Dict:
    """Seeded weights for ``model`` (the configuration's ``model`` group).
    Matmul weights are normal with std fan_in^-1/2, embeddings 0.02; the
    LayerNorm scales are 1 + 0.1 N(0, 1) and biases 0.1 N(0, 1), float32."""
    d, f, v, nl = (model["d_model"], model["d_ff"], model["vocab"],
                   model["n_layers"])
    hd = model.get("head_dim") or d // model["n_heads"]
    qd, kvd = model["n_heads"] * hd, model["n_kv_heads"] * hd
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std, dt=dtype):
        return (jax.random.normal(next(ks), shape, jnp.float32)
                * std).astype(dt)

    def norm(*lead):
        return {"scale": 1.0 + normal(lead + (d,), 0.1, jnp.float32),
                "bias": normal(lead + (d,), 0.1, jnp.float32)}

    layers = {
        "ln1": norm(nl),
        "attn": {"wq": normal((nl, d, qd), d ** -0.5),
                 "wkv": normal((nl, d, 2 * kvd), d ** -0.5),
                 "wo": normal((nl, qd, d), qd ** -0.5)},
        "ln2": norm(nl),
        "mlp": {"w_in": normal((nl, d, f), d ** -0.5),
                "w_gate": normal((nl, d, f), d ** -0.5),
                "w_out": normal((nl, f, d), f ** -0.5)},
    }
    return {"embed": normal((v, d), 0.02), "stack": {"layers": layers},
            "final_norm": norm(), "lm_head": normal((v, d), 0.02)}


def _layernorm(p, x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _rotary(x, positions, rot_dim: int, theta: float):
    """Rotate the first ``rot_dim`` features of x (S, H, hd)."""
    inv = 1.0 / theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                          / rot_dim)
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    xr, xp = x[..., :rot_dim], x[..., rot_dim:]
    half = rot_dim // 2
    rotated = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * cos + rotated * sin, xp], -1)


def _layer(model: Dict, lp, x, positions):
    s, d = x.shape
    h_, kvh = model["n_heads"], model["n_kv_heads"]
    hd = model.get("head_dim") or d // h_
    rot = int(hd * model.get("partial_rotary_factor", 0.25))
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    y = _layernorm(lp["ln1"], x)
    q = jnp.dot(y, lp["attn"]["wq"], precision=HI).reshape(s, h_, hd)
    kv = jnp.dot(y, lp["attn"]["wkv"], precision=HI).reshape(s, 2, kvh, hd)
    k, v = kv[:, 0], kv[:, 1]
    q = _rotary(q, positions, rot, model["rope_theta"])
    k = _rotary(k, positions, rot, model["rope_theta"])
    group = h_ // kvh
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * hd ** -0.5
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", w, v, precision=HI).reshape(s, h_ * hd)
    x = x + jnp.dot(o, lp["attn"]["wo"], precision=HI)
    y = _layernorm(lp["ln2"], x)
    up = jnp.dot(y, lp["mlp"]["w_in"], precision=HI)
    gate = jnp.dot(y, lp["mlp"]["w_gate"], precision=HI)
    return x + jnp.dot(jax.nn.silu(gate) * up, lp["mlp"]["w_out"],
                       precision=HI)


def logits(params, model: Dict, tokens: jax.Array) -> jax.Array:
    """tokens (S,) int32 at positions 0..S-1 -> float32 logits (S, V)."""
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = params["embed"][tokens].astype(jnp.float32)

    def body(h, lp):
        return _layer(model, lp, h, positions), None

    x, _ = jax.lax.scan(body, x, params["stack"]["layers"])
    fn = jax.tree.map(lambda a: a.astype(jnp.float32), params["final_norm"])
    x = _layernorm(fn, x)
    return jnp.dot(x, params["lm_head"].astype(jnp.float32).T, precision=HI)
