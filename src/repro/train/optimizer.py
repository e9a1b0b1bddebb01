"""AdamW optimizer + schedules — pure pytree implementation (no optax dep).

Kept deliberately framework-grade: f32 master moments regardless of param
dtype, decoupled weight decay, global-norm clipping, cosine schedule with
linear warmup.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class OptState(NamedTuple):
    step: jax.Array            # ()
    mu: Dict                   # first moment  (f32, param-shaped)
    nu: Dict                   # second moment (f32, param-shaped)


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1


def cosine_lr(cfg: AdamWConfig, step: jax.Array) -> jax.Array:
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(cfg.warmup_steps, 1)
    prog = (step - cfg.warmup_steps) / jnp.maximum(
        cfg.total_steps - cfg.warmup_steps, 1)
    prog = jnp.clip(prog, 0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)


def init_opt_state(params) -> OptState:
    # zeros_like keeps each parameter's sharding: moments live where their
    # parameter does
    zeros = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    return OptState(step=jnp.zeros((), jnp.int32), mu=zeros,
                    nu=jax.tree.map(jnp.copy, zeros))


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def clip_by_global_norm(grads, max_norm: float) -> Tuple[Dict, jax.Array]:
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale.astype(g.dtype), grads), norm


def adamw_update(cfg: AdamWConfig, params, grads, state: OptState
                 ) -> Tuple[Dict, OptState, Dict[str, jax.Array]]:
    """One AdamW step.  Returns (new_params, new_state, metrics)."""
    grads, gnorm = clip_by_global_norm(grads, cfg.clip_norm)
    step = state.step + 1
    lr = cosine_lr(cfg, step)
    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        gf = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * gf
        v = b2 * v + (1 - b2) * gf * gf
        mh = m / bc1
        vh = v / bc2
        delta = mh / (jnp.sqrt(vh) + cfg.eps)
        if p.ndim >= 2:            # decay matrices only (standard practice)
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype), m, v

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.mu)
    flat_v = treedef.flatten_up_to(state.nu)
    out = [upd(p, g, m, v) for p, g, m, v in
           zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, OptState(step=step, mu=new_m, nu=new_v), metrics
