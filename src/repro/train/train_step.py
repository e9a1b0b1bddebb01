"""Train-step builders: microbatched (grad-accum) pjit step + DP-compressed
shard_map step.

The pjit step is the production path: params/opt-state sharded by the
partition rules (FSDP+TP), batch sharded over (pod, data), gradient
reduction left to XLA (overlapped with backward by the latency-hiding
scheduler).  ``n_micro`` gradient-accumulation microbatches run under
``lax.scan`` so the residual working set is a microbatch, not the global
batch (DESIGN.md D4).

The shard_map step is the compressed-collective path (pure-DP): per-device
grads are reduced with the EF-int8 / ZVC-top-k wire formats from
``grad_compress`` — FlexNN's compressed-domain data movement applied to the
gradient traffic (§Perf lever).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import model as model_lib
from repro.models.unroll import maybe_unrolled_scan
from repro.sharding.partition import Rules, use_rules
from repro.train.grad_compress import CompressConfig, compressed_mean
from repro.train.optimizer import AdamWConfig, OptState, adamw_update


def loss_for(cfg: ArchConfig, shape: ShapeConfig) -> Callable:
    def loss_fn(params, batch):
        return model_lib.train_loss(
            params, cfg, batch, remat=shape.remat,
            loss_chunk=shape.loss_chunk, q_chunk=shape.attn_chunk)
    return loss_fn


def _microbatch(batch: Dict, n_micro: int) -> Dict:
    """Split the global batch's leading batch dim into (n_micro, b/n, ...).

    ``mrope_positions`` carries its batch dim at axis 1.
    """
    def split(name, x):
        if name == "mrope_positions":
            b = x.shape[1]
            return jnp.moveaxis(
                x.reshape(x.shape[0], n_micro, b // n_micro, *x.shape[2:]),
                1, 0)
        b = x.shape[0]
        return x.reshape(n_micro, b // n_micro, *x.shape[1:])
    return {k: split(k, v) for k, v in batch.items()}


def make_step_fn(cfg: ArchConfig, shape: ShapeConfig, opt_cfg: AdamWConfig):
    """The pure step: (params, opt_state, batch) -> (params, opt_state, m)."""
    loss_fn = loss_for(cfg, shape)
    n_micro = max(shape.n_micro, 1)
    # grad accumulation/reduction dtype: bf16 halves the DP reduction bytes
    # (§Perf lever — the ZVC compressed-movement idea applied to gradients;
    # the optimizer's f32 moments restore precision downstream).
    acc_dtype = jnp.bfloat16 if shape.grad_dtype == "bf16" else jnp.float32

    def step(params, opt_state: OptState, batch):
        if n_micro > 1:
            micro = _microbatch(batch, n_micro)

            def body(carry, mb):
                g_acc, l_acc = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(acc_dtype), g_acc, g)
                return (g_acc, l_acc + l), None

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dtype),
                              params)
            (grads, loss), _ = maybe_unrolled_scan(
                body, (g0, jnp.zeros((), jnp.float32)), micro)
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss = loss / n_micro
        else:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            grads = jax.tree.map(lambda g: g.astype(acc_dtype), grads)
        params, opt_state, metrics = adamw_update(opt_cfg, params, grads,
                                                  opt_state)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return step


def build_train_step(cfg: ArchConfig, shape: ShapeConfig,
                     opt_cfg: AdamWConfig, mesh: Optional[Mesh] = None,
                     rules: Optional[Rules] = None, *,
                     donate: bool = True):
    """pjit-wrapped step.  Without a mesh, returns a plain jit step (CPU)."""
    raw = make_step_fn(cfg, shape, opt_cfg)

    if mesh is None or rules is None:
        return jax.jit(raw, donate_argnums=(0, 1) if donate else ())

    def step(params, opt_state, batch):
        with use_rules(rules):
            return raw(params, opt_state, batch)

    from repro.sharding.partition import batch_shardings
    specs = model_lib.input_specs(cfg, shape)
    return jax.jit(
        step,
        in_shardings=(None, None, batch_shardings(specs, mesh)),
        donate_argnums=(0, 1) if donate else (),
    )


# ---------------------------------------------------------------------------
# DP shard_map step with compressed gradient collectives
# ---------------------------------------------------------------------------

def build_dp_compressed_step(cfg: ArchConfig, shape: ShapeConfig,
                             opt_cfg: AdamWConfig, mesh: Mesh,
                             compress: CompressConfig):
    """Pure-DP train step: params replicated, batch sharded over all mesh
    axes, per-device grads combined by the compressed wire format.

    State = (params, opt_state, err) — err is the error-feedback carry.
    """
    loss_fn = loss_for(cfg, shape)
    axes = tuple(mesh.axis_names)
    compress = CompressConfig(mode=compress.mode,
                              topk_frac=compress.topk_frac,
                              axis_name=axes)

    def device_step(params, opt_state, err, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        flat_g, treedef = jax.tree.flatten(grads)
        flat_e = treedef.flatten_up_to(err)
        red, new_e = [], []
        for g, e in zip(flat_g, flat_e):
            r, ne = compressed_mean(g, e, compress)
            red.append(r.astype(g.dtype))
            new_e.append(ne)
        grads = treedef.unflatten(red)
        err = treedef.unflatten(new_e)
        loss = jax.lax.pmean(loss, axes)
        params, opt_state, metrics = adamw_update(opt_cfg, params, grads,
                                                  opt_state)
        metrics["loss"] = loss
        return params, opt_state, err, metrics

    batch_spec = {k: (P(None, axes) if k == "mrope_positions"
                      else P(axes)) for k in
                  model_lib.input_specs(cfg, shape)}

    smapped = jax.shard_map(
        device_step, mesh=mesh,
        in_specs=(P(), P(), P(), batch_spec),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(smapped, donate_argnums=(0, 1, 2))
