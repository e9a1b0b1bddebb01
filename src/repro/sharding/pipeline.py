"""Pipeline parallelism over a mesh axis (GPipe-style microbatch pipeline).

The optional third way to use the "pod" axis (DESIGN.md §7): split the layer
stack into S contiguous stages, one per pod, and stream M microbatches
through them with ``lax.ppermute`` hops between neighbours.  Runs inside
``shard_map`` over the pipeline axis; each device holds only its stage's
parameters (1/S of the stack) — the pipeline analogue of FlexNN's
loop *partitioning* applied to the layer dimension.

Schedule: plain GPipe — M + S − 1 ticks, bubble fraction (S−1)/(M+S−1).
The driver below is inference/forward-oriented (activation streaming);
training composes it with grad-accumulation outside.

    y = pipeline_apply(layer_fn, stage_params, x, axis_name="pod",
                       n_micro=M)

``stage_params`` leaves carry a leading per-stage dim sharded over
``axis_name``; inside the shard_map body each stage sees its local slice
and scans its layers.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def split_stages(stacked_params, n_stages: int):
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-major params."""
    def reshape(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])
    return jax.tree.map(reshape, stacked_params)


def pipeline_apply(layer_fn: Callable, stage_params, x: jax.Array, *,
                   mesh: Mesh, axis_name: str = "pod",
                   n_micro: int = 4) -> jax.Array:
    """Run ``x`` through all S×(L/S) layers, pipelined over ``axis_name``.

    layer_fn(layer_params, h) -> h — one layer.
    stage_params: (S, L/S, ...) pytree (S sharded over ``axis_name``).
    x: (B, ...) global batch; B % n_micro == 0.
    """
    n_stages = mesh.shape[axis_name]
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    mb = b // n_micro

    def body(params_local, x_local):
        # params_local: (1, L/S, ...) — this device's stage
        # x_local: full batch copy (replicated over the pipe axis)
        stage = jax.lax.axis_index(axis_name)
        micro = x_local.reshape(n_micro, mb, *x_local.shape[1:])

        def run_stage(h):
            def step(carry, lp):
                return layer_fn(lp, carry), None
            out, _ = jax.lax.scan(
                step, h, jax.tree.map(lambda p: p[0], params_local))
            return out

        n_ticks = n_micro + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            inflight, outputs = carry
            # stage 0 injects microbatch t (if still in range)
            inject = micro[jnp.minimum(t, n_micro - 1)]
            h_in = jnp.where(stage == 0, inject, inflight)
            h_out = run_stage(h_in)
            # last stage emits microbatch (t - S + 1)
            out_idx = t - (n_stages - 1)
            emit = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            outputs = jax.lax.cond(
                jnp.logical_and(emit, out_idx < n_micro),
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, h_out, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outputs)
            # pass activations to the next stage
            inflight = jax.lax.ppermute(h_out, axis_name, fwd_perm)
            return (inflight, outputs), None

        init = (jnp.zeros_like(micro[0]),
                jnp.zeros((n_micro, mb, *x_local.shape[1:]), x_local.dtype))
        (_, outputs), _ = jax.lax.scan(tick, init, jnp.arange(n_ticks))
        # outputs accumulate only on the last stage (zeros elsewhere);
        # psum over the pipe axis broadcasts them to every stage
        outputs = jax.lax.psum(outputs, axis_name)
        return outputs.reshape(b, *x_local.shape[1:])

    smapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P(),
        check_vma=False)
    return smapped(stage_params, x)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble overhead — the schedule-selection napkin number."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
