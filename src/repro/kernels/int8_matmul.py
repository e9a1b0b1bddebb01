"""INT8-weight matmul Pallas kernel — dequantize-in-VMEM, MXU-adjacent.

FlexNN computes INT8 natively in the PE array (§III-A); the TPU analogue
keeps weights INT8 in HBM (half the bf16 bytes — decode is weight-bandwidth
bound, so this directly moves the §Roofline memory term) and dequantizes
tiles *after* the HBM→VMEM transfer: the int8 tile is converted and scaled
in-register right before the MXU dot, so HBM never sees the f32/bf16 copy.

Grid: output-stationary (m, n, k); per-output-channel scales applied once
per (n) block on the f32 accumulator at the final K step (scales are
K-invariant, so scaling the accumulator is exact).

Oracle: ``ref.int8_matmul_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _int8_kernel(a_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # dequant in-register: int8 tile → f32; accumulate raw (unscaled)
    w = q_ref[...].astype(jnp.float32)
    acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32), w,
                            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret",
                                             "out_dtype"))
def _int8_matmul(a, q, scale, *, bm, bn, bk, interpret, out_dtype):
    # scale (1, N): Mosaic refuses a 1-D block whose tiling differs from
    # the array's XLA layout, so the per-channel scales ride as one row
    m, k = a.shape
    _, n = q.shape
    pm, pk, pn = (-m) % bm, (-k) % bk, (-n) % bn
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        q = jnp.pad(q, ((0, pk), (0, pn)))
    if pn:
        scale = jnp.pad(scale, ((0, 0), (0, pn)))
    mp, kp = a.shape
    np_ = q.shape[1]
    tm, tn, tk = mp // bm, np_ // bn, kp // bk

    out = pl.pallas_call(
        functools.partial(_int8_kernel, n_k=tk),
        grid=(tm, tn, tk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[_vmem((bm, bn))],
        interpret=interpret,
        compiler_params=_dims(("parallel", "parallel", "arbitrary"),
                              interpret),
    )(a, q, scale)
    return out[:m, :n]


def _vmem(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


def _dims(sem, interpret):
    if interpret:
        return None
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=sem)


def int8_matmul(a: jax.Array, qw, *, bm: int = 128, bn: int = 128,
                bk: int = 128, interpret: bool = False,
                out_dtype=None) -> jax.Array:
    """C[M,N] = A[M,K] @ dequant(QW) with per-N-channel scales.

    ``qw`` is a ``quant.QuantizedLinear`` (q int8 (K,N), scale f32 (N,)).
    """
    m, k = a.shape
    n = qw.q.shape[1]
    out_dtype = out_dtype or a.dtype
    return _int8_matmul(a, qw.q, qw.scale.reshape(1, n),
                        bm=min(bm, m), bn=min(bn, n), bk=min(bk, k),
                        interpret=interpret, out_dtype=out_dtype)
