"""Two-sided block-sparse Pallas matmul — the CSB + CAG unit, TPU-granular.

FlexNN's sparsity logic (§III-D): IF and FL sparsity bitmaps are ANDed into a
combined sparsity bitmap (CSB); the CAG unit generates addresses for only the
surviving pairs, so MAC cycles scale with popcount(CSB).

The MXU cannot skip individual MACs, so the TPU-native rendering works at
*block* granularity (DESIGN.md §2): per-(bm×bk) A-block and (bk×bn) B-block
bitmaps are ANDed along K per output tile, and the live K-block indices are
compressed into a scalar-prefetch index list (``BlockSparseMeta.kidx`` /
``kcnt`` — built by ``core.sparsity.build_block_sparse_meta``, the CAG
analogue).  The kernel's grid dimension over K iterates only ``max_nnz``
steps and its BlockSpec index_maps *chase the compressed indices*, so blocks
where either operand is all-zero are never fetched from HBM nor multiplied —
both the energy and the cycle win of the paper, at tile granularity.

Cycles ∝ Σ kcnt (vs tm·tn·tk dense): ``meta.skip_fraction`` is the measured
block-CSB skip rate.

Three entry points honor the same contract (see ``core.descriptors``):
``kernels.ops.block_sparse_matmul`` passes *precomputed* host-built metadata
(``build_block_sparse_meta``); the descriptor-driven dispatch
(``ops.flex_matmul`` for 2-D leaves, ``ops.flex_expert_matmul`` for the
batched-expert einsums — vmapped per expert on the XLA path, unrolled over
the static E axis here since the scalar-prefetch grid has no batching
rule — and ``ops.head_matmul`` for the transposed lm_head contraction)
builds metadata *at trace time* (``build_block_sparse_meta_jnp``)
with ``max_nnz = tk``; and the weight-plan path (``core.sparsity
.PlannedWeight`` attached at engine bring-up) supplies the weight-side
lists as jit inputs and runs the plan's *tight* static ``max_nnz`` ≤ tk —
shrinking the kernel's s-grid to the real worst-case live K-count.  Traced
``kidx``/``kcnt`` are fine (scalar-prefetch operands), only ``max_nnz`` and
the block shapes must be static.  Dead tiles (kcnt == 0) MAC one clamped
block; with data-derived bitmaps that block is all-zero on at least one
side, so the contribution is exactly 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import block_sparse_matmul_ref  # re-export oracle


def _bs_kernel(kidx_ref, kcnt_ref, a_ref, b_ref, o_ref, acc_ref, *,
               max_nnz: int):
    """Grid (tm, tn, max_nnz); s-axis walks the compressed K index list."""
    i, j, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    live = s < kcnt_ref[i, j]

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _mac():
        acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(s == max_nnz - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _bs_kernel_scaled(kidx_ref, kcnt_ref, a_ref, b_ref, s_ref, o_ref,
                      acc_ref, *, max_nnz: int):
    """The quantized variant: B tiles arrive int8 and are dequantized
    in-register (cast only — the per-output-channel scales are K-invariant,
    so the accumulator is scaled *once* at the final grid step, exactly the
    ``int8_matmul`` epilogue trick).  HBM traffic for the weight is the
    int8 payload: the ZVC skip and the int8 bytes compound.
    """
    i, j, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    live = s < kcnt_ref[i, j]

    @pl.when(s == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _mac():
        acc_ref[...] += jnp.dot(a_ref[...].astype(jnp.float32),
                                b_ref[...].astype(jnp.float32),
                                preferred_element_type=jnp.float32)

    @pl.when(s == max_nnz - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "max_nnz",
                                             "interpret", "out_dtype"))
def _block_sparse_matmul(a, b, kidx, kcnt, *, bm, bn, bk, max_nnz,
                         interpret, out_dtype):
    m, k = a.shape
    _, n = b.shape
    tm, tn, tk = m // bm, n // bn, k // bk
    grid = (tm, tn, max_nnz)

    def a_map(i, j, s, kidx_ref, kcnt_ref):
        # clamp dead steps to the last live block (never fetched into a MAC)
        return (i, kidx_ref[i, j, jnp.minimum(s, kcnt_ref[i, j] - 1)])

    def b_map(i, j, s, kidx_ref, kcnt_ref):
        return (kidx_ref[i, j, jnp.minimum(s, kcnt_ref[i, j] - 1)], j)

    def o_map(i, j, s, kidx_ref, kcnt_ref):
        return (i, j)

    return pl.pallas_call(
        functools.partial(_bs_kernel, max_nnz=max_nnz),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), a_map),
                pl.BlockSpec((bk, bn), b_map),
            ],
            out_specs=pl.BlockSpec((bm, bn), o_map),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(kidx, jnp.maximum(kcnt, 1), a, b)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "max_nnz",
                                             "interpret", "out_dtype"))
def _block_sparse_matmul_scaled(a, b, scale, kidx, kcnt, *, bm, bn, bk,
                                max_nnz, interpret, out_dtype):
    """Quantized twin of ``_block_sparse_matmul``: B is the int8 payload,
    ``scale`` (1, N) f32 rides its own (1, bn)-blocked spec indexed by j and
    is applied to the f32 accumulator at the final s step.  The scale is
    2-D because Mosaic refuses a 1-D block whose tiling differs from the
    array's XLA layout."""
    m, k = a.shape
    _, n = b.shape
    tm, tn, tk = m // bm, n // bn, k // bk
    grid = (tm, tn, max_nnz)

    def a_map(i, j, s, kidx_ref, kcnt_ref):
        return (i, kidx_ref[i, j, jnp.minimum(s, kcnt_ref[i, j] - 1)])

    def b_map(i, j, s, kidx_ref, kcnt_ref):
        return (kidx_ref[i, j, jnp.minimum(s, kcnt_ref[i, j] - 1)], j)

    def s_map(i, j, s, kidx_ref, kcnt_ref):
        return (0, j)

    def o_map(i, j, s, kidx_ref, kcnt_ref):
        return (i, j)

    return pl.pallas_call(
        functools.partial(_bs_kernel_scaled, max_nnz=max_nnz),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, bk), a_map),
                pl.BlockSpec((bk, bn), b_map),
                pl.BlockSpec((1, bn), s_map),
            ],
            out_specs=pl.BlockSpec((bm, bn), o_map),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
    )(kidx, jnp.maximum(kcnt, 1), a, b, scale)


def block_sparse_matmul(a: jax.Array, b: jax.Array, meta, *,
                        interpret: bool = False,
                        out_dtype=None, scale=None) -> jax.Array:
    """C = A @ B skipping CSB-dead (A-block, B-block) pairs.

    Shapes must be divisible by the meta block sizes (the metadata builder
    padded its bitmaps; pad inputs the same way if needed).

    ``scale`` (N,) f32 selects the quantized path: ``b`` is an int8
    payload, dequantized in-register with the per-output-channel scales
    applied once to the f32 accumulator in the kernel epilogue (exact —
    scales are K-invariant).
    """
    tm, tk = meta.a_bitmap.shape
    _, tn = meta.b_bitmap.shape
    m, k = a.shape
    n = b.shape[1]
    bm, bk, bn = m // tm, k // tk, n // tn
    assert bm * tm == m and bk * tk == k and bn * tn == n, \
        (a.shape, b.shape, meta.a_bitmap.shape, meta.b_bitmap.shape)
    out_dtype = out_dtype or a.dtype
    if scale is not None:
        assert scale.shape == (n,), (scale.shape, n)
        return _block_sparse_matmul_scaled(
            a, b, scale.astype(jnp.float32).reshape(1, n), meta.kidx,
            meta.kcnt, bm=bm, bn=bn, bk=bk, max_nnz=meta.max_nnz,
            interpret=interpret, out_dtype=out_dtype)
    return _block_sparse_matmul(
        a, b, meta.kidx, meta.kcnt, bm=bm, bn=bn, bk=bk,
        max_nnz=meta.max_nnz, interpret=interpret, out_dtype=out_dtype)
