"""Property tests for the two-sided sparsity machinery (hypothesis)."""
from hypothesis import given, settings, strategies as st
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import sparsity as S
from repro.kernels.ref import block_sparse_matmul_ref

ARRS = st.integers(1, 6).flatmap(
    lambda r: st.integers(1, 6).map(lambda c: (r * 8, c * 8)))


def _sparse_array(rng, shape, density):
    x = rng.normal(size=shape).astype(np.float32)
    mask = rng.random(shape) < density
    return x * mask


# ---------------------------------------------------------------------------
# ZVC codec
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(shape=ARRS, density=st.floats(0.0, 1.0), seed=st.integers(0, 2**16))
def test_zvc_np_roundtrip(shape, density, seed):
    rng = np.random.default_rng(seed)
    x = _sparse_array(rng, shape, density)
    vals, bm = S.zvc_encode_np(x)
    assert vals.size == int(np.count_nonzero(x))
    np.testing.assert_array_equal(S.zvc_decode_np(vals, bm), x)


@settings(max_examples=20, deadline=None)
@given(shape=ARRS, density=st.floats(0.0, 1.0), seed=st.integers(0, 2**16))
def test_zvc_jnp_roundtrip(shape, density, seed):
    rng = np.random.default_rng(seed)
    x = _sparse_array(rng, shape, density)
    packed, bm, nnz = S.zvc_encode(jnp.asarray(x))
    assert int(nnz) == int(np.count_nonzero(x))
    out = S.zvc_decode(packed, bm)
    np.testing.assert_array_equal(np.asarray(out), x)
    # packed prefix holds the non-zeros in scan order (Fig 12 layout)
    np.testing.assert_array_equal(np.asarray(packed)[:int(nnz)],
                                  x.reshape(-1)[x.reshape(-1) != 0])


def test_zvc_compressed_bytes():
    x = np.zeros((16, 16), np.float32)
    x[0, 0] = 1.0
    # 1 non-zero byte + 256-bit bitmap
    assert S.zvc_compressed_bytes(x, elem_bytes=1) == 1 + 256 / 8


# ---------------------------------------------------------------------------
# Combined sparsity bitmap
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(n=st.integers(1, 256), seed=st.integers(0, 2**16),
       da=st.floats(0.0, 1.0), dw=st.floats(0.0, 1.0))
def test_csb_popcount(n, seed, da, dw):
    rng = np.random.default_rng(seed)
    a = rng.random(n) < da
    w = rng.random(n) < dw
    pc = int(S.csb_popcount(jnp.asarray(a), jnp.asarray(w)))
    assert pc == int(np.sum(a & w))
    assert pc <= min(a.sum(), w.sum())       # CSB never exceeds either side


# ---------------------------------------------------------------------------
# Magnitude pruning
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sp", [0.25, 0.5, 0.75])
def test_prune_magnitude_level(rng, sp):
    w = rng.normal(size=(64, 64)).astype(np.float32)
    out = S.prune_magnitude(w, sp)
    got = 1.0 - np.count_nonzero(out) / out.size
    assert abs(got - sp) < 0.05
    # surviving entries are untouched
    nz = out != 0
    np.testing.assert_array_equal(out[nz], w[nz])


def test_prune_magnitude_block(rng):
    w = rng.normal(size=(128, 128)).astype(np.float32)
    out = S.prune_magnitude(w, 0.5, block=(32, 32))
    bm = S.block_bitmap(out, 32, 32)
    # roughly half the 16 blocks survive, and zeroed blocks are fully zero
    assert 0.25 <= bm.mean() <= 0.75
    blocks = out.reshape(4, 32, 4, 32)
    for i in range(4):
        for j in range(4):
            if not bm[i, j]:
                assert np.all(blocks[i, :, j, :] == 0)


# ---------------------------------------------------------------------------
# Block-sparse metadata (the CAG analogue)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16), sp=st.floats(0.0, 0.95))
def test_block_meta_consistency(seed, sp):
    rng = np.random.default_rng(seed)
    a = S.prune_magnitude(rng.normal(size=(128, 128)).astype(np.float32),
                          sp, block=(32, 32))
    b = S.prune_magnitude(rng.normal(size=(128, 128)).astype(np.float32),
                          sp, block=(32, 32))
    meta = S.build_block_sparse_meta(a, b, 32, 32, 32)
    a_bm = np.asarray(meta.a_bitmap)
    b_bm = np.asarray(meta.b_bitmap)
    csb = a_bm[:, None, :] & b_bm.T[None, :, :]
    np.testing.assert_array_equal(np.asarray(meta.kcnt), csb.sum(-1))
    # every listed K index is live in the CSB
    kidx = np.asarray(meta.kidx)
    kcnt = np.asarray(meta.kcnt)
    for mi in range(kidx.shape[0]):
        for ni in range(kidx.shape[1]):
            for s_ in range(kcnt[mi, ni]):
                assert csb[mi, ni, kidx[mi, ni, s_]]
    assert 0.0 <= meta.skip_fraction <= 1.0


@settings(max_examples=30, deadline=None)
@given(tm=st.integers(1, 5), tk=st.integers(1, 6), tn=st.integers(1, 5),
       da=st.floats(0.0, 1.0), dw=st.floats(0.0, 1.0),
       tight=st.booleans(), seed=st.integers(0, 2**16))
def test_jnp_meta_builder_matches_numpy_oracle(tm, tk, tn, da, dw, tight,
                                               seed):
    """Property: the trace-time builder agrees entry-for-entry with the
    numpy oracle across random shapes/densities — with the oracle's tight
    ``max_nnz`` (which may be < tk) and with the tk upper bound, whose extra
    padded entries must stay zero.  Density 0 covers all-zero tiles."""
    rng = np.random.default_rng(seed)
    bm, bk, bn = 8, 8, 8
    a = rng.normal(size=(tm * bm, tk * bk)).astype(np.float32) \
        * (rng.random((tm * bm, tk * bk)) < da)
    b = rng.normal(size=(tk * bk, tn * bn)).astype(np.float32) \
        * (rng.random((tk * bk, tn * bn)) < dw)
    meta_np = S.build_block_sparse_meta(a, b, bm, bk, bn)
    nnz = meta_np.max_nnz if tight else tk
    meta_j = S.build_block_sparse_meta_jnp(meta_np.a_bitmap,
                                           meta_np.b_bitmap, max_nnz=nnz)
    np.testing.assert_array_equal(np.asarray(meta_j.kcnt),
                                  np.asarray(meta_np.kcnt))
    np.testing.assert_array_equal(
        np.asarray(meta_j.kidx)[..., :meta_np.max_nnz],
        np.asarray(meta_np.kidx))
    assert np.all(np.asarray(meta_j.kidx)[..., meta_np.max_nnz:] == 0)
    # both describe the exact product through the oracle kernel
    out = np.asarray(block_sparse_matmul_ref(jnp.asarray(a), jnp.asarray(b),
                                             meta_j))
    np.testing.assert_allclose(out, a @ b, rtol=2e-5, atol=2e-4)


def test_meta_builders_all_zero_tile():
    """Edge case: fully zero operands — kcnt all zero, max_nnz floors at 1,
    the kernel contract still yields an exactly-zero product."""
    a = np.zeros((16, 16), np.float32)
    b = np.zeros((16, 8), np.float32)
    meta_np = S.build_block_sparse_meta(a, b, 8, 8, 8)
    assert meta_np.max_nnz == 1
    assert int(np.asarray(meta_np.kcnt).sum()) == 0
    meta_j = S.build_block_sparse_meta_jnp(meta_np.a_bitmap,
                                           meta_np.b_bitmap,
                                           max_nnz=meta_np.max_nnz)
    np.testing.assert_array_equal(np.asarray(meta_j.kidx),
                                  np.asarray(meta_np.kidx))
    np.testing.assert_array_equal(np.asarray(meta_j.kcnt),
                                  np.asarray(meta_np.kcnt))


@settings(max_examples=15, deadline=None)
@given(tk=st.integers(2, 6), tn=st.integers(1, 5),
       max_live=st.integers(1, 3), seed=st.integers(0, 2**16))
def test_prune_k_blocks_bounds_live_count(tk, tn, max_live, seed):
    rng = np.random.default_rng(seed)
    bk = bn = 8
    w = rng.normal(size=(tk * bk, tn * bn)).astype(np.float32)
    out = S.prune_k_blocks(w, bk, bn, max_live)
    bm_ = S.block_bitmap(out, bk, bn)
    assert int(bm_.sum(axis=0).max()) <= min(max_live, tk)
    # surviving blocks are untouched
    nz = out != 0
    np.testing.assert_array_equal(out[nz], w[nz])


# ---------------------------------------------------------------------------
# PE cycle model
# ---------------------------------------------------------------------------

def test_simulate_pe_cycles_dense_exact():
    assert S.simulate_pe_cycles(256, 16, 10, 1.0, macs_per_pe=8) \
        == 10 * 256 / 8


def test_simulate_pe_cycles_monotone_in_density():
    cycles = [S.simulate_pe_cycles(256, 16, 10, d) for d in
              (0.1, 0.3, 0.5, 0.8, 1.0)]
    assert all(a <= b + 1e-9 for a, b in zip(cycles, cycles[1:]))


def test_simulate_pe_cycles_imbalance_penalty():
    """More lockstep PEs -> higher expected max -> more cycles."""
    few = S.simulate_pe_cycles(256, 2, 10, 0.5)
    many = S.simulate_pe_cycles(256, 64, 10, 0.5)
    assert many >= few


def test_simulate_pe_cycles_mc_close_to_analytic():
    ana = S.simulate_pe_cycles(512, 16, 64, 0.4)
    mc = S.simulate_pe_cycles(512, 16, 64, 0.4, mc=True)
    assert abs(ana - mc) / mc < 0.15


def test_relu_activation_bitmap():
    x = jnp.asarray([-1.0, 0.0, 0.5, 2.0, -0.05])
    np.testing.assert_array_equal(
        np.asarray(S.relu_activation_bitmap(x, threshold=0.1)),
        [True, False, True, True, False])
