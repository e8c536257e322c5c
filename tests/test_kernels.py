"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAS_HYPOTHESIS = True
except ImportError:   # degrade the property test to a skip, not an error
    HAS_HYPOTHESIS = False

from repro.kernels import (fused_star_gather, fused_star_gather_ref,
                           onehot_matmul, onehot_matmul_ref, tree_predict,
                           tree_predict_ref)
from repro.core.fusion import random_tree


# ------------------------------------------------------------ onehot_matmul
@pytest.mark.parametrize("n,r,d", [
    (8, 16, 8), (128, 512, 128), (130, 513, 129), (1, 7, 3), (256, 64, 384),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_onehot_matmul_shapes(n, r, d, dtype):
    rng = np.random.default_rng(n * 1000 + r + d)
    idx = rng.integers(-2, r + 2, size=n).astype(np.int32)  # incl. OOR
    tbl = rng.normal(size=(r, d)).astype(np.float32)
    got = np.asarray(onehot_matmul(jnp.asarray(idx),
                                   jnp.asarray(tbl, dtype),
                                   block_n=8, block_r=16, block_d=128,
                                   interpret=True))
    want = np.asarray(onehot_matmul_ref(jnp.asarray(idx),
                                        jnp.asarray(tbl, dtype)))
    rtol = 1e-6 if dtype == jnp.float32 else 1e-2
    np.testing.assert_allclose(got, want, rtol=rtol, atol=1e-5)


if HAS_HYPOTHESIS:
    @settings(max_examples=10, deadline=None)
    @given(st.integers(0, 2**31 - 2), st.integers(1, 70), st.integers(1, 90),
           st.integers(1, 50))
    def test_onehot_matmul_property(seed, n, r, d):
        rng = np.random.default_rng(seed)
        idx = rng.integers(0, r, size=n).astype(np.int32)
        tbl = rng.normal(size=(r, d)).astype(np.float32)
        got = np.asarray(onehot_matmul(jnp.asarray(idx), jnp.asarray(tbl),
                                       block_n=8, block_r=8, block_d=128,
                                       interpret=True))
        np.testing.assert_allclose(got, tbl[idx], rtol=1e-6, atol=1e-6)
else:
    @pytest.mark.skip(reason="property tests need hypothesis "
                             "(requirements-dev)")
    def test_onehot_matmul_property():
        pass


# --------------------------------------------------------- fused_star_gather
@pytest.mark.parametrize("n,l,rows", [
    (16, 8, (32, 16, 8)), (7, 130, (5, 9)), (64, 1, (100,)),
    (33, 257, (12, 7, 5, 3)),
])
def test_fused_star_gather_linear(n, l, rows):
    rng = np.random.default_rng(n + l)
    tables = [jnp.asarray(rng.normal(size=(r, l)).astype(np.float32))
              for r in rows]
    ptrs = jnp.asarray(
        np.stack([rng.integers(0, r, size=n) for r in rows]).astype(np.int32))
    found = jnp.asarray(rng.integers(0, 2, size=(len(rows), n)).astype(np.int32))
    got = np.asarray(fused_star_gather(ptrs, found, tables, interpret=True))
    want = np.asarray(fused_star_gather_ref(ptrs, found, tables))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_fused_star_gather_chunks_past_one_call(monkeypatch):
    """Batches longer than one call's SMEM-resident pointers map over
    chunks (and a ragged tail) with the same result."""
    from repro.kernels.fused_star_gather import kernel

    monkeypatch.setattr(kernel, "SMEM_POINTER_BYTES", 64 * 8 * 8)
    assert kernel.max_rows_per_call(3) == 64
    rng = np.random.default_rng(7)
    rows, n, l = (11, 6, 9), 150, 3
    tables = [jnp.asarray(rng.normal(size=(r, l)).astype(np.float32))
              for r in rows]
    ptrs = jnp.asarray(
        np.stack([rng.integers(0, r, size=n) for r in rows]).astype(np.int32))
    found = jnp.asarray(rng.integers(0, 2, size=(3, n)).astype(np.int32))
    got = np.asarray(fused_star_gather(ptrs, found, tables, interpret=True))
    want = np.asarray(fused_star_gather_ref(ptrs, found, tables))
    np.testing.assert_array_equal(got, want)


def test_fused_star_gather_tree_compare():
    rng = np.random.default_rng(0)
    n, l, rows = 24, 16, (10, 8)
    # Integer-valued partials so == compare is exact.
    tables = [jnp.asarray(rng.integers(0, 3, size=(r, l)).astype(np.float32))
              for r in rows]
    h = jnp.asarray(rng.integers(0, 5, size=l).astype(np.float32))
    ptrs = jnp.asarray(
        np.stack([rng.integers(0, r, size=n) for r in rows]).astype(np.int32))
    found = jnp.asarray(np.ones((2, n), np.int32))
    got = np.asarray(fused_star_gather(ptrs, found, tables, h, interpret=True))
    want = np.asarray(fused_star_gather_ref(ptrs, found, tables, h))
    np.testing.assert_array_equal(got, want)
    assert set(np.unique(got)) <= {0.0, 1.0}


@pytest.mark.parametrize("l", [1, 5, 127, 130])
def test_fused_star_gather_nan_padded_columns_never_leak(l):
    """Regression: the wrapper NaN-pads ``h`` to the 128-lane multiple for
    the compare path; for every l % 128 != 0 the padded columns must not
    leak into the sliced result (no NaNs, no spurious leaf hits)."""
    rng = np.random.default_rng(l)
    n, rows = 33, (9, 6)
    # Integer-valued partials: rows summing to 0 would match a zero-padded
    # h in the pad columns — the NaN padding is what keeps them False.
    tables = [jnp.asarray(rng.integers(0, 2, size=(r, l)).astype(np.float32))
              for r in rows]
    h = jnp.asarray(rng.integers(0, 3, size=l).astype(np.float32))
    ptrs = jnp.asarray(
        np.stack([rng.integers(0, r, size=n) for r in rows]).astype(np.int32))
    found = jnp.asarray(rng.integers(0, 2, size=(2, n)).astype(np.int32))
    got = np.asarray(fused_star_gather(ptrs, found, tables, h, interpret=True))
    assert got.shape == (n, l)
    assert np.isfinite(got).all()
    assert set(np.unique(got)) <= {0.0, 1.0}
    want = np.asarray(fused_star_gather_ref(ptrs, found, tables, h))
    np.testing.assert_array_equal(got, want)


def test_fused_star_gather_empty_batch():
    """Regression: n == 0 must short-circuit (a zero-size Pallas grid is
    rejected) and preserve the (0, l) result shape, compare path or not."""
    rng = np.random.default_rng(0)
    l, rows = 5, (7, 3)
    tables = [jnp.asarray(rng.normal(size=(r, l)).astype(np.float32))
              for r in rows]
    ptrs = jnp.zeros((2, 0), jnp.int32)
    found = jnp.zeros((2, 0), jnp.int32)
    out = fused_star_gather(ptrs, found, tables, interpret=True)
    assert out.shape == (0, l)
    h = jnp.zeros((l,), jnp.float32)
    out = fused_star_gather(ptrs, found, tables, h, interpret=True)
    assert out.shape == (0, l)
    assert out.dtype == jnp.float32


# --------------------------------------------------------------- tree_predict
@pytest.mark.parametrize("n,k,depth", [(8, 4, 2), (130, 16, 4), (64, 256, 6),
                                       (17, 3, 1)])
def test_tree_predict_kernel_vs_ref(n, k, depth):
    rng = np.random.default_rng(n + k + depth)
    tree = random_tree(rng, k, depth)
    x = rng.normal(size=(n, k)).astype(np.float32)
    got = np.asarray(tree_predict(jnp.asarray(x), tree.F, tree.v, tree.H,
                                  tree.h, block_n=8, block_l=128,
                                  interpret=True))
    want = np.asarray(tree_predict_ref(jnp.asarray(x), tree.F, tree.v,
                                       tree.H, tree.h))
    np.testing.assert_array_equal(got, want)
    # Exactly one leaf fires per row.
    np.testing.assert_array_equal(got.sum(axis=1), np.ones(n))


def test_tree_predict_kernel_equals_model_apply():
    rng = np.random.default_rng(5)
    tree = random_tree(rng, 12, 3)
    x = jnp.asarray(rng.normal(size=(40, 12)).astype(np.float32))
    got = np.asarray(tree_predict(x, tree.F, tree.v, tree.H, tree.h,
                                  block_n=8, interpret=True))
    want = np.asarray(tree.apply(x))
    np.testing.assert_array_equal(got, want)
