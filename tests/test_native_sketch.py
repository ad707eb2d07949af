"""Native (C++) sketch/binning fast path must match the pure-Python
reference semantics exactly (cuts, min_vals, bin assignments), including
weighted sketches, categorical features, NaN missing, and -0.0."""

import numpy as np
import pytest

import xgboost_tpu.data.binned as bn
import xgboost_tpu.data.quantile as q
from xgboost_tpu import native


pytestmark = pytest.mark.skipif(native.load() is None,
                                reason="no C++ toolchain")


def _python_cuts(X, max_bin, weights, types):
    summaries = [q.FeatureSummary.from_data(X[:, f], weights)
                 for f in range(X.shape[1])]
    return q.cuts_from_summaries(summaries, max_bin, types)


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("categorical", [False, True])
def test_native_cuts_match_python(weighted, categorical):
    rng = np.random.default_rng(7)
    n, nf = 5000, 9
    X = rng.normal(size=(n, nf)).astype(np.float32)
    X[rng.random((n, nf)) < 0.08] = np.nan
    X[:, 2] = rng.integers(0, 5, n)
    X[::11, 4] = -0.0
    X[:, 6] = 1.25  # constant feature
    types = (["q"] * nf) if categorical else None
    if categorical:
        types[2] = "c"
    # integer-valued weights: tie-weight sums are then exact in f64 on both
    # paths, making bitwise cut equality deterministic (the two paths
    # accumulate tie weights in different orders)
    w = rng.integers(1, 6, n).astype(np.float32) if weighted else None

    native_cuts = q._sketch_matrix_native(X, 64, w, types)
    py = _python_cuts(X, 64, w, types)
    np.testing.assert_array_equal(native_cuts.ptrs, py.ptrs)
    np.testing.assert_array_equal(native_cuts.values, py.values)
    np.testing.assert_allclose(native_cuts.min_vals, py.min_vals)


@pytest.mark.parametrize("with_missing", [False, True])
def test_native_search_bin_matches_python(with_missing):
    rng = np.random.default_rng(3)
    n, nf = 4000, 6
    X = rng.normal(size=(n, nf)).astype(np.float32)
    if with_missing:
        X[rng.random((n, nf)) < 0.1] = np.nan
    cuts = _python_cuts(X, 32, None, None)
    X = np.ascontiguousarray(X)
    has_missing, max_nbins, dtype, miss, n_nan = bn._matrix_layout(X, cuts)
    assert n_nan == int(np.isnan(X).sum())
    arr = np.empty(X.shape, dtype)
    bn.search_bin_into(X, cuts, miss, arr)
    local = cuts.search_bin(X)
    ref_missing = bool((local < 0).any())
    assert has_missing == ref_missing == with_missing
    mb = int(cuts.n_real_bins().max()) + int(ref_missing)
    assert max_nbins == mb
    ref = np.where(local < 0, mb - 1, local) if ref_missing else local
    np.testing.assert_array_equal(arr.astype(np.int32), ref.astype(np.int32))


def test_float64_input_uses_python_path():
    # f64 data must not be narrowed to f32 by the native path: values 1.0 and
    # 1.0+1e-12 are distinct in f64 but equal in f32
    X = np.asarray([[1.0], [1.0 + 1e-12], [2.0], [3.0]])
    assert q._sketch_matrix_native(X, 8, None, None) is None
    cuts = q.sketch_matrix(X, 8)
    assert cuts.n_bins(0) == 4


def test_weights_length_mismatch_raises():
    X = np.zeros((100, 2), np.float32)
    with pytest.raises((ValueError, IndexError)):
        q.sketch_matrix(X, 8, weights=np.ones(10, np.float32))


def test_all_nan_feature():
    X = np.column_stack([
        np.full(50, np.nan, np.float32),
        np.arange(50, dtype=np.float32),
    ])
    native_cuts = q._sketch_matrix_native(X, 16, None, None)
    py = _python_cuts(X, 16, None, None)
    np.testing.assert_array_equal(native_cuts.ptrs, py.ptrs)
    np.testing.assert_array_equal(native_cuts.values, py.values)
