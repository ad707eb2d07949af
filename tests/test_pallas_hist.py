"""Pallas histogram kernel tests (VERDICT r1 item 5).

The hottest kernel in the framework ships with numerical-equivalence
coverage: ``build_hist_pallas(interpret=True)`` (runs the kernel logic on
CPU) against the plain-XLA ``build_hist_segment`` ground truth, across bin
counts, node counts, ragged row tails, and precision variants. An opt-in
real-chip smoke test runs the same comparison compiled on the TPU (the
conftest pins tests to CPU, so bypass it):

    BENCH_TPU=1 pytest tests/test_pallas_hist.py --noconftest -q
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from xgboost_tpu.ops.histogram import build_hist_segment
from xgboost_tpu.ops.pallas import histogram as ph
from xgboost_tpu.ops.pallas.histogram import build_hist_pallas


def _data(n, F, max_nbins, n_nodes, seed=0, inactive_frac=0.0):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, max_nbins, (n, F)).astype(np.uint8)
    gpair = rng.randn(n, 2).astype(np.float32)
    gpair[:, 1] = np.abs(gpair[:, 1])  # hessians positive like real losses
    rel = rng.randint(0, n_nodes, n).astype(np.int32)
    if inactive_frac:
        rel[rng.rand(n) < inactive_frac] = n_nodes  # inactive rows
    return jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(rel)


def _reference(bins, gpair, rel, n_nodes, max_nbins):
    return np.asarray(build_hist_segment(bins, gpair, rel, n_nodes,
                                         max_nbins))


TOL = {
    "f32": dict(rtol=1e-5, atol=1e-5),
    # 15-bit fixed point: |err| <= 2^-15 * max|g| per element, n elements sum
    "int8x2": dict(rtol=2e-3, atol=2e-3),
    # bf16 hi/lo split: ~16 mantissa bits on inputs (CPU emulation is the
    # weak link; the docstring documents TPU-only full accuracy)
    "bf16x2": dict(rtol=2e-2, atol=2e-2),
}


# bf16x2 is exercised only on the real chip (BENCH_TPU=1): XLA:CPU emulates
# bf16 dots with bf16 accumulation, so CPU equivalence would need a
# meaninglessly loose tolerance (see ops/pallas/histogram.py docstring)
@pytest.mark.parametrize("precision", ["f32", "int8x2"])
# 16/256 bins take the packed SWAR one-hot (B % 4 == 0), 17 the compare
# fallback (also the missing-slot B = 257 shape class)
@pytest.mark.parametrize("max_nbins,n_nodes", [(16, 1), (16, 64), (256, 4),
                                               (17, 4)])
def test_pallas_interpret_matches_segment(precision, max_nbins, n_nodes):
    n, F = 1000, 5  # ragged: not a multiple of the 128-row tile
    bins, gpair, rel = _data(n, F, max_nbins, n_nodes, seed=max_nbins)
    ref = _reference(bins, gpair, rel, n_nodes, max_nbins)
    got = np.asarray(build_hist_pallas(
        bins.T, gpair, rel, n_nodes, max_nbins, precision=precision,
        block_rows=256, interpret=True))
    assert got.shape == ref.shape == (n_nodes, F, max_nbins, 2)
    scale = max(np.abs(ref).max(), 1.0)
    np.testing.assert_allclose(got / scale, ref / scale, **TOL[precision])


def test_pallas_interpret_inactive_rows_and_tiny_n():
    # rows parked at rel == n_nodes must not contribute; n smaller than one
    # row block exercises the padding path
    n, F, max_nbins, n_nodes = 37, 3, 16, 2
    bins, gpair, rel = _data(n, F, max_nbins, n_nodes, seed=9,
                             inactive_frac=0.5)
    ref = _reference(bins, gpair, rel, n_nodes, max_nbins)
    got = np.asarray(build_hist_pallas(
        bins.T, gpair, rel, n_nodes, max_nbins, precision="f32",
        interpret=True))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    # histogram total equals the active rows' gradient sum
    active = np.asarray(rel) < n_nodes
    np.testing.assert_allclose(
        got.sum(axis=(0, 2))[0], np.asarray(gpair)[active].sum(axis=0),
        rtol=1e-5, atol=1e-5)


def test_pallas_interpret_feature_block_padding():
    # F not a multiple of feat_block exercises the feature-pad trim
    n, F, max_nbins, n_nodes = 512, 11, 32, 8
    bins, gpair, rel = _data(n, F, max_nbins, n_nodes, seed=3)
    ref = _reference(bins, gpair, rel, n_nodes, max_nbins)
    got = np.asarray(build_hist_pallas(
        bins.T, gpair, rel, n_nodes, max_nbins, precision="f32",
        feat_block=8, interpret=True))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)


def test_int8x2_feat_block_bit_identity():
    # the auto (whole-F) feature block and an explicit 8-wide block must
    # produce identical bits: feature padding rows carry zero gradients
    # and the per-feature int32 dot accumulation is order-independent
    n, F, max_nbins, n_nodes = 700, 11, 256, 8
    bins, gpair, rel = _data(n, F, max_nbins, n_nodes, seed=7)
    a = np.asarray(build_hist_pallas(bins.T, gpair, rel, n_nodes, max_nbins,
                                     precision="int8x2", interpret=True))
    b = np.asarray(build_hist_pallas(bins.T, gpair, rel, n_nodes, max_nbins,
                                     precision="int8x2", feat_block=8,
                                     interpret=True))
    np.testing.assert_array_equal(a, b)


def test_int8x2_order_independence_interpret():
    # the fixed-point path must be ORDER-independent bitwise (the property
    # the reference buys with fixed-point atomics,
    # gpu_hist/histogram.cu:55-100): permuting the rows regroups every
    # partial sum across row blocks, yet exact int32 accumulation of the
    # same quantised values must reproduce identical bits
    n, F, max_nbins, n_nodes = 777, 4, 64, 16
    bins, gpair, rel = _data(n, F, max_nbins, n_nodes, seed=4)
    a = np.asarray(build_hist_pallas(bins.T, gpair, rel, n_nodes, max_nbins,
                                     precision="int8x2", interpret=True))
    perm = np.random.RandomState(0).permutation(n)
    b = np.asarray(build_hist_pallas(
        bins[perm].T, gpair[perm], rel[perm], n_nodes, max_nbins,
        precision="int8x2", interpret=True))
    np.testing.assert_array_equal(a, b)


def _under_rule(monkeypatch, rule, *arrays, **static):
    """``build_hist_pallas``'s body under ``_dot_features = rule`` (the
    module's own where no ``monkeypatch`` is given) and a jit of its own:
    nothing jax cached under the module's rule is served."""
    if monkeypatch is not None:
        monkeypatch.setattr(ph, "_dot_features", rule)
    return np.asarray(jax.jit(
        lambda *a: ph.build_hist_pallas.__wrapped__(
            *a, interpret=True, **static))(*arrays))


def _narrow(F, B, N, ids, n=300):
    """A two-level level's input: ids under B with the missing share in the
    last slot, some rows on no node, and a row tail the block pads."""
    rng = np.random.RandomState(F * B + N)
    bins = rng.randint(0, B - 1, (F, n)).astype(ids)
    bins[rng.rand(F, n) < 0.3] = B - 1
    gpair = rng.randn(n, 2).astype(np.float32)
    gpair[:, 1] = np.abs(gpair[:, 1])
    rel = rng.randint(0, N + 1, n).astype(np.int32)     # N: inactive
    return jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(rel)


# the two-level search's coarse and refine widths at every width the
# benchmark runs narrow enough to interpret, from the root to the widest
# level: 5 features are one short dot, 28 and 67 end in one (of 4 and 3)
@pytest.mark.parametrize("ids", [np.uint8, np.uint16])
@pytest.mark.parametrize("N", [1, 8, 32, 128])
@pytest.mark.parametrize("B", [20, 36])
@pytest.mark.parametrize("F", [5, 28, 67])
def test_stacked_dot_equals_the_dot_a_feature_bit_for_bit(monkeypatch, F, B,
                                                          N, ids):
    """A group of features' one-hots in one dot (``_dot_features``): a row
    block's sums are exact int32 whatever the grouping, so the histogram is
    the dot a feature's, every bit."""
    arrays = _narrow(F, B, N, ids)
    assert ph._dot_features(B, N) == ph.DOT_FEATURES == 8 and F % 8
    static = dict(n_nodes=N, max_nbins=B, block_rows=128)
    stacked = _under_rule(monkeypatch, ph._dot_features, *arrays, **static)
    feature = _under_rule(monkeypatch, lambda B, N: 1, *arrays, **static)
    assert stacked.shape == (N, F, B, 2)
    np.testing.assert_array_equal(stacked, feature)
    assert float(np.abs(stacked[:, F - 1, B - 1]).sum()) > 0


def test_stacked_dot_over_the_u4_transport_is_bit_identical(monkeypatch):
    """The packed page's nibble rows feed the stacked dot as the byte rows
    do (16 slots: two features a vreg of words)."""
    F, n, B, N = 11, 300, 16, 4
    bins, gpair, rel = _narrow(F, B, N, np.uint8, n=n)
    even = np.concatenate([np.asarray(bins), np.zeros((1, n), np.uint8)])
    packed = jnp.asarray(even[0::2] | even[1::2] << 4)   # [ceil(F/2), n]
    static = dict(n_nodes=N, max_nbins=B)
    assert ph._dot_features(B, N) == 8
    got = _under_rule(monkeypatch, ph._dot_features, packed, gpair, rel,
                      packed_u4=F, **static)
    want = _under_rule(monkeypatch, lambda B, N: 1, bins, gpair, rel,
                       **static)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("B,N,form,rows", [
    (20, 8, "stacked", 160), (36, 128, "stacked", 288),
    (16, 1, "stacked", 128), (64, 4, "stacked", 512),
    # a one-hot wider than 64 slots streams as long as a tile takes to
    # latch: the one-pass schedules at 256 bins keep the dot a feature
    (68, 4, "feature", 68), (256, 4, "feature", 256),
    # 257 slots (a missing slot past a byte) take the compare build
    (257, 2, "feature", 257),
])
def test_dot_counter_and_gauge_read_what_was_traced(B, N, form, rows):
    from xgboost_tpu.obs import metrics as obs_metrics

    assert (ph._dot_features(B, N) > 1) == (form == "stacked")
    registry = obs_metrics.get_registry()
    registry.set_gauge("xtpu_hist_dot_rows", 0)
    before = obs_metrics.hist_dot_counts()
    bins, gpair, rel = _narrow(3, B, N, np.uint16, n=64)
    jax.make_jaxpr(lambda *a: ph.build_hist_pallas.__wrapped__(
        *a, n_nodes=N, max_nbins=B, interpret=True))(bins, gpair, rel)
    after = obs_metrics.hist_dot_counts()
    other = "feature" if form == "stacked" else "stacked"
    assert after.get(form, 0) == before.get(form, 0) + 1
    assert after.get(other, 0) == before.get(other, 0)
    assert obs_metrics.hist_dot_rows() == rows
    with pytest.raises(ValueError):
        obs_metrics.count_hist_dot("held", 128)


MISSING = {np.uint8: 255, np.uint16: 256}


def _refine_level(F, N, ids, n=700):
    """A refine build's input: raw ids with the missing slot set, a row
    tail no row block divides, rows outside the level (rel == N), and each
    node's window start per feature; plus the remapped ids the build
    replaces, each row's window looked up by numpy (``refine_bin_ids``)."""
    from xgboost_tpu.ops.split import refine_bin_ids

    missing = MISSING[ids]
    rng = np.random.RandomState(F + N)
    bins = rng.randint(0, missing, (n, F)).astype(ids)
    bins[rng.rand(n, F) < 0.2] = missing
    gpair = rng.randn(n, 2).astype(np.float32)
    gpair[:, 1] = np.abs(gpair[:, 1])
    rel = rng.randint(0, N + 1, n).astype(np.int32)
    window = rng.randint(0, 15, (N, F)).astype(np.int32)
    c_row = np.concatenate([window, np.zeros((1, F), np.int32)])[rel]
    remapped = refine_bin_ids(jnp.asarray(bins, jnp.int32),
                              jnp.asarray(c_row), missing)
    return ((jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(rel),
             jnp.asarray(window)), remapped, missing)


REFINE_CASES = ([("kernel", ids, F, N) for ids in (np.uint8, np.uint16)
                 for F in (28, 220, 968) for N in (1, 32, 128)]
                + [("xla", ids, F, N) for ids in (np.uint8, np.uint16)
                   for F in (28, 220) for N in (1, 32)])


@pytest.mark.parametrize("where,ids,F,N", REFINE_CASES)
def test_refine_window_build_equals_the_remapped_copy(where, ids, F, N):
    """The refine build over the RAW ids and the window table equals the
    build over ``refine_bin_ids``' remapped copy, bit for bit: in the
    int8x2 kernel, which remaps in VMEM (``kernel``: 28 and 220 features
    whole-F bodies, 968 in feature blocks), and in ``build_hist``'s XLA
    build, which remaps first (``xla``)."""
    from xgboost_tpu.obs.metrics import refine_remap_counts
    from xgboost_tpu.ops.histogram import build_hist
    from xgboost_tpu.ops.split import WINDOW

    (bins, gpair, rel, window), remapped, missing = _refine_level(F, N, ids)
    B = WINDOW + 4
    if where == "kernel":
        static = dict(n_nodes=N, max_nbins=B, block_rows=256)
        got = _under_rule(None, None, bins.T, gpair, rel, window=window,
                          missing_bin=missing, **static)
        want = _under_rule(None, None, remapped.T, gpair, rel, **static)
    else:
        before = refine_remap_counts().get("xla", 0)
        got = np.asarray(build_hist(bins, gpair, rel, N, B, window=window,
                                    missing_bin=missing))
        assert refine_remap_counts()["xla"] == before + 1
        want = _reference(remapped, gpair, rel, N, B)
    assert got.shape == (N, F, B, 2)
    np.testing.assert_array_equal(got, want)
    # in-window mass lands in the window's slots, the rest in the pad slot
    assert float(np.abs(got[..., :WINDOW, :]).sum()) > 0
    assert float(np.abs(got[..., WINDOW + 3, :]).sum()) > 0


def test_refine_window_outside_the_int8_kernel_refused():
    bins, gpair, rel = _data(300, 3, 16, 2)
    window = jnp.zeros((2, 3), jnp.int32)
    for static in (dict(precision="f32", missing_bin=15),
                   dict(missing_bin=None)):
        with pytest.raises(ValueError):
            build_hist_pallas(bins.T, gpair, rel, 2, 36, window=window,
                              interpret=True, **static)


@pytest.mark.skipif(os.environ.get("BENCH_TPU") != "1",
                    reason="real-chip smoke test; set BENCH_TPU=1")
def test_pallas_compiled_on_tpu_matches_segment():
    import jax

    assert jax.default_backend() == "tpu"
    n, F, max_nbins, n_nodes = 100_000, 8, 256, 32
    bins, gpair, rel = _data(n, F, max_nbins, n_nodes, seed=1)
    ref = _reference(bins, gpair, rel, n_nodes, max_nbins)
    for precision in ("f32", "int8x2", "bf16x2"):
        got = np.asarray(build_hist_pallas(
            bins.T, gpair, rel, n_nodes, max_nbins, precision=precision))
        scale = max(np.abs(ref).max(), 1.0)
        np.testing.assert_allclose(got / scale, ref / scale,
                                   **TOL[precision])


@pytest.mark.skipif(os.environ.get("BENCH_TPU") != "1",
                    reason="real-chip smoke test; set BENCH_TPU=1")
def test_pallas_wide_feature_matrix_fits_vmem_on_tpu():
    # F=136 (MSLR-shape): the whole-F accumulator would be 8.9 MB at
    # N=32 — the feat_block auto-pick must leave scoped-VMEM headroom for
    # the one-hot plane/PT4/temporaries (a 12 MB budget OOMed Mosaic at
    # 17.53M > 16M); only a real-chip compile exercises that limit
    import jax

    assert jax.default_backend() == "tpu"
    n, F, max_nbins, n_nodes = 50_000, 136, 256, 32
    bins, gpair, rel = _data(n, F, max_nbins, n_nodes, seed=2)
    ref = _reference(bins, gpair, rel, n_nodes, max_nbins)
    got = np.asarray(build_hist_pallas(
        bins.T, gpair, rel, n_nodes, max_nbins, precision="int8x2"))
    scale = max(np.abs(ref).max(), 1.0)
    np.testing.assert_allclose(got / scale, ref / scale, **TOL["int8x2"])
