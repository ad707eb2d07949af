"""Cross-level fused histogram sweep parity (hist_method="fused").

The fused scheme reschedules the two-level coarse->refine histogram: at
each level boundary the row advance below level L's decoded splits and
level L+1's coarse accumulation share one sweep over the bin matrix
(``ops/histogram.py fused_advance_coarse``; the Pallas kernel in
``ops/pallas/histogram.py`` reads the [F, R] tile once for both). The
contract is BIT-EXACTNESS with the two-pass ``hist_method="coarse"``
schedule — same search space, same numerics, fewer HBM streams — and
these tests pin it at three altitudes:

- kernel:   ``fused_advance_coarse_pallas(interpret=True)`` against the
            sequential ``advance_positions_level`` + int8x2 coarse build
            (bit-identical) and the segment ground truth (tolerance);
- op:       the XLA ``fused_advance_coarse`` body against the sequential
            composition, dense and walk kinds (bit-identical);
- model:    trains with hist_method 'fused' vs 'coarse' — resident
            depthwise, lossguide, paged external memory, the row-split
            mesh under both growers and the mesh column-split
            composition, to a level past DENSE_LEVEL_MAX and under the
            growers' options — identical dumps; and 'fused' against the
            one-pass search where max_bin <= 32 makes the two the same
            search.

The round driver's budget of two dispatches a round is pinned here too
(``test_dispatch_count_resident``).

Below the LAST level of a deep tree there is no next coarse pass to fuse
with: ``advance_leaf`` routes the rows and looks their leaf up in one
kernel sweep where a TPU runs it (``advance_leaf_pallas``), bit for bit
what ``update_positions`` + ``leaf_value[positions]`` give; the gate is
held by ``xtpu_grow_epilogue_total{kind}``.

Plus the ADVICE r5 #2 satellite: colsample draws seeded from real columns
only, so padded mesh-col-split feature axes keep sampling parity.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import xgboost_tpu as xgb
from xgboost_tpu.obs.metrics import grow_epilogue_counts
from xgboost_tpu.ops.histogram import (advance_leaf, build_hist_segment,
                                       fused_advance_coarse)
from xgboost_tpu.ops.pallas.histogram import (ADVANCE_LEAF_MAX_NODES,
                                              advance_leaf_pallas,
                                              build_hist_pallas,
                                              fused_advance_coarse_pallas)
from xgboost_tpu.ops.partition import advance_positions_level, update_positions
from xgboost_tpu.ops.split import COARSE_B, coarse_bin_ids


def _level_data(n, F, max_nbins, lo_prev, n_prev, seed=0):
    """Rows parked at level ``lo_prev..lo_prev+n_prev`` plus strays, and a
    random (partially non-splitting) split payload for that level."""
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, max_nbins, (n, F)).astype(np.uint8)
    gpair = rng.randn(n, 2).astype(np.float32)
    gpair[:, 1] = np.abs(gpair[:, 1])
    positions = rng.randint(lo_prev, lo_prev + n_prev, n).astype(np.int32)
    positions[rng.rand(n) < 0.1] = 0  # strays above the level stay put
    feat = rng.randint(0, F, n_prev).astype(np.int32)
    thr = rng.randint(0, max_nbins - 1, n_prev).astype(np.int32)
    dleft = rng.rand(n_prev) < 0.5
    can_split = rng.rand(n_prev) < 0.8
    feat = np.where(can_split, feat, -1).astype(np.int32)
    thr = np.where(can_split, thr, 0).astype(np.int32)
    dleft = dleft & can_split
    return (jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(positions),
            jnp.asarray(feat), jnp.asarray(thr), jnp.asarray(dleft),
            jnp.asarray(can_split))


def _sequential(bins, gpair, positions, feat, thr, dleft, can_split,
                lo_prev, n_prev, lo, n_level, missing_bin, coarse_kernel):
    """The two-pass ground truth: advance below the previous level's
    splits, then the new level's coarse histogram as a separate pass."""
    rel_prev = jnp.where(
        (positions >= lo_prev) & (positions < lo_prev + n_prev),
        positions - lo_prev, n_prev).astype(jnp.int32)
    new_pos = advance_positions_level(
        bins.astype(jnp.float32), positions, rel_prev, feat, thr, dleft,
        can_split, missing_bin)
    rel = jnp.where((new_pos >= lo) & (new_pos < lo + n_level),
                    new_pos - lo, n_level).astype(jnp.int32)
    cb = coarse_bin_ids(bins.astype(jnp.int32), missing_bin)
    return new_pos, coarse_kernel(cb, gpair, rel, n_level)


@pytest.mark.parametrize("n,n_prev,n_level", [(700, 2, 4), (1500, 4, 8)])
def test_fused_pallas_interpret_matches_sequential(n, n_prev, n_level):
    F, max_nbins = 5, 64
    missing_bin = max_nbins - 1
    lo_prev, lo = n_prev - 1, 2 * n_prev - 1
    data = _level_data(n, F, max_nbins, lo_prev, n_prev, seed=n)
    bins, gpair = data[0], data[1]

    pos_f, hist_f = fused_advance_coarse_pallas(
        bins.T, gpair, *data[2:], lo_prev=lo_prev, n_prev=n_prev, lo=lo,
        n_level=n_level, missing_bin=missing_bin, block_rows=256,
        interpret=True)

    # positions: pure integer routing — bit-exact with the matmul advance
    pos_ref, hist_q = _sequential(
        *data, lo_prev, n_prev, lo, n_level, missing_bin,
        lambda cb, gp, rel, nl: build_hist_pallas(
            cb.T, gp, rel, nl, COARSE_B, precision="int8x2",
            block_rows=256, interpret=True))
    np.testing.assert_array_equal(np.asarray(pos_f), np.asarray(pos_ref))
    # histogram: BIT-identical to the unfused int8x2 kernel (same
    # quantisation, same packed SWAR one-hot, same accumulation order)
    np.testing.assert_array_equal(np.asarray(hist_f), np.asarray(hist_q))
    assert hist_f.shape == (n_level, F, COARSE_B, 2)

    # and within fixed-point tolerance of the exact segment ground truth
    _, hist_ref = _sequential(
        *data, lo_prev, n_prev, lo, n_level, missing_bin,
        lambda cb, gp, rel, nl: build_hist_segment(cb, gp, rel, nl,
                                                   COARSE_B))
    scale = max(float(np.abs(np.asarray(hist_ref)).max()), 1.0)
    np.testing.assert_allclose(np.asarray(hist_f) / scale,
                               np.asarray(hist_ref) / scale,
                               rtol=2e-3, atol=2e-3)


def test_fused_op_xla_dense_matches_sequential():
    """The XLA body of fused_advance_coarse (the non-Pallas path every
    backend gets) composes the exact sequential ops — bit-identical."""
    n, F, max_nbins, n_prev, n_level = 900, 6, 32, 2, 4
    missing_bin = max_nbins - 1
    lo_prev, lo = 1, 3
    data = _level_data(n, F, max_nbins, lo_prev, n_prev, seed=7)
    bins, gpair = data[0], data[1]
    feat, thr, dleft, can_split = data[3:]
    prev = {"kind": "dense", "lo": lo_prev, "n_level": n_prev,
            "arrs": (feat, thr, dleft, can_split)}
    pos_f, hist_f = fused_advance_coarse(
        bins, gpair, data[2], prev, lo, n_level, missing_bin,
        bins_t=bins.T, method="auto")
    pos_ref, hist_ref = _sequential(
        *data, lo_prev, n_prev, lo, n_level, missing_bin,
        lambda cb, gp, rel, nl: build_hist_segment(cb, gp, rel, nl,
                                                   COARSE_B))
    np.testing.assert_array_equal(np.asarray(pos_f), np.asarray(pos_ref))
    np.testing.assert_array_equal(np.asarray(hist_f), np.asarray(hist_ref))


def test_fused_op_walk_kind_matches_update_positions():
    """Deep levels route through the per-row gather walk: the fused
    boundary sweep must produce the same positions + coarse histogram."""
    n, F, max_nbins = 800, 4, 32
    missing_bin = max_nbins - 1
    n_prev, lo_prev = 4, 3
    n_level, lo = 8, 7
    max_nodes = 15
    rng = np.random.RandomState(3)
    bins = jnp.asarray(rng.randint(0, max_nbins, (n, F)).astype(np.uint8))
    gpair = jnp.asarray(np.abs(rng.randn(n, 2)).astype(np.float32))
    positions = jnp.asarray(
        rng.randint(lo_prev, lo_prev + n_prev, n).astype(np.int32))
    sf = np.full(max_nodes, -1, np.int32)
    sb = np.zeros(max_nodes, np.int32)
    dl = np.zeros(max_nodes, bool)
    isf = np.zeros(max_nodes, bool)
    for nid in range(lo_prev, lo_prev + n_prev):
        if rng.rand() < 0.75:
            sf[nid] = rng.randint(0, F)
            sb[nid] = rng.randint(0, max_nbins - 1)
            dl[nid] = rng.rand() < 0.5
            isf[nid] = True
    arrs = (jnp.asarray(sf), jnp.asarray(sb), jnp.asarray(dl),
            jnp.asarray(isf))
    prev = {"kind": "walk", "lo": lo_prev, "n_level": n_prev, "arrs": arrs}
    pos_f, hist_f = fused_advance_coarse(
        bins, gpair, positions, prev, lo, n_level, missing_bin,
        bins_t=bins.T, method="auto")
    pos_ref = update_positions(bins, positions, *arrs, missing_bin)
    rel = jnp.where((pos_ref >= lo) & (pos_ref < lo + n_level),
                    pos_ref - lo, n_level).astype(jnp.int32)
    cb = coarse_bin_ids(bins.astype(jnp.int32), missing_bin)
    hist_ref = build_hist_segment(cb, gpair, rel, n_level, COARSE_B)
    np.testing.assert_array_equal(np.asarray(pos_f), np.asarray(pos_ref))
    np.testing.assert_array_equal(np.asarray(hist_f), np.asarray(hist_ref))


def _last_level(n, F, n_prev, has_missing, split_share, seed):
    """A tree grown down to a last evaluated level of ``n_prev`` nodes, as
    the walk's whole-heap arrays: rows parked on the level, a share that
    stopped at a leaf on an earlier level, ``split_share`` of the level's
    nodes splitting, a leaf table with signed zeros in it."""
    rng = np.random.RandomState(seed)
    lo, max_nodes = n_prev - 1, 4 * n_prev - 1
    max_nbins = 257 if has_missing else 256
    missing_bin = max_nbins - 1 if has_missing else max_nbins
    bins = rng.randint(0, max_nbins, (n, F)).astype(
        np.int16 if has_missing else np.uint8)
    positions = rng.randint(lo, lo + n_prev, n).astype(np.int32)
    stopped = rng.rand(n) < 0.15
    positions[stopped] = rng.randint(0, lo, stopped.sum())
    can_split = rng.rand(n_prev) < split_share
    sf = np.full(max_nodes, -1, np.int32)
    sb = np.zeros(max_nodes, np.int32)
    dl = np.zeros(max_nodes, bool)
    isf = np.zeros(max_nodes, bool)
    sf[lo:lo + n_prev] = np.where(can_split, rng.randint(0, F, n_prev), -1)
    sb[lo:lo + n_prev] = np.where(can_split,
                                  rng.randint(0, max_nbins - 1, n_prev), 0)
    dl[lo:lo + n_prev] = can_split & (rng.rand(n_prev) < 0.5)
    isf[lo:lo + n_prev] = can_split
    leaf = rng.randn(max_nodes).astype(np.float32)
    leaf[rng.rand(max_nodes) < 0.1] = -0.0
    arrs = tuple(jnp.asarray(a) for a in (sf, sb, dl, isf))
    return (jnp.asarray(bins), jnp.asarray(positions), arrs,
            jnp.asarray(leaf), missing_bin)


# ---- categorical routing at the airline job's widths ----------------------
# 352 slots (351 categories and the missing slot) make left sets of 11
# words; three categorical columns among 13, uint16 ids.
CAT_SLOTS, CAT_COLS = 352, (2, 6, 9)


def _cat_level(n, n_prev, lo_prev, seed, split_share=0.85):
    """Rows on a level of ``n_prev`` nodes (strays above it), each node
    splitting on a numeric column by threshold or on a categorical one by
    a random left set of 11 words; a share of every column missing."""
    rng = np.random.RandomState(seed)
    F, missing_bin = 13, CAT_SLOTS - 1
    bins = rng.randint(0, 256, (n, F))
    for c in CAT_COLS:
        bins[:, c] = rng.randint(0, CAT_SLOTS - 1, n)
    bins[rng.rand(n, F) < 0.05] = missing_bin
    positions = rng.randint(lo_prev, lo_prev + n_prev, n).astype(np.int32)
    positions[rng.rand(n) < 0.1] = max(lo_prev - 1, 0)
    can_split = rng.rand(n_prev) < split_share
    is_cat = can_split & (rng.rand(n_prev) < 0.6)
    feat = np.where(is_cat, rng.choice(CAT_COLS, n_prev),
                    rng.choice([f for f in range(F) if f not in CAT_COLS],
                               n_prev))
    feat = np.where(can_split, feat, -1).astype(np.int32)
    thr = np.where(can_split & ~is_cat, rng.randint(0, 255, n_prev),
                   0).astype(np.int32)
    dleft = can_split & (rng.rand(n_prev) < 0.5)
    words = rng.randint(0, 2 ** 32, (n_prev, 11), dtype=np.uint64)
    words = np.where(is_cat[:, None], words, 0).astype(np.uint32)
    return (jnp.asarray(bins.astype(np.uint16)), jnp.asarray(positions),
            tuple(jnp.asarray(a) for a in (feat, thr, dleft, can_split)),
            (jnp.asarray(is_cat), jnp.asarray(words)), missing_bin)


@pytest.mark.parametrize("n,n_prev", [(1500, 4), (2049, 64)])
def test_fused_pallas_interpret_routes_categorical_splits(n, n_prev):
    """The boundary sweep's bitmask advance sends every row where
    ``advance_positions_level`` sends it at 352 slots, 11 words: a
    categorical node by its left set, a missing value by default_left;
    its coarse histogram is the unfused build's, bit for bit."""
    lo_prev, lo, n_level = n_prev - 1, 2 * n_prev - 1, 2 * n_prev
    bins, positions, arrs, cat, missing_bin = _cat_level(
        n, n_prev, lo_prev, seed=n)
    gpair = jnp.asarray(np.random.RandomState(1).randn(n, 2)
                        .astype(np.float32))
    pos_k, hist_k = fused_advance_coarse_pallas(
        bins.T, gpair, positions, *arrs, cat, lo_prev=lo_prev,
        n_prev=n_prev, lo=lo, n_level=n_level, missing_bin=missing_bin,
        block_rows=256, interpret=True, cat_cols=CAT_COLS)
    rel_prev = jnp.where(
        (positions >= lo_prev) & (positions < lo_prev + n_prev),
        positions - lo_prev, n_prev).astype(jnp.int32)
    pos_ref = advance_positions_level(
        bins.astype(jnp.float32), positions, rel_prev, *arrs, missing_bin,
        is_cat=cat[0], cat_words=cat[1])
    np.testing.assert_array_equal(np.asarray(pos_k), np.asarray(pos_ref))
    moved = np.asarray(pos_ref) != np.asarray(positions)
    at_cat = np.asarray(cat[0])[np.clip(np.asarray(positions) - lo_prev, 0,
                                        n_prev - 1)]
    assert (moved & at_cat).any()
    rel = jnp.where((pos_ref >= lo) & (pos_ref < lo + n_level),
                    pos_ref - lo, n_level).astype(jnp.int32)
    cb = coarse_bin_ids(bins.astype(jnp.int32), missing_bin)
    hist_q = build_hist_pallas(cb.T, gpair, rel, n_level, COARSE_B,
                               block_rows=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(hist_k), np.asarray(hist_q))


@pytest.mark.parametrize("n_prev", [128, 256])
def test_advance_leaf_pallas_interpret_routes_categorical_splits(n_prev):
    """The last level's kernel routes categorical nodes by their left
    sets: positions and delta bit for bit what ``update_positions`` and
    the leaf gather give at 352 slots, 11 words."""
    n = 2049
    lo = n_prev - 1
    bins, positions, arrs, cat, missing_bin = _cat_level(
        n, n_prev, lo, seed=n_prev)
    max_nodes = 4 * n_prev - 1
    heap = [np.zeros(max_nodes, np.asarray(a).dtype) for a in arrs]
    heap[0][:] = -1
    for h, a in zip(heap, arrs):
        h[lo:lo + n_prev] = np.asarray(a)
    is_cat_h = np.zeros(max_nodes, bool)
    is_cat_h[lo:lo + n_prev] = np.asarray(cat[0])
    words_h = np.zeros((max_nodes, 11), np.uint32)
    words_h[lo:lo + n_prev] = np.asarray(cat[1])
    leaf = jnp.asarray(np.random.RandomState(2).randn(max_nodes)
                       .astype(np.float32))
    pos_k, delta_k = advance_leaf_pallas(
        bins.T, positions, *arrs, leaf, cat, n_prev=n_prev,
        missing_bin=missing_bin, block_rows=256, interpret=True,
        cat_cols=CAT_COLS)
    pos_ref = update_positions(
        bins, positions, *(jnp.asarray(h) for h in heap), missing_bin,
        is_cat_split=jnp.asarray(is_cat_h), cat_words=jnp.asarray(words_h))
    np.testing.assert_array_equal(np.asarray(pos_k), np.asarray(pos_ref))
    np.testing.assert_array_equal(np.asarray(delta_k),
                                  np.asarray(leaf)[np.asarray(pos_ref)])


@pytest.mark.parametrize("feat_block", [None, 4])
def test_refine_build_sums_the_categorical_columns_in_its_sweep(feat_block):
    """The refine kernel's categorical columns (in one feature block, or in
    three of four): their full histograms are the compare build's over
    those columns alone, bit for bit, and the refine histogram is the one
    it gives without them."""
    rng = np.random.RandomState(4)
    n, N = 3000, 8
    bins, _, _, _, missing_bin = _cat_level(n, 1, 0, seed=9)
    gpair = jnp.asarray(rng.randn(n, 2).astype(np.float32))
    rel = jnp.asarray(rng.randint(0, N + 1, n).astype(np.int32))
    window = jnp.asarray(rng.randint(0, 15, (N, 13)).astype(np.int32))
    common = dict(window=window, missing_bin=missing_bin, block_rows=256,
                  interpret=True, feat_block=feat_block)
    hist_r, hist_cat = build_hist_pallas(
        bins.T, gpair, rel, N, 36, cat_rows=CAT_COLS, cat_slots=CAT_SLOTS,
        **common)
    np.testing.assert_array_equal(
        np.asarray(hist_r),
        np.asarray(build_hist_pallas(bins.T, gpair, rel, N, 36, **common)))
    alone = build_hist_pallas(bins.T[np.asarray(CAT_COLS)], gpair, rel, N,
                              CAT_SLOTS, block_rows=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(hist_cat), np.asarray(alone))
    assert hist_cat.shape == (N, len(CAT_COLS), CAT_SLOTS, 2)


def test_cat_advance_loop_matches_the_gather_walk():
    """``advance_positions_level``'s word loop (11 words, not unrolled)
    routes as ``update_positions``' per-row gather of the same words."""
    n, n_prev = 3000, 8
    lo = n_prev - 1
    bins, positions, arrs, cat, missing_bin = _cat_level(n, n_prev, lo, 5)
    rel = jnp.where((positions >= lo) & (positions < lo + n_prev),
                    positions - lo, n_prev).astype(jnp.int32)
    dense = advance_positions_level(
        bins.astype(jnp.float32), positions, rel, *arrs, missing_bin,
        is_cat=cat[0], cat_words=cat[1])
    max_nodes = 2 * n_prev + lo
    full = [np.zeros(max_nodes, np.asarray(a).dtype) for a in arrs]
    for h, a in zip(full, arrs):
        h[lo:lo + n_prev] = np.asarray(a)
    ic = np.zeros(max_nodes, bool)
    ic[lo:lo + n_prev] = np.asarray(cat[0])
    cw = np.zeros((max_nodes, 11), np.uint32)
    cw[lo:lo + n_prev] = np.asarray(cat[1])
    walk = update_positions(bins, positions,
                            *(jnp.asarray(h) for h in full), missing_bin,
                            is_cat_split=jnp.asarray(ic),
                            cat_words=jnp.asarray(cw))
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(walk))


def _bits(x):
    return np.asarray(x).view(np.int32)


# the block is 256 rows: 700 and 2,049 rows are no multiple of it
@pytest.mark.parametrize("n_prev", [128, 256, ADVANCE_LEAF_MAX_NODES])
@pytest.mark.parametrize("n,has_missing,split_share", [
    (700, False, 0.8), (2049, True, 0.8),
    (700, True, 0.0),            # a level where no node splits
    (1024, False, 1.0),
])
def test_advance_leaf_pallas_interpret_matches_walk(n_prev, n, has_missing,
                                                    split_share):
    """The last level's kernel against ``update_positions`` +
    ``leaf_value[positions]``: positions and delta bit for bit, signed
    zeros too."""
    F = 5
    bins, positions, arrs, leaf, missing_bin = _last_level(
        n, F, n_prev, has_missing, split_share, seed=n_prev + n)
    lo = n_prev - 1
    pos_k, delta_k = advance_leaf_pallas(
        bins.T, positions, *(a[lo:lo + n_prev] for a in arrs), leaf,
        n_prev=n_prev, missing_bin=missing_bin, block_rows=256,
        interpret=True)
    pos_ref = update_positions(bins, positions, *arrs, missing_bin)
    np.testing.assert_array_equal(np.asarray(pos_k), np.asarray(pos_ref))
    np.testing.assert_array_equal(_bits(delta_k), _bits(leaf[pos_ref]))
    moved = np.asarray(pos_ref) != np.asarray(positions)
    assert moved.any() == (split_share > 0)
    assert (~moved).any()        # rows that stopped at a leaf earlier


def test_kernels_take_the_tile_padded_to_the_row_blocks_once():
    """A round program pads its ``[F, n]`` tile to the kernels' row blocks
    once (``tree/grow.py``, ``fit_rows``); each kernel takes that tile as
    it is, and one padded for a larger block cut to its own: the boundary
    sweep, the refine build and the last level's advance give what they
    give over the unpadded tile, every bit."""
    from xgboost_tpu.ops.pallas.histogram import fit_rows, row_blocks
    from xgboost_tpu.ops.split import WINDOW

    n, F, block = 700, 6, 256
    n_pad = row_blocks(n, block)[1]
    assert n_pad == 768
    data = _level_data(n, F, 255, 1, 2, seed=3)
    bins_t = data[0].T
    padded, wide = fit_rows(bins_t, n_pad), fit_rows(bins_t, 1024)
    assert padded.shape == (F, n_pad)
    np.testing.assert_array_equal(np.asarray(fit_rows(wide, n_pad)),
                                  np.asarray(padded))

    def sweep(tile):
        return fused_advance_coarse_pallas(
            tile, *data[1:], lo_prev=1, n_prev=2, lo=3, n_level=4,
            missing_bin=254, block_rows=block, interpret=True)

    def refine(tile):
        rel = jnp.asarray(np.arange(n, dtype=np.int32) % 5)   # 4: outside
        window = jnp.asarray(np.arange(4 * F, dtype=np.int32).reshape(
            4, F) % 15)
        return build_hist_pallas(tile, data[1], rel, 4, WINDOW + 4,
                                 block_rows=block, window=window,
                                 missing_bin=254, interpret=True)

    def leaf(tile):
        return advance_leaf_pallas(
            tile, data[2], *data[3:], jnp.arange(8, dtype=jnp.float32),
            n_prev=2, missing_bin=254, block_rows=block, interpret=True)

    for kernel in (sweep, refine, leaf):
        want = jax.tree.leaves(kernel(bins_t))
        for tile in (padded, wide):
            for a, b in zip(jax.tree.leaves(kernel(tile)), want):
                np.testing.assert_array_equal(_bits(a), _bits(b))


def test_advance_leaf_op_kinds(monkeypatch):
    """What ``advance_leaf`` reports: the kernel for a walk-kind level on
    a TPU, the XLA advance off it, for a dense level and wherever a
    column split needs the decisions' psum."""
    n, F, n_prev = 300, 4, 128
    bins, positions, arrs, leaf, missing_bin = _last_level(
        n, F, n_prev, False, 0.8, seed=5)
    walk = {"kind": "walk", "lo": n_prev - 1, "n_level": n_prev,
            "arrs": arrs, "feat_offset": jnp.int32(0)}
    pos_ref = update_positions(bins, positions, *arrs, missing_bin)

    pos, delta, kind = advance_leaf(bins, positions, walk, leaf, missing_bin)
    assert kind == "walk" and delta is None      # the CPU keeps the walk
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(pos_ref))
    pos, delta, kind = advance_leaf(bins, positions, walk, leaf, missing_bin,
                                    interpret=True)
    assert kind == "kernel"
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(pos_ref))
    np.testing.assert_array_equal(_bits(delta), _bits(leaf[pos_ref]))

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def kind_of(prev, **kw):
        out = {}

        def fn(b, p):
            pos, delta, out["kind"] = advance_leaf(b, p, prev, leaf,
                                                   missing_bin, **kw)
            return pos
        out["jaxpr"] = str(jax.make_jaxpr(fn, axis_env=[("cols", 2)])(
            bins, positions))
        return out["kind"], "pallas_call" in out["jaxpr"]

    assert kind_of(walk) == ("kernel", True)
    # column split: the owner's decisions cross the shards by one psum
    assert kind_of(walk, decision_axis="cols") == ("walk", False)
    lo6 = 63
    dense = {"kind": "dense", "lo": lo6, "n_level": 64,
             "arrs": tuple(a[lo6:lo6 + 64] for a in arrs)}
    assert kind_of(dense) == ("dense", False)
    too_wide = dict(walk, lo=2 * ADVANCE_LEAF_MAX_NODES - 1,
                    n_level=2 * ADVANCE_LEAF_MAX_NODES)
    assert kind_of(too_wide) == ("walk", False)


# a shape a case: jit's trace cache does not know the backend is patched
@pytest.mark.parametrize("n,depth,backend,split_mode,kind", [
    (641, 8, "tpu", "row", "kernel"), (642, 6, "tpu", "row", "dense"),
    (643, 8, "cpu", "row", "walk"), (644, 8, "tpu", "col", "walk"),
])
def test_grow_epilogue_counter(monkeypatch, n, depth, backend, split_mode,
                               kind):
    """``xtpu_grow_epilogue_total{kind}``: ``_grow`` under the fused
    schedule takes the kernel below a level past DENSE_LEVEL_MAX on a TPU,
    and only there; one count a traced program."""
    from xgboost_tpu.tree.grow import _grow
    from xgboost_tpu.tree.param import TrainParam

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    F = 6
    args = (jax.ShapeDtypeStruct((n, F), jnp.uint8),
            jax.ShapeDtypeStruct((n, 2), jnp.float32),
            jax.ShapeDtypeStruct((F,), jnp.int32),
            jax.ShapeDtypeStruct((F,), jnp.bool_), jax.random.key(0))
    kwargs = dict(param=TrainParam(max_depth=depth), max_nbins=256,
                  hist_method="fused", has_missing=False)
    if split_mode == "col":
        kwargs.update(axis_name="cols", split_mode="col")

    def trace():
        # traced, never lowered: the CPU cannot compile a Mosaic kernel
        return str(jax.make_jaxpr(
            lambda *a: _grow(*a, **kwargs).delta,
            axis_env=[("cols", 2)])(*args))

    before = grow_epilogue_counts()
    jaxpr = trace()
    after = grow_epilogue_counts()
    grew = {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}
    assert grew == {kind: 1}
    assert ("name=advance_leaf" in jaxpr) == (kind == "kernel")


def test_one_pass_schedule_has_no_epilogue():
    X, y = _binary_data(n=1237, F=5)
    before = grow_epilogue_counts().get("none", 0)
    xgb.train({"objective": "binary:logistic", "max_depth": 3, "max_bin": 32,
               "hist_method": "segment"}, xgb.DMatrix(X, label=y), 1,
              verbose_eval=False)
    assert grow_epilogue_counts().get("none", 0) == before + 1


def _binary_data(n=4000, F=8, missing=False, seed=11):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    y = (np.nan_to_num(X) @ rng.randn(F) > 0).astype(np.float32)
    if missing:
        X[rng.rand(n, F) < 0.1] = np.nan
    return X, y


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device (virtual) platform")
    return xgb.make_data_mesh()


LOSSGUIDE = {"grow_policy": "lossguide", "max_leaves": 10, "max_depth": 0}
# tier -> (extra params, rows); depth 8 is a level past DENSE_LEVEL_MAX:
# the "walk" boundary inside the loop and the walk epilogue below it
TIERS = {
    "resident": ({}, 4000),
    "lossguide": (LOSSGUIDE, 3000),
    "paged": ({}, 3000),
    "mesh-row": ({}, 4096),
    "mesh-row-lossguide": (LOSSGUIDE, 4096),
    "mesh-col-lossguide": ({**LOSSGUIDE, "data_split_mode": "col"}, 3000),
}
CASES = [(tier, missing, 4) for tier in TIERS for missing in (False, True)]
CASES += [(tier, missing, 8) for tier in ("resident", "paged", "mesh-row")
          for missing in (False, True)]
# categorical columns beside the numeric ones: full histograms in the refine
# sweep, the categorical arm of the split search, the bitmask advance
TIERS["resident-cat"] = ({}, 4000)
CASES += [("resident-cat", missing, depth) for depth in (4, 8)
          for missing in (False, True)]


def _with_categories(X, y, seed):
    """Columns 1 and 4 of ``X`` as category codes (40 and 300 of them,
    Zipf-drawn, missing where X was), the label moved by their effects."""
    rng = np.random.RandomState(seed)
    X = X.copy()
    types = ["q"] * X.shape[1]
    for col, n_cat in ((1, 40), (4, 300)):
        codes = (rng.zipf(1.3, len(X)) - 1) % n_cat
        y = np.where(rng.rand(n_cat)[codes] < 0.3, 1.0 - y, y)
        X[:, col] = np.where(np.isnan(X[:, col]), np.nan, codes)
        types[col] = "c"
    return X, y.astype(np.float32), types


def _paged_dmatrix(X, y, tmp_path, monkeypatch):
    """Three streamed pages, held on the page kernels."""
    from xgboost_tpu.data.dmatrix import DataIter

    monkeypatch.setenv("XTPU_PAGE_ROWS", "1024")
    monkeypatch.setenv("XTPU_PAGED_COLLAPSE", "0")

    class It(DataIter):
        def __init__(self):
            super().__init__()
            self.parts = np.array_split(np.arange(len(X)), 3)
            self.i = 0

        def next(self, input_data):
            if self.i >= len(self.parts):
                return 0
            idx = self.parts[self.i]
            input_data(data=X[idx], label=y[idx])
            self.i += 1
            return 1

        def reset(self):
            self.i = 0

    it = It()
    it.cache_prefix = str(tmp_path / "pc")
    return xgb.QuantileDMatrix(it, max_bin=64)


@pytest.mark.parametrize(
    "tier,missing,depth", CASES,
    ids=[f"{t}-{'missing' if m else 'dense'}-d{d}" for t, m, d in CASES])
def test_fused_matches_coarse(tier, missing, depth, tmp_path, monkeypatch,
                              request):
    """'fused' is the coarse scheme rescheduled: identical trees, stats
    included, in every tier that runs the two-level search — resident
    depthwise, lossguide (one-dispatch apply + eval), paged external
    memory (one advance + coarse page body), the row-split mesh (the
    boundary sweep psums the same coarse histogram) under both growers,
    and column split x lossguide (owner-decision advance + feature-local
    eval in one program)."""
    extra, n = TIERS[tier]
    X, y = _binary_data(n=n, F=6, missing=missing, seed=11 + depth)
    params = {"objective": "binary:logistic", "eta": 0.3, "max_bin": 64,
              "max_depth": depth, **extra}
    if tier.startswith("mesh"):
        params["mesh"] = request.getfixturevalue("mesh")

    types = None
    if tier.endswith("-cat"):
        X, y, types = _with_categories(X, y, seed=depth)

    def train(method):
        dm = (_paged_dmatrix(X, y, tmp_path, monkeypatch)
              if tier == "paged" else xgb.DMatrix(
                  X, label=y, feature_types=types,
                  enable_categorical=types is not None))
        return xgb.train({**params, "hist_method": method}, dm,
                         2 if depth == 8 else 3, verbose_eval=False)

    b_c, b_f = train("coarse"), train("fused")
    assert b_f.get_dump(with_stats=True) == b_c.get_dump(with_stats=True)
    if depth == 8 and "grow_policy" not in extra:
        assert max(t.max_depth() for t in b_f.gbm.trees) == 8
    if types is not None:
        assert any(t.is_cat_split.any() for t in b_f.gbm.trees)


@pytest.mark.parametrize("extra", [
    {"gamma": 0.5, "min_child_weight": 5.0},
    {"colsample_bytree": 0.6, "subsample": 0.8, "reg_alpha": 0.5,
     "max_delta_step": 0.7},
    {"objective": "multi:softprob", "num_class": 4},
    {**LOSSGUIDE, "colsample_bylevel": 0.7},
    {**LOSSGUIDE, "monotone_constraints": "(1,-1,0,0,0,0,0,0)"},
], ids=["gamma-mcw", "sampling-alpha-mds", "multiclass",
        "lossguide-bylevel", "lossguide-monotone"])
def test_fused_matches_coarse_under_options(extra):
    rng = np.random.RandomState(12)
    X = rng.randn(1500, 8).astype(np.float32)
    w = rng.randn(8)
    y = ((np.abs(X @ w) * 2).astype(np.int32) % 4 if "num_class" in extra
         else X @ w > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "eta": 0.3, "max_bin": 64,
              "max_depth": 3, **extra}
    b_c, b_f = (xgb.train({**params, "hist_method": m},
                          xgb.DMatrix(X, label=y), 3, verbose_eval=False)
                for m in ("coarse", "fused"))
    assert b_f.get_dump(with_stats=True) == b_c.get_dump(with_stats=True)


SMALL_BIN_CASES = [(b, m, d) for b in (16, 32) for m in (False, True)
                   for d in (3, 8)]


@pytest.mark.parametrize(
    "max_bin,missing,depth", SMALL_BIN_CASES,
    ids=[f"b{b}-{'missing' if m else 'dense'}-d{d}"
         for b, m, d in SMALL_BIN_CASES])
def test_fused_matches_exact_search_at_small_max_bin(max_bin, missing,
                                                     depth):
    """With max_bin <= 32 every fine bin lives inside the refine window,
    so the two-level search space IS the one-pass search's: the fused
    schedule must pick the same splits, walk boundary included."""
    X, y = _binary_data(n=5000, F=6, missing=missing, seed=max_bin + depth)
    params = {"objective": "binary:logistic", "max_depth": depth,
              "max_bin": max_bin}
    b_e, b_f = (xgb.train({**params, "hist_method": m},
                          xgb.DMatrix(X, label=y), 2, verbose_eval=False)
                for m in ("segment", "fused"))
    for te, tf in zip(b_e.gbm.trees, b_f.gbm.trees):
        np.testing.assert_array_equal(te.split_feature, tf.split_feature)
        np.testing.assert_array_equal(te.split_bin, tf.split_bin)
        np.testing.assert_allclose(te.leaf_value, tf.leaf_value,
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("hist_method", ["auto", "fused"])
def test_dispatch_count_resident(monkeypatch, hist_method):
    """A steady resident boosting round is <=2 compiled-program launches
    (the round driver's budget: ``core.steady_round_dispatches``).

    jax runs cache-hit jit calls AND cache-hit eager ops entirely on the
    C++ fast path, invisible to any Python hook. Only a program's FIRST
    execution after compilation routes through Python
    ``ExecuteReplicated``. So the launch count is pinned from two
    directions:

    - steady rounds: the two known entry points (``_fused_round_fn``,
      ``_margin_bad_rows``) are each called exactly once per round and
      ZERO fresh executions happen — no recompiles, no stray eager ops
      with novel shapes;
    - after ``jax.clear_caches()``: ONE round re-executes exactly 2
      distinct compiled programs — every launch is a first launch, so
      the Python path sees them all.
    """
    import jax._src.interpreters.pxla as pxla

    from xgboost_tpu import core

    X, y = _binary_data(n=2000, seed=20)
    dtr = xgb.DMatrix(X, label=y)
    params = {"objective": "binary:logistic", "eta": 0.3, "max_bin": 64,
              "max_depth": 3, "hist_method": hist_method, "seed": 0}
    bst = xgb.train(params, dtr, 3, verbose_eval=False)
    assert bst._fused_round is not None  # the round-program fast path

    calls = {"fused": 0, "margin": 0, "exec": 0}
    orig_fused, orig_margin = core._fused_round_fn, core._margin_bad_rows
    monkeypatch.setattr(core, "_fused_round_fn", lambda *a, **k: (
        calls.__setitem__("fused", calls["fused"] + 1),
        orig_fused(*a, **k))[1])
    monkeypatch.setattr(core, "_margin_bad_rows", lambda *a, **k: (
        calls.__setitem__("margin", calls["margin"] + 1),
        orig_margin(*a, **k))[1])
    orig_exec = pxla.ExecuteReplicated.__call__

    def spy(self, *a, **k):
        calls["exec"] += 1
        return orig_exec(self, *a, **k)

    monkeypatch.setattr(pxla.ExecuteReplicated, "__call__", spy)
    for it in (3, 4, 5):
        bst.update(dtr, it)
    assert calls["fused"] == 3      # one round-program launch per round
    assert calls["margin"] == 3     # one NaN-guard launch per round
    assert calls["exec"] == 0       # zero fresh compiles in steady state

    jax.clear_caches()
    calls["exec"] = 0
    bst.update(dtr, 6)
    assert calls["exec"] <= 2       # the whole round is <=2 programs


def test_fused_rejected_outside_hist_scalar():
    X, y = _binary_data(n=400, F=4, seed=16)
    dm = xgb.DMatrix(X, label=y)
    with pytest.raises(NotImplementedError):
        xgb.train({"objective": "binary:logistic", "tree_method": "approx",
                   "hist_method": "fused"}, dm, 1, verbose_eval=False)


# ---- ADVICE r5 #2: colsample draws come from REAL columns only ----------

def test_col_masks_padded_columns_keep_sampling_parity():
    """col_masks seeded with a base mask of the real columns draws the
    SAME features as the unpadded run — padded mesh-col-split columns no
    longer consume colsample draws."""
    from xgboost_tpu.tree.lossguide import col_masks
    from xgboost_tpu.tree.param import TrainParam

    param = TrainParam(colsample_bytree=0.5, colsample_bylevel=0.7,
                       colsample_bynode=0.7, max_depth=4)
    F, F_pad = 6, 8
    base = np.zeros(F_pad, bool)
    base[:F] = True
    m_ref = col_masks(param, 123, F)
    m_pad = col_masks(param, 123, F_pad, base)
    for depth in range(3):
        ref = m_ref(depth)
        pad = m_pad(depth)
        np.testing.assert_array_equal(pad[:F], ref)
        assert not pad[F:].any()


def test_lossguide_col_split_colsample_matches_single_device(mesh):
    """End to end: F=6 pads to 8 under the 8-way col-split mesh; with
    colsample active the mesh model must still equal the single-device
    model (pre-fix, the padded columns consumed draws and diverged)."""
    X, y = _binary_data(n=3000, F=6, seed=17)
    params = {"objective": "binary:logistic", "eta": 0.3, "max_bin": 64,
              "grow_policy": "lossguide", "max_leaves": 8, "max_depth": 0,
              "colsample_bytree": 0.5, "seed": 9}
    b1 = xgb.train(params, xgb.DMatrix(X, label=y), 3, verbose_eval=False)
    b2 = xgb.train({**params, "mesh": mesh, "data_split_mode": "col"},
                   xgb.DMatrix(X, label=y), 3, verbose_eval=False)
    np.testing.assert_allclose(b1.predict(xgb.DMatrix(X)),
                               b2.predict(xgb.DMatrix(X)),
                               rtol=1e-5, atol=1e-6)
