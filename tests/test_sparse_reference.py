"""The program against the benchmark's plain sparsity-aware reference
(``benchmark/lib/reference_sparse.py``: float64 sums over present entries,
both default directions scanned, imports nothing of the program) on seeded
block-missing data (``benchmark/lib/data_sparse.py``), and the paths a
missing slot and a wide matrix take: cuts from present values, the fused
boundary past its VMEM gate, the counters and gauges that say what ran.

On the CPU ``auto`` never promotes, so the two-level search with a missing
slot is named (``fused``, ``coarse``); the kernels run in interpret mode."""

import json
import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

import xgboost_tpu as xgb
from xgboost_tpu.obs import metrics as obs_metrics
from xgboost_tpu.obs import trace as obs_trace
from xgboost_tpu.ops.histogram import fused_advance_coarse
from xgboost_tpu.ops.pallas.histogram import build_hist_pallas
from xgboost_tpu.ops.partition import advance_positions_level
from xgboost_tpu.ops.split import COARSE_B, coarse_bin_ids

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import data_sparse  # noqa: E402
from lib import reference_sparse as rs  # noqa: E402

ROWS, FEATURES, ROUNDS = 20000, 96, 2
PARAMS = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.1,
          "max_bin": 256, "tree_method": "hist"}


@pytest.fixture(scope="module")
def data():
    X, y = data_sparse.bosch_like(ROWS, FEATURES, seed=2 ** 31 + 35)
    return X, y, rs.train(X, y, PARAMS, ROUNDS)


def stated_trees(bst):
    model = json.loads(bytes(bst.save_raw("json")))["learner"]
    return [{"left": np.asarray(t["left_children"], np.int64),
             "right": np.asarray(t["right_children"], np.int64),
             "feat": np.asarray(t["split_indices"], np.int64),
             "thr": np.asarray(t["split_conditions"], np.float32),
             "dleft": np.asarray(t["default_left"], bool),
             "value": np.asarray(t["split_conditions"], np.float32),
             "sum_hess": np.asarray(t["sum_hessian"], np.float64)}
            for t in model["gradient_booster"]["trees"]], float(
                model["learner_model_param"]["base_score"][0])


@pytest.mark.parametrize("hist_method", ["auto", "fused", "coarse"])
def test_program_against_the_sparse_reference(data, hist_method):
    X, y, want = data
    assert 0.79 < np.isnan(X).mean() < 0.83
    dtrain = xgb.DMatrix(X, label=y)
    bst = xgb.train(dict(PARAMS, hist_method=hist_method), dtrain, ROUNDS)
    trees, base = stated_trees(bst)
    assert len(trees) == ROUNDS
    # the root: every row's hessian, and the split the reference finds with
    # its learned direction (the two-level search scores every coarse
    # boundary and the window around the best: the root's cut is inside)
    g, h = want["grad"]
    ref0 = want["trees"][0]
    assert trees[0]["sum_hess"][0] == pytest.approx(
        float(h.sum(dtype=np.float64)), rel=1e-5)
    assert abs(base - want["base_margin"]) < 1e-5
    assert trees[0]["feat"][0] == ref0["feat"][0]
    assert trees[0]["dleft"][0] == ref0["dleft"][0]
    assert trees[0]["thr"][0] == pytest.approx(ref0["thr"][0], abs=1e-6)
    # a default direction differs from the better one nowhere by more than
    # rounding: flipped, no split of the first tree gains
    assert rs.default_dir_gap(trees[0], X, g, h, 1.0, 1.0) < 1e-4
    assert rs.default_dir_gap(ref0, X, g, h, 1.0, 1.0) == 0.0
    # wherever both trees split a node on the same (feature, threshold) and
    # the directions' gains differ, they learned the same direction
    ours = {(int(f), float(t)): bool(d) for f, t, d, l in zip(
        trees[0]["feat"], trees[0]["thr"], trees[0]["dleft"],
        trees[0]["left"]) if l >= 0}
    theirs = {(int(f), float(t)): bool(d) for f, t, d, l in zip(
        ref0["feat"], ref0["thr"], ref0["dleft"], ref0["left"]) if l >= 0}
    shared = set(ours) & set(theirs)
    assert len(shared) >= 3
    flipped = [k for k in shared if ours[k] != theirs[k]]
    for f, t in flipped:       # a tie: no missing row reaches such a node
        one = {"left": np.array([1, -1, -1]), "right": np.array([2, -1, -1]),
               "feat": np.array([f, 0, 0]), "thr": np.float32([t, 0, 0]),
               "dleft": np.array([ours[f, t], False, False])}
        assert rs.default_dir_gap(one, X, g, h, 1.0, 1.0) < 1e-4
    # the state the booster carries is the model it states, NaN walked by
    # each split's stated direction; and the loss follows the reference's
    m = np.full(ROWS, np.float32(base), np.float32)
    for tree in trees:
        m = m + rs.walk(tree, X)
    state = np.asarray(bst._state_of(dtrain, is_train=True)["margin"],
                       np.float32).reshape(-1)
    assert np.abs(state - m).max() / np.abs(m).max() < 1e-5
    assert rs.logloss(m, y) == pytest.approx(want["losses"][-1], rel=2e-3)


@pytest.mark.parametrize("kind", ["rare", "ternary"])
def test_sparse_and_discrete_columns_keep_their_cuts(data, kind):
    """Cuts come from a column's present values alone: a column present in
    under 1% of the rows and one with three distinct values are cut as the
    reference cuts them."""
    X = data[0]
    lay = data_sparse.layout(FEATURES)
    present = (~np.isnan(X)).mean(axis=0)
    if kind == "rare":
        cols = np.flatnonzero((present < 0.01) & ~lay["ternary"])
    else:
        cols = np.flatnonzero(lay["ternary"] & (present > 0.05))
    assert len(cols)
    f = int(cols[0])
    cuts = xgb.DMatrix(X).binned(256).cuts
    ours = cuts.values[cuts.ptrs[f]:cuts.ptrs[f + 1]]
    col = X[~np.isnan(X[:, f]), f]
    ptr, _, vals = rs.make_present(X)
    theirs = rs.make_cuts(ptr, vals, 256)[f]
    assert 0 < len(col) and (len(col) < 0.01 * ROWS) == (kind == "rare")
    if kind == "ternary":
        assert len(np.unique(col)) == 3
    # fewer distinct present values than bins: every one of them is a cut
    # (the program's last cut lies just over the maximum, not on it)
    np.testing.assert_array_equal(theirs, np.unique(col))
    np.testing.assert_array_equal(ours[:-1], theirs[:-1])
    assert theirs[-1] <= ours[-1] <= theirs[-1] + 1e-4


def test_fused_boundary_past_the_vmem_gate_equals_the_kernel():
    """At F = 416 the boundary into a 128-node level is past
    ``fused_advance_coarse``'s 8 MiB accumulator gate, so a TPU takes the
    XLA body there (the advance, ``coarse_bin_ids`` over the matrix, an
    unfused int8x2 build). With ``uint16`` bins and the missing slot set it
    routes the rows and sums the level bit for bit as the kernel does in
    interpret mode."""
    n, F, n_prev, n_level, missing_bin = 384, 416, 64, 128, 256
    assert F * COARSE_B * 2 * n_level * 4 > 8 * 2 ** 20
    lo_prev, lo = n_prev - 1, n_level - 1
    rng = np.random.RandomState(35)
    bins = rng.randint(0, 256, (n, F)).astype(np.uint16)
    bins[rng.rand(n, F) < 0.8] = missing_bin
    bins = jnp.asarray(bins)
    gpair = rng.randn(n, 2).astype(np.float32)
    gpair[:, 1] = np.abs(gpair[:, 1])
    gpair = jnp.asarray(gpair)
    positions = jnp.asarray(
        rng.randint(lo_prev, lo_prev + n_prev, n).astype(np.int32))
    can_split = rng.rand(n_prev) < 0.8
    feat = jnp.asarray(np.where(can_split, rng.randint(0, F, n_prev), -1)
                       .astype(np.int32))
    thr = jnp.asarray(np.where(can_split, rng.randint(0, 255, n_prev), 0)
                      .astype(np.int32))
    dleft = jnp.asarray((rng.rand(n_prev) < 0.5) & can_split)
    prev = {"kind": "dense", "lo": lo_prev, "n_level": n_prev,
            "arrs": (feat, thr, dleft, jnp.asarray(can_split))}

    before = obs_metrics.fused_boundary_counts()
    pos_k, hist_k = fused_advance_coarse(
        bins, gpair, positions, prev, lo, n_level, missing_bin,
        bins_t=bins.T, interpret=True)
    pos_x, hist_x = fused_advance_coarse(
        bins, gpair, positions, prev, lo, n_level, missing_bin,
        bins_t=bins.T, method="segment")
    after = obs_metrics.fused_boundary_counts()
    assert after.get("kernel", 0) == before.get("kernel", 0) + 1
    assert after.get("xla", 0) == before.get("xla", 0) + 1
    # the XLA body's own steps, with the build a TPU gives it
    rel_prev = jnp.where(
        (positions >= lo_prev) & (positions < lo_prev + n_prev),
        positions - lo_prev, n_prev).astype(jnp.int32)
    pos_s = advance_positions_level(
        bins.astype(jnp.float32), positions, rel_prev, feat, thr, dleft,
        jnp.asarray(can_split), missing_bin)
    rel = jnp.where((pos_s >= lo) & (pos_s < lo + n_level), pos_s - lo,
                    n_level).astype(jnp.int32)
    cb = coarse_bin_ids(bins.astype(jnp.int32), missing_bin)
    hist_s = build_hist_pallas(cb.T, gpair, rel, n_level, COARSE_B,
                               precision="int8x2", interpret=True)
    np.testing.assert_array_equal(np.asarray(pos_k), np.asarray(pos_x))
    np.testing.assert_array_equal(np.asarray(pos_k), np.asarray(pos_s))
    np.testing.assert_array_equal(np.asarray(hist_k), np.asarray(hist_s))
    # missing rows land in the coarse histogram's last slot, in both
    assert float(np.abs(np.asarray(hist_k)[:, :, COARSE_B - 1]).sum()) > 0
    scale = float(np.abs(np.asarray(hist_x)).max())
    np.testing.assert_allclose(np.asarray(hist_k) / scale,
                               np.asarray(hist_x) / scale, atol=2e-3)


def test_binned_gauges_and_the_ingest_span(data):
    X = data[0]
    was = obs_trace.tracer()
    ring = obs_trace.enable()
    ring.clear()
    try:
        binned = xgb.DMatrix(X).binned(256)
        spans = [s for s in ring.spans() if s.name == "ingest/bin"]
    finally:
        if was is None:
            obs_trace.disable()
    assert binned.bins.dtype == jnp.uint16 and binned.max_nbins == 257
    got = obs_metrics.binned_layout()
    assert got["bin_bytes"] == 2
    assert got["missing_ratio"] == pytest.approx(np.isnan(X).mean(), abs=1e-9)
    assert len(spans) == 1
    assert spans[0].args["nan"] == int(np.isnan(X).sum())
    assert spans[0].args["dtype"] == "uint16"
    assert spans[0].args["rows"] == ROWS
    # no NaN: the slot goes and a value takes one byte
    dense = xgb.DMatrix(np.nan_to_num(X)).binned(256)
    assert dense.bins.dtype == jnp.uint8 and not dense.has_missing
    assert obs_metrics.binned_layout() == {"missing_ratio": 0.0,
                                           "bin_bytes": 1}


@pytest.mark.parametrize("width, build", [(257, "compare"), (36, "swar"),
                                          (COARSE_B, "swar")])
def test_onehot_counter_says_what_the_kernel_builds(width, build):
    """257 slots (256 bins and the missing one) take the compare one-hot;
    the two-level search's widths (20 coarse, 32 + 4 refined) keep the
    packed build, missing slot or not."""
    n = 128 + width           # a shape no other test traces: jit caches
    rng = np.random.RandomState(width)
    bins_t = jnp.asarray(rng.randint(0, width, (3, n)).astype(np.uint16))
    gpair = jnp.asarray(np.abs(rng.randn(n, 2)).astype(np.float32))
    rel = jnp.asarray(rng.randint(0, 2, n).astype(np.int32))
    before = obs_metrics.hist_onehot_counts()
    hist = build_hist_pallas(bins_t, gpair, rel, 2, width, interpret=True)
    after = obs_metrics.hist_onehot_counts()
    other = "swar" if build == "compare" else "compare"
    assert after.get(build, 0) == before.get(build, 0) + 1
    assert after.get(other, 0) == before.get(other, 0)
    assert float(np.asarray(hist)[:, 0, :, 1].sum()) == pytest.approx(
        float(np.asarray(gpair)[:, 1].sum()), rel=1e-3)
