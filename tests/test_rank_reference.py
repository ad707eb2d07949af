"""``rank:ndcg`` against the benchmark's plain LambdaMART reference
(``benchmark/lib/reference_rank.py``: numpy float64, the published
algorithm, nothing of the program). The reference is loaded by path, under a
package name of its own, so nothing is put on ``sys.path``.
"""

import importlib
import importlib.util
import os
import sys

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.objective import get_objective

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOPK = {"lambdarank_pair_method": "topk", "ndcg_exp_gain": "true"}


def _reference():
    name = "xtpu_benchlib"
    if name not in sys.modules:
        lib = os.path.join(ROOT, "benchmark", "lib")
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(lib, "__init__.py"),
            submodule_search_locations=[lib])
        pkg = importlib.util.module_from_spec(spec)
        sys.modules[name] = pkg
        spec.loader.exec_module(pkg)
    return importlib.import_module(name + ".reference_rank")


class _Info:
    def __init__(self, labels, ptr):
        self.labels = np.asarray(labels, np.float32)
        self.weights = None
        self.group_ptr = np.asarray(ptr, np.int64)


# a group of one row, a group of equal labels, a group longer than the
# truncation (8 here, 32 in the cell), ordinary ragged ones, and a group of
# 13 whose rows ranked 7 and 8 tie in score: the tie straddles truncation 8,
# and the slot decides which of the two is the last anchor
SIZES = [1, 5, 40, 2, 13, 9]
EQUAL = 1            # index of the group whose labels are all equal
LONGEST = max(SIZES)
STRADDLE = 4         # index of the group whose tie straddles truncation 8


def _ragged(seed):
    rng = np.random.RandomState(seed)
    y = np.concatenate([rng.randint(0, 5, s) for s in SIZES]).astype(
        np.float32)
    ptr = np.concatenate([[0], np.cumsum(SIZES)]).astype(np.int64)
    y[ptr[EQUAL]:ptr[EQUAL + 1]] = 2.0
    s = rng.randn(len(y)).astype(np.float32)
    s[ptr[2] + 3] = s[ptr[2] + 7]            # a tie in score: stable ranks
    pair = _ranked_7_and_8(s, ptr)
    s[pair] = s[pair[0]]                     # they tie now,
    y[pair] = [0.0, 3.0]                     # on labels that make a pair
    return s, y, ptr


def _ranked_7_and_8(s, ptr):
    lo, hi = ptr[STRADDLE], ptr[STRADDLE + 1]
    return lo + np.argsort(-s[lo:hi], kind="stable")[7:9]


def _program_grad(s, y, ptr, truncation, chunk=None):
    obj = get_objective("rank:ndcg", dict(
        TOPK, lambdarank_num_pair_per_sample=truncation))
    info = _Info(y, ptr)
    if chunk is not None:
        obj._chunk_of = lambda layout, block: chunk
    return np.asarray(obj.get_gradient(s, info), np.float64)[:, 0, :]


# (a) f32 on the device against f64: a lambda is a sum of at most 40 pair
# terms, each exp and divide in f32 (relative 1e-6 to 1e-5), with
# cancellation between a row's wins and losses; 2e-4 of the value or 1e-6
# absolute is what the program's own device-against-host test allows
# Truncations: inside the longest group (K < L: the block is [C, K, L]), one
# under, equal to and one over it (K = L - 1, L, L), and none (K = L)
@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("truncation", [8, 0, LONGEST - 1, LONGEST,
                                        LONGEST + 1])
def test_gradient_matches_reference(seed, truncation):
    rr = _reference()
    s, y, ptr = _ragged(seed)
    got = _program_grad(s, y, ptr, truncation)
    g, h = rr.lambda_gradients(s, y, ptr, truncation=truncation)
    np.testing.assert_allclose(got[:, 0], g, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(got[:, 1], h, rtol=2e-4, atol=1e-6)
    assert (got[ptr[0]:ptr[1]] == 0).all()           # one row: no pair
    assert (got[ptr[EQUAL]:ptr[EQUAL + 1]] == 0).all()   # equal labels


# each pair once: a pair of two rows inside the truncation is not counted
# from both sides. Two rows, both in the top 32: one pair, whose lambda the
# formula gives in closed form
def test_pair_inside_truncation_counts_once():
    s = np.asarray([0.3, -0.2], np.float32)
    y = np.asarray([0.0, 1.0], np.float32)
    got = _program_grad(s, y, [0, 2], 32)
    p = 1.0 / (1.0 + np.exp(-0.2 - 0.3))      # the higher label scores lower
    delta = abs((1.0 - 0.0) * (1.0 / np.log2(3.0) - 1.0)) / 1.0
    np.testing.assert_allclose(got[:, 0], [p * delta, -p * delta], rtol=1e-5)
    np.testing.assert_allclose(got[:, 1], [p * (1 - p) * delta] * 2,
                               rtol=1e-5)


# the tie across the truncation boundary goes to the lower slot, as the
# stable argsort of the host loop and of the reference has it: of the two
# tied rows only the one at rank 7 pairs with the rows ranked below both.
# With their scores nudged apart either way, the order the slots give is
# the one whose gradient the program states
def test_tie_across_the_truncation_goes_to_the_lower_slot():
    rr = _reference()
    s, y, ptr = _ragged(5)
    a, b = _ranked_7_and_8(s, ptr)
    assert s[a] == s[b] and a < b and y[a] != y[b]
    rows = slice(ptr[STRADDLE], ptr[STRADDLE + 1])
    got = _program_grad(s, y, ptr, 8)[rows]
    for first in (a, b):
        nudged = s.astype(np.float64)
        nudged[first] += 1e-9               # ``first`` takes rank 7
        g, h = rr.lambda_gradients(nudged, y, ptr, truncation=8)
        close = np.allclose(got[:, 0], g[rows], rtol=2e-4, atol=1e-6) \
            and np.allclose(got[:, 1], h[rows], rtol=2e-4, atol=1e-6)
        assert close == (first == a)


# (b) the parts add up to the whole
def test_gradient_invariant_to_group_order_and_chunk():
    s, y, ptr = _ragged(3)
    whole = _program_grad(s, y, ptr, 8)
    for chunk in (1, len(SIZES)):
        np.testing.assert_allclose(_program_grad(s, y, ptr, 8, chunk=chunk),
                                   whole, rtol=1e-6, atol=1e-9)
    perm = np.random.RandomState(0).permutation(len(SIZES))
    rows = np.concatenate([np.arange(ptr[q], ptr[q + 1]) for q in perm])
    ptr_p = np.concatenate([[0], np.cumsum(np.asarray(SIZES)[perm])])
    moved = _program_grad(s[rows], y[rows], ptr_p, 8)
    np.testing.assert_allclose(moved, whole[rows], rtol=1e-6, atol=1e-9)


# (e) a truncation that is ignored fails this
def test_truncation_changes_the_gradient():
    s, y, ptr = _ragged(4)
    long_group = slice(ptr[2], ptr[3])
    g2 = _program_grad(s, y, ptr, 2)
    g8 = _program_grad(s, y, ptr, 8)
    g_all = _program_grad(s, y, ptr, 0)
    assert np.abs(g2[long_group] - g8[long_group]).max() > 1e-3
    assert np.abs(g8[long_group] - g_all[long_group]).max() > 1e-3
    # a group no longer than the truncation reads the same under both
    short = slice(ptr[1], ptr[2])
    np.testing.assert_allclose(g8[short], g_all[short], rtol=1e-6)


def _ltr(rows=4000, features=12, groups=40, seed=7):
    """Features on a grid of 16 values, so that the program's sketch and the
    reference's exact quantiles both cut at every value."""
    rng = np.random.RandomState(seed)
    X = rng.randint(0, 16, (rows, features)).astype(np.float32)
    score = (X[:, 0] - 0.6 * X[:, 1] + 0.05 * X[:, 2] * X[:, 3]
             + 3.0 * rng.randn(rows))
    ptr = np.linspace(0, rows, groups + 1).astype(np.int64)
    y = np.zeros(rows, np.float32)
    for q in range(groups):
        part = score[ptr[q]:ptr[q + 1]]
        y[ptr[q]:ptr[q + 1]] = np.digitize(
            part, np.quantile(part, [0.6, 0.8, 0.9, 0.97]))
    return X, y, ptr


PARAMS = dict(TOPK, objective="rank:ndcg", lambdarank_num_pair_per_sample=32,
              max_depth=3, eta=0.3, max_bin=256, tree_method="hist")


@pytest.fixture(scope="module")
def trained():
    import json

    X, y, ptr = _ltr()
    dtrain = xgb.DMatrix(X, label=y, group=np.diff(ptr))
    bst = xgb.train(dict(PARAMS, eval_metric="ndcg@10"), dtrain, 2,
                    verbose_eval=False)
    model = json.loads(bytes(bst.save_raw("json")))["learner"]
    return X, y, ptr, dtrain, bst, model["gradient_booster"]["trees"]


# (c) two rounds through xgb.train against two rounds of the reference
def test_two_rounds_match_reference_trees(trained):
    rr = _reference()
    X, y, ptr, dtrain, bst, trees = trained
    want = rr.train(X, y, ptr, PARAMS, 2)
    for got, ref_tree in zip(trees, want["trees"]):
        heap = [(0, 0)]                  # (program's node id, heap id)
        while heap:
            node, h = heap.pop()
            leaf = got["left_children"][node] < 0
            assert leaf == (ref_tree["left"][h] < 0)
            if leaf:
                # a leaf is -eta G / (H + 1) with G a sum of 100 rows'
                # lambdas that nearly cancel: f32 sums against f64
                np.testing.assert_allclose(got["split_conditions"][node],
                                           ref_tree["value"][h], rtol=2e-3,
                                           atol=1e-6)
                continue
            assert got["split_indices"][node] == ref_tree["feat"][h]
            assert got["split_conditions"][node] == ref_tree["thr"][h]
            heap += [(got["left_children"][node], 2 * h + 1),
                     (got["right_children"][node], 2 * h + 2)]
    margin = bst.predict(dtrain, output_margin=True)
    # margins are sums of two such leaves
    np.testing.assert_allclose(margin, want["margin"], rtol=2e-3, atol=2e-6)


# (d) the program's metric against the reference's
@pytest.mark.parametrize("k", [10, 3])
def test_ndcg_metric_matches_reference(trained, k):
    rr = _reference()
    _X, y, ptr, dtrain, bst, _trees = trained
    bst.set_param("eval_metric", f"ndcg@{k}")
    got = float(bst.eval(dtrain).split(":")[-1])
    margin = bst.predict(dtrain, output_margin=True)
    assert got == pytest.approx(rr.ndcg_at(margin, y, ptr, k), abs=2e-6)
    # and a query with no label above 0 counts 1
    assert rr.ndcg_at(np.zeros(3), np.zeros(3), [0, 3], 10) == 1.0
