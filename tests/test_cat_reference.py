"""Categorical columns against the plain reference (``cat_reference.py``).

At a small size shaped like the airline on-time job: 50,000 x 13 with three
categorical columns of 30, 350 and 350 Zipf-drawn categories (one with
missing values) and one of 3 (one category against the rest), a numeric
column with missing values, depth 6. ``max_bin=32`` makes the two-level
search of the ``fused`` schedule exact (every fine bin inside its refine
window), so it and the one-pass search (``auto`` on the CPU) must both pick
the reference's splits: the same feature, default direction and bin, or the
same left set of categories, in every node of every tree.

A categorical split is compared by how it parts the categories the node's
rows hold: a category with no row in the node routes nothing, and two
splits that part them the same, either way round, are one split of one
gain (a one-hot split of a node that holds two categories scores {a}
against {b} as {b} against {a}), which float rounding may state either way;
the comparison then follows the children crosswise.

Tolerances, and why: margins to 1e-4 of their largest. The program sums the
gradient pairs in float32 (the reference in float64): over 50,000 rows a
leaf's sums round at a few parts in 1e6 of themselves, and three trees' leaf
values add up (measured: 1.4e-5 of the largest margin; 1.4e-3 while the
categorical arms took a right child as the parent's sums less the left
one's). Splits: exact, as the gains that order them differ by float32
rounding only.
"""

import json

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.obs.metrics import cat_split_counts

import cat_reference as cr

ROUNDS = 3
PARAMS = {"objective": "binary:logistic", "max_depth": 6, "max_bin": 32}


def _airline_like(n=50_000, seed=0):
    """Flights-like rows: numeric columns, Zipf-drawn categories with a
    tenth of them delay-prone, missing values in a numeric column and in a
    categorical one."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, 13).astype(np.float32)
    types = ["q"] * 13
    score = 0.8 * X[:, 0] + 0.5 * np.sin(2 * X[:, 3]) + 0.4 * X[:, 8]
    for col, n_cat, s in ((6, 30, 1.1), (9, 350, 1.0), (10, 350, 1.0),
                          (12, 3, 0.0)):
        share = np.cumsum(1.0 / np.arange(1, n_cat + 1) ** s)
        codes = rng.permutation(n_cat)[np.minimum(
            np.searchsorted(share / share[-1], rng.rand(n)), n_cat - 1)]
        eff = (np.where(rng.rand(n_cat) < 0.1, 1.5, 0.0)
               + rng.normal(0, 0.4, n_cat)) if n_cat > 3 \
            else np.array([-1.2, 0.0, 1.5])
        score += eff[codes]
        X[:, col] = codes
        types[col] = "c"
    y = (score + rng.logistic(size=n) > 0.8).astype(np.float32)
    X[rng.rand(n) < 0.02, 8] = np.nan
    X[rng.rand(n) < 0.01, 10] = np.nan
    return X, y, types


@pytest.fixture(scope="module")
def job():
    X, y, types = _airline_like()
    dm = xgb.DMatrix(X, label=y, feature_types=types, enable_categorical=True)
    cuts = dm.binned(PARAMS["max_bin"]).cuts
    per_col = [np.asarray(cuts.values[cuts.ptrs[f]:cuts.ptrs[f + 1]])
               for f in range(X.shape[1])]
    is_cat = np.array([t == "c" for t in types])
    return X, y, dm, per_col, is_cat


def _left_set(words):
    return [b for b in range(len(words) * 32)
            if (int(words[b // 32]) >> (b % 32)) & 1]


def _mismatches(tp, tr):
    """Nodes where the program's tree ``tp`` (a TreeModel) and the
    reference's heap tree ``tr`` differ, walking both from the root."""
    out, stack = [], [(0, 0)]
    while stack:
        p, i = stack.pop()
        split = not tp.is_leaf[p]
        if split != bool(tr["is_split"][i]):
            out.append((i, "split or leaf"))
            continue
        if not split:
            continue
        same = (tp.split_feature[p] == tr["feat"][i]
                and bool(tp.default_left[p]) == bool(tr["default_left"][i])
                and bool(tp.is_cat_split[p]) == bool(tr["is_cat"][i]))
        kids = (tp.left_child[p], tp.right_child[p])
        if same and tr["is_cat"][i]:
            # the categories the node's rows hold, as the split parts them
            seen = set(tr["seen"][i])
            got = set(_left_set(tp.cat_words[p])) & seen
            want = set(tr["cats"][i]) & seen
            if got == seen - want:         # the same two parts, mirrored
                kids = kids[::-1]
            same = got in (want, seen - want)
        elif same:
            same = tp.split_bin[p] == tr["bin"][i]
        if not same:
            out.append((i, "split"))
            continue
        stack += [(kids[0], 2 * i + 1), (kids[1], 2 * i + 2)]
    return out


@pytest.mark.parametrize("hist_method", ["fused", "auto"])
def test_categorical_splits_match_the_plain_reference(job, hist_method):
    X, y, dm, cuts, is_cat = job
    before = cat_split_counts()
    bst = xgb.train({**PARAMS, "hist_method": hist_method}, dm, ROUNDS)
    base = float(json.loads(bytes(bst.save_raw("json")))["learner"]
                 ["learner_model_param"]["base_score"][0])
    trees, margin = cr.train(X, y.astype(np.float64), cuts, is_cat,
                             {"max_depth": PARAMS["max_depth"]}, ROUNDS, base)
    for t, (tp, tr) in enumerate(zip(bst.gbm.trees, trees)):
        assert _mismatches(tp, tr) == [], t
    # every kind of split is there: sorted partitions of the wide columns,
    # one category against the rest in the 3-category one, thresholds
    kinds = {"onehot": 0, "partition": 0}
    for tr in trees:
        for i in np.flatnonzero(tr["is_cat"]):
            kinds["onehot" if is_cat[tr["feat"][i]]
                  and len(cuts[tr["feat"][i]]) <= 4 else "partition"] += 1
    assert kinds["partition"] > 10 and kinds["onehot"] > 0, kinds
    after = cat_split_counts()
    for kind in ("partition", "onehot"):
        assert after.get(kind, 0) - before.get(kind, 0) == kinds[kind]
    got = bst.predict(dm, output_margin=True)
    gap = np.abs(np.asarray(got, np.float64) - margin).max()
    assert gap <= 1e-4 * np.abs(margin).max(), gap
