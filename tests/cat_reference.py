"""Plain reference for boosted trees with categorical columns: numpy,
float64 sums, imports nothing of the program.

``binary:logistic``, depthwise growth, histogram splits over given cuts,
with XGBoost 2.0's categorical semantics (``doc/tutorials/categorical.rst``):

- a column of at most ``max_cat_to_onehot`` categories splits one category
  against the rest;
- a wider one sorts its categories ascending by ``G / (H + lambda)`` and
  scores every prefix of that order of at most ``max_cat_threshold``
  categories as the left set, carried as a set of category codes;
- a missing value is tried on both sides, and a split remembers the side
  (``default_left``); a category the split never saw goes right.

Numeric columns split after a bin: ``x <= cut`` goes left. Gain
``GL^2/(HL+l) + GR^2/(HR+l) - G^2/(H+l)``, leaf ``-eta G/(H+l)``, a node
splits when its best gain passes ``max(gamma, 1e-6)`` and both children
hold ``min_child_weight``.

Departures from XGBoost, each the program's choice that the comparison has
to share:

- the left set is the set stated: the model lists the categories that go
  LEFT (XGBoost's lists those that go right);
- an empty category (no hessian in the node) sorts last, ties in order of
  code, and the ratio's denominator carries ``1e-10``;
- a one-hot split sends a missing value with the category when
  ``default_left`` is false, as the program's search scores it;
- the split search is exact over the given cuts, with no sketch; the
  starting margin is handed in (the program's ``base_score``) rather than
  fitted.
"""

from __future__ import annotations

import numpy as np


def bin_matrix(X: np.ndarray, cuts: list, is_cat: np.ndarray):
    """[n, F] int64 bin ids (-1 missing): a numeric column's
    ``searchsorted(cuts, x, 'left')`` clamped into its last bin, a
    categorical column's code."""
    out = np.full(X.shape, -1, np.int64)
    for f in range(X.shape[1]):
        col = X[:, f]
        ok = ~np.isnan(col)
        if is_cat[f]:
            out[ok, f] = col[ok].astype(np.int64)
        else:
            out[ok, f] = np.minimum(
                np.searchsorted(cuts[f], col[ok], side="left"),
                len(cuts[f]) - 1)
    return out


def _gain(GL, HL, GR, HR, lam):
    return (GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam)
            - (GL + GR) ** 2 / (HL + HR + lam))


def _best_split(B, g, h, n_real, is_cat, p):
    """The best split of one node's rows: (gain, feature, default_left,
    bin, left category set or None) or None."""
    lam, mcw = p["lambda"], p["min_child_weight"]
    G, H = g.sum(), h.sum()
    best = None
    for f in range(B.shape[1]):
        nb = int(n_real[f])
        b = B[:, f]
        miss = b < 0
        Gm, Hm = g[miss].sum(), h[miss].sum()
        Gc = np.bincount(b[~miss], g[~miss], nb)[:nb]
        Hc = np.bincount(b[~miss], h[~miss], nb)[:nb]
        sets = None
        if not is_cat[f]:
            left_g, left_h = np.cumsum(Gc), np.cumsum(Hc)
            k = np.arange(nb)
        elif nb <= p["max_cat_to_onehot"]:
            # the other categories' sums, summed: two categories that are
            # the node's only ones give mirror splits of one gain, to the
            # last bit, and the first is taken
            left_g = np.array([np.delete(Gc, j).sum() for j in range(nb)])
            left_h = np.array([np.delete(Hc, j).sum() for j in range(nb)])
            k = np.arange(nb)
            sets = [[c for c in range(nb) if c != j] for j in range(nb)]
        else:
            ratio = np.where(Hc <= 0.0, np.inf, Gc / (Hc + lam + 1e-10))
            order = np.argsort(ratio, kind="stable")
            left_g, left_h = np.cumsum(Gc[order]), np.cumsum(Hc[order])
            k = np.arange(min(nb, p["max_cat_threshold"]))
            left_g, left_h = left_g[k], left_h[k]
            sets = [sorted(order[:j + 1].tolist()) for j in k]
        for d in (0, 1):                    # missing right, missing left
            GL = left_g + (Gm if d else 0.0)
            HL = left_h + (Hm if d else 0.0)
            GR, HR = G - GL, H - HL
            gain = np.where((HL >= mcw) & (HR >= mcw),
                            _gain(GL, HL, GR, HR, lam), -np.inf)
            j = int(np.argmax(gain))
            if best is None or gain[j] > best[0]:
                best = (float(gain[j]), f, bool(d), int(k[j]),
                        None if sets is None else sets[j])
    return best


def grow_tree(B, g, h, n_real, is_cat, p):
    """One depthwise tree over bin ids ``B`` as a dict of heap arrays:
    ``feat``, ``bin``, ``default_left``, ``is_cat``, ``cats`` (left sets),
    ``seen`` (the categories the node's rows hold), ``value`` (leaves),
    ``sum_hess``, ``gain``, ``is_split``; and each row's leaf."""
    depth = p["max_depth"]
    size = 2 ** (depth + 1) - 1
    tree = {"feat": np.full(size, -1), "bin": np.zeros(size, np.int64),
            "default_left": np.zeros(size, bool),
            "is_cat": np.zeros(size, bool), "cats": [None] * size,
            "seen": [None] * size,
            "value": np.zeros(size), "sum_hess": np.zeros(size),
            "gain": np.zeros(size), "is_split": np.zeros(size, bool)}
    pos = np.zeros(len(g), np.int64)
    for level in range(depth + 1):
        lo = 2 ** level - 1
        moved = pos.copy()
        for i in range(lo, 2 * lo + 1):
            rows = np.flatnonzero(pos == i)
            if len(rows) == 0:
                continue
            G, H = g[rows].sum(), h[rows].sum()
            tree["sum_hess"][i] = H
            tree["value"][i] = -p["eta"] * G / (H + p["lambda"])
            if level == depth:
                continue
            best = _best_split(B[rows], g[rows], h[rows], n_real, is_cat, p)
            if best is None or not best[0] > max(p["gamma"], 1e-6):
                continue
            gain, f, dl, b, cats = best
            tree["feat"][i], tree["bin"][i] = f, b
            tree["default_left"][i], tree["gain"][i] = dl, gain
            tree["is_split"][i] = True
            tree["is_cat"][i], tree["cats"][i] = cats is not None, cats
            if cats is not None:
                present = B[rows, f]
                tree["seen"][i] = np.unique(present[present >= 0]).tolist()
            moved[rows] = 2 * i + 1 + ~go_left(tree, i, B[rows, f])
        pos = moved
    return tree, pos


def go_left(tree, i, b):
    """Rows of node ``i`` with bin ids ``b`` of its split column that go
    left (missing: -1)."""
    if tree["is_cat"][i]:
        left = np.isin(b, tree["cats"][i])
    else:
        left = b <= tree["bin"][i]
    return np.where(b < 0, tree["default_left"][i], left)


def walk(tree, B) -> np.ndarray:
    """Leaf value of every row of bin ids ``B``."""
    pos = np.zeros(B.shape[0], np.int64)
    for _ in range(int(np.log2(len(tree["feat"]) + 1))):
        i = pos
        split = tree["is_split"][i]
        if not split.any():
            break
        nxt = pos.copy()
        for node in np.unique(i[split]):
            rows = np.flatnonzero(pos == node)
            left = go_left(tree, node, B[rows, tree["feat"][node]])
            nxt[rows] = 2 * node + 1 + ~left
        pos = nxt
    return tree["value"][pos]


def train(X, y, cuts, is_cat, params, rounds, base_margin):
    """Boost ``rounds`` trees from ``base_margin``; -> (trees, margin)."""
    p = {"eta": 0.3, "lambda": 1.0, "gamma": 0.0, "min_child_weight": 1.0,
         "max_depth": 6, "max_cat_to_onehot": 4, "max_cat_threshold": 64,
         **params}
    B = bin_matrix(X, cuts, is_cat)
    n_real = np.array([len(c) for c in cuts])
    margin = np.full(len(y), base_margin, np.float64)
    trees = []
    for _ in range(rounds):
        prob = 1.0 / (1.0 + np.exp(-margin))
        g, h = prob - y, np.maximum(prob * (1.0 - prob), 1e-16)
        tree, pos = grow_tree(B, g, h, n_real, is_cat, p)
        trees.append(tree)
        margin = margin + tree["value"][pos]
    return trees, margin
