"""Plain reference with categorical columns, block by block:
``reference_blocks.py``'s booster and streaming comparison with XGBoost
2.0's categorical semantics (``doc/tutorials/categorical.rst``). numpy, and
``reference.py`` / ``reference_blocks.py`` beside it; imports nothing of the
program.

The semantics:

- a categorical column of at most ``max_cat_to_onehot`` categories splits
  one category against the rest; a wider one sorts its categories
  ascending by ``G / (H + lambda)`` and scores every prefix of at most
  ``max_cat_threshold`` categories of that order as the left set;
- a numeric column splits after a cut: ``x <= cut`` goes left;
- a missing value is tried on both sides and the split keeps the side
  (``default_left``); a category the split never saw goes right;
- ``binary:logistic``, exact cuts (no sketch), float64 sums, depthwise
  growth, gain ``GL^2/(HL+l) + GR^2/(HR+l) - G^2/(H+l)``, leaf
  ``-eta G/(H+l)``, the stump's margin to start.

Departures from XGBoost, each the program's choice that the comparison has
to share: the model lists a categorical node's LEFT set (XGBoost's lists
the categories that go right); an empty category sorts last, ties in
order of code, and the ratio's denominator carries ``1e-10``.

Two uses, as ``reference_blocks.py``:

``train``    a whole blockwise booster for SMALL sizes (tests, the control
             and the planted faults).
``numbers``  at the cell's size, from the trees the program states and its
             margin when the window closed: ``reference_blocks.numbers``'
             seven numbers with each row routed by its category's place in
             a node's left set, missing values by ``default_left``, and
             ``split_gap``'s exact search scoring the categorical columns'
             one-hot and sorted-partition splits beside every numeric cut.
"""

from __future__ import annotations

import numpy as np

from . import reference as ref
from . import reference_blocks as rb
from .reference_blocks import Source, array_source, node_sums, split_gain

__all__ = ["Source", "array_source", "train", "numbers", "leaf_ids",
           "make_cuts"]

# rows a task of the streaming comparison walks (whole blocks of them)
GROUP_ROWS = 1 << 20


# ---- cuts and bins -------------------------------------------------------------

def make_cuts(X: np.ndarray, max_bin: int, cat_cols) -> list:
    """Per column: a numeric column's values at ranks i*n/max_bin of its
    present values, deduplicated, and its maximum as the last cut (as
    ``reference.make_cuts``); a categorical column's codes 0..max."""
    out = []
    for f in range(X.shape[1]):
        col = X[:, f]
        col = col[~np.isnan(col)]
        if f in cat_cols:
            out.append(np.arange(int(col.max()) + 1 if col.size else 1,
                                 dtype=np.float32))
            continue
        n = col.size
        ranks = np.unique(np.minimum(
            n - 1, (np.arange(1, max_bin) * n) // max_bin)).astype(np.int64)
        at = np.partition(col, ranks)[ranks]
        out.append(np.unique(np.append(at, col.max())).astype(np.float32))
    return out


def bin_columns(X: np.ndarray, cuts, cat_cols) -> np.ndarray:
    """[F, n] int16 bin ids, feature-major, missing as -1: a numeric
    column's ``searchsorted(cuts, x, 'left')``, a categorical one's code."""
    out = np.empty((X.shape[1], X.shape[0]), np.int16)
    for f in range(X.shape[1]):
        col = X[:, f]
        miss = np.isnan(col)
        if f in cat_cols:
            b = np.where(miss, -1, col).astype(np.int64)
        else:
            b = np.searchsorted(cuts[f], col, side="left")
        out[f] = np.where(miss, -1, np.minimum(b, len(cuts[f]) - 1))
    return out


# ---- routing -----------------------------------------------------------------

def _tables(tree):
    """The arrays ``leaf_ids`` walks by: per node its children, feature,
    threshold, default side, and a flat left-set table for categorical
    nodes (``[nodes, width]`` bools, row-major)."""
    cats = tree.get("cats") or {}
    width = 1 + max((max(v) for v in cats.values() if len(v)), default=0)
    size = len(tree["left"])
    table = np.zeros((size, width), bool)
    for node, members in cats.items():
        table[int(node), np.asarray(members, np.int64)] = True
    return (np.asarray(tree["left"], np.int32),
            np.asarray(tree["right"], np.int32),
            np.asarray(tree["feat"], np.int32),
            np.asarray(tree["thr"], np.float32),
            np.asarray(tree.get("default_left", np.zeros(size, bool)), bool),
            np.asarray(tree.get("is_cat", np.zeros(size, bool)), bool),
            table.reshape(-1), width)


def _goes_left(tables, p, x):
    """Whether rows at nodes ``p`` with values ``x`` of the nodes' split
    columns go left."""
    _l, _r, _f, thr, dl, is_cat, table, width = tables
    go_left = x <= np.take(thr, p)                 # False where missing
    at = np.flatnonzero(np.take(is_cat, p))
    if len(at):
        xc = x[at]
        seen = (xc >= 0) & (xc < width)            # False where missing
        code = np.where(seen, xc, 0).astype(np.int32)
        go_left[at] = np.take(table, p[at] * width + code) & seen
    miss = np.isnan(x)
    if miss.any():
        go_left[miss] = np.take(dl, p[miss])
    return go_left


def leaf_ids(tree, X: np.ndarray, tables=None) -> np.ndarray:
    """The node every row of X ends in, by raw values: a numeric node sends
    ``x <= thr`` left, a categorical node the codes in its left set, and a
    missing value goes to ``default_left``'s side. ``tables``: the tree's
    ``_tables``, where the caller holds them."""
    tables = _tables(tree) if tables is None else tables
    left, right, feat = tables[:3]
    n, F = X.shape
    flat = np.ascontiguousarray(X).reshape(-1)
    idx = np.int32 if n * F < 2 ** 31 else np.int64
    base = np.arange(n, dtype=idx) * F
    p = np.zeros(n, idx)
    for _ in range(len(left)):
        lp = np.take(left, p)
        inner = lp >= 0
        if not inner.any():
            break
        x = np.take(flat, base + np.take(feat, p))
        go_left = _goes_left(tables, p, x)
        p = np.where(inner, np.where(go_left, lp, np.take(right, p)), p)
    return p


def walk(tree, X: np.ndarray) -> np.ndarray:
    return np.take(np.asarray(tree["value"], np.float32), leaf_ids(tree, X))


# ---- the split search of one node ------------------------------------------------

def _candidates(Gc, Hc, Gm, Hm, is_cat, lam, params):
    """A column's candidate splits at one node from its per-bin sums
    ``Gc``/``Hc`` and missing sums: (left G, left H, default_left, bin,
    left set or None) arrays over both missing sides."""
    nb = len(Gc)
    sets = None
    if not is_cat:
        lg, lh, k = np.cumsum(Gc), np.cumsum(Hc), np.arange(nb)
    elif nb <= int(params.get("max_cat_to_onehot", 4)):
        lg = np.array([np.delete(Gc, j).sum() for j in range(nb)])
        lh = np.array([np.delete(Hc, j).sum() for j in range(nb)])
        k = np.arange(nb)
        sets = [np.delete(np.arange(nb), j) for j in range(nb)]
    else:
        order = params.get("_order", "ascending")
        ratio = np.where(Hc <= 0.0, np.inf, Gc / (Hc + lam + 1e-10))
        o = np.argsort(ratio, kind="stable")
        if order == "descending":        # a planted fault: the order reversed
            o = np.argsort(np.where(Hc <= 0.0, np.inf, -ratio),
                           kind="stable")
        k = np.arange(min(nb, int(params.get("max_cat_threshold", 64))))
        lg, lh = np.cumsum(Gc[o])[k], np.cumsum(Hc[o])[k]
        sets = [o[:j + 1] for j in k]
    out = []
    for d in (0, 1):
        out.append((lg + (Gm if d else 0.0), lh + (Hm if d else 0.0), d, k))
    return out, sets


def _best(hists, G, H, cat_cols, params):
    """(gain, feature, default_left, bin, left set) of the best split over
    per-column (Gc, Hc, Gm, Hm) sums; None where nothing is valid."""
    lam = float(params.get("lambda", 1.0))
    mcw = float(params.get("min_child_weight", 1.0))
    best = None
    for f, (Gc, Hc, Gm, Hm) in enumerate(hists):
        if Gc is None:
            continue
        sides, sets = _candidates(Gc, Hc, Gm, Hm, f in cat_cols, lam, params)
        for GL, HL, d, k in sides:
            gain = np.where((HL >= mcw) & (H - HL >= mcw),
                            split_gain(GL, HL, G - GL, H - HL, lam), -np.inf)
            if not len(gain):
                continue
            j = int(np.argmax(gain))
            if best is None or gain[j] > best[0]:
                best = (float(gain[j]), f, bool(d), int(k[j]),
                        None if sets is None else sets[j])
    return best


# ---- the blockwise booster (small sizes) -----------------------------------------

def _grow_tree(bins, cuts, g, h, cat_cols, params, weight, fault,
               left_out):
    """One depthwise tree over ``bins`` ([F, n] int16, -1 missing). Every
    level's sums are float64 bincounts; ``weight`` (None: all rows) is the
    0/1 mask the histograms count; ``left_out`` a column no histogram
    holds. ``fault``: None, or a planted fault (``train``)."""
    F, n = bins.shape
    depth_max = int(params["max_depth"])
    lam, eta = float(params.get("lambda", 1.0)), float(params["eta"])
    size = 2 ** (depth_max + 1) - 1
    tree = {"feat": np.zeros(size, np.int64), "thr": np.zeros(size,
                                                              np.float32),
            "default_left": np.zeros(size, bool),
            "is_cat": np.zeros(size, bool), "cats": {},
            "value": np.zeros(size), "sum_hess": np.zeros(size),
            "gain": np.zeros(size)}
    split_bin = np.zeros(size, np.int64)
    node_split = np.zeros(size, bool)
    leaf = np.zeros(size, bool)
    exists = np.zeros(size, bool)
    exists[0] = True
    pos = np.zeros(n, np.int64)
    gw = g.astype(np.float64) * (1.0 if weight is None else weight)
    hw = h.astype(np.float64) * (1.0 if weight is None else weight)
    search = dict(params)
    if fault == "partition_reversed":
        search["_order"] = "descending"
    for depth in range(depth_max + 1):
        lo, cnt = 2 ** depth - 1, 2 ** depth
        rel = pos - lo
        rel[(rel < 0) | (rel >= cnt)] = cnt
        Gt = np.bincount(rel, gw, cnt + 1)[:cnt]
        Ht = np.bincount(rel, hw, cnt + 1)[:cnt]
        ids = lo + np.arange(cnt)
        tree["sum_hess"][ids] = Ht
        tree["value"][ids] = -eta * Gt / (Ht + lam)
        if depth == depth_max:
            leaf[ids] = exists[ids]
            break
        per_col = []
        for f in range(F):
            if f == left_out:
                per_col.append(None)
                continue
            nb = len(cuts[f])
            b = bins[f].astype(np.int64)
            miss = b < 0
            idx = rel * (nb + 1) + np.where(miss, nb, b)
            Gs = np.bincount(idx, gw, (cnt + 1) * (nb + 1)).reshape(
                cnt + 1, nb + 1)
            Hs = np.bincount(idx, hw, (cnt + 1) * (nb + 1)).reshape(
                cnt + 1, nb + 1)
            per_col.append((Gs, Hs, nb))
        for k in range(cnt):
            i = ids[k]
            if not exists[i]:
                continue
            hists = [None if c is None else (c[0][k, :c[2]], c[1][k, :c[2]],
                                             c[0][k, c[2]], c[1][k, c[2]])
                     for c in per_col]
            hists = [(None,) * 4 if x is None else x for x in hists]
            best = _best(hists, Gt[k], Ht[k], cat_cols, search)
            if best is None or not best[0] > 1e-6:
                leaf[i] = True
                continue
            gain, f, dl, b, left_set = best
            tree["feat"][i], split_bin[i] = f, b
            tree["default_left"][i], tree["gain"][i] = dl, gain
            node_split[i] = True
            if left_set is not None:
                tree["is_cat"][i] = True
                tree["cats"][i] = np.sort(np.asarray(left_set)).tolist()
            else:
                tree["thr"][i] = cuts[f][b]
            exists[2 * i + 1] = exists[2 * i + 2] = True
        # advance the rows of the nodes that split
        at = (pos >= lo) & (pos < lo + cnt) & node_split[pos]
        f = tree["feat"][pos]
        b = bins[f, np.arange(n)].astype(np.int64)
        go_left = b <= split_bin[pos]
        for i in np.flatnonzero(tree["is_cat"][lo:lo + cnt]) + lo:
            rows = at & (pos == i)
            if fault == "threshold_routed":   # a planted fault: the bin
                continue                      # threshold, not the set
            go_left[rows] = np.isin(b[rows], tree["cats"][i])
        go_left = np.where(b < 0, tree["default_left"][pos], go_left)
        pos = np.where(at, 2 * pos + np.where(go_left, 1, 2), pos)
    idx = np.arange(size)
    inner = ~leaf & exists
    tree["left"] = np.where(inner, 2 * idx + 1, -1)
    tree["right"] = np.where(inner, 2 * idx + 2, -1)
    tree["value"] = tree["value"].astype(np.float32)
    tree["cats"] = {int(k): v for k, v in tree["cats"].items()}
    return tree, pos


def train(source: Source, params, rounds, cat_cols, *, precision="float32",
          start_margin=None, row_weight=None, fault=None, left_out=None):
    """Boost ``rounds`` trees over the stream. Returns what
    ``reference_blocks.train`` returns. Small sizes only (the exact cuts
    need each column whole). Planted faults: ``row_weight(b, rows)`` (rows
    the histograms do not count), ``left_out`` (a column no histogram
    holds), ``fault="threshold_routed"`` (a categorical node's rows routed
    by ``bin <= its split bin``, not by its left set, which the tree still
    states) and ``fault="partition_reversed"`` (categories sorted
    descending by ``G / (H + lambda)``)."""
    if precision not in ("float32", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    rnd = ref.to_bf16 if precision == "bfloat16" \
        else (lambda a: np.asarray(a, np.float32))
    spans = [source.span(b) for b in range(source.n_blocks)]
    parts = [source.get(b) for b in range(source.n_blocks)]
    X = np.concatenate([p[0] for p in parts])
    y = np.concatenate([p[1] for p in parts])
    cuts = make_cuts(X, int(params["max_bin"]), cat_cols)
    bins = bin_columns(X, cuts, cat_cols)
    weight = None if row_weight is None else np.concatenate(
        [row_weight(b, hi - lo) for b, (lo, hi) in enumerate(spans)])
    base = ref.stump_margin(y)
    margin = rnd(np.full(len(y), base, np.float32) if start_margin is None
                 else start_margin)
    trees, losses = [], []
    for _ in range(rounds):
        g, h = rb._gradients(margin, y, rnd)
        tree, pos = _grow_tree(bins, cuts, g, h, cat_cols, params, weight,
                               fault, left_out)
        tree["value"] = rnd(tree["value"])
        trees.append(tree)
        margin = rnd(margin + tree["value"][pos])
        losses.append(ref.logloss(margin, y))
    return {"trees": trees, "base_margin": base, "margin": margin,
            "losses": losses}


# ---- the streaming comparison ----------------------------------------------------

def _exact_top_splits(tree, X, g, h, top_levels, cat_cols, params):
    """Per node above ``top_levels`` that the tree splits: (the best gain an
    exact search over every cut of the sampled rows and every categorical
    split finds, routed by the tree's own splits; the gain of the split the
    tree states), both from float64 sums."""
    lam = float(params.get("lambda", 1.0))
    cuts = make_cuts(X, int(params["max_bin"]), cat_cols)
    bins = bin_columns(X, cuts, cat_cols)
    tables = _tables(tree)
    left, right, feat = tables[:3]
    out = {}
    level, rows_of = [0], {0: np.arange(X.shape[0])}
    for _ in range(top_levels):
        nxt = []
        for i in level:
            rows = rows_of.pop(i)
            if left[i] < 0 or len(rows) == 0:
                continue
            gi, hi = g[rows].astype(np.float64), h[rows].astype(np.float64)
            Gt, Ht = gi.sum(), hi.sum()
            hists = []
            for f in range(X.shape[1]):
                b = bins[f, rows].astype(np.int64)
                nb = len(cuts[f])
                miss = b < 0
                hists.append((np.bincount(b[~miss], gi[~miss], nb)[:nb],
                              np.bincount(b[~miss], hi[~miss], nb)[:nb],
                              gi[miss].sum(), hi[miss].sum()))
            best = _best(hists, Gt, Ht, cat_cols, params)
            go_left = _goes_left(tables, np.full(len(rows), i),
                                 X[rows, feat[i]])
            GL, HL = gi[go_left].sum(), hi[go_left].sum()
            out[int(i)] = (-np.inf if best is None else best[0],
                           float(split_gain(GL, HL, Gt - GL, Ht - HL, lam)))
            rows_of[int(left[i])] = rows[go_left]
            rows_of[int(right[i])] = rows[~go_left]
            nxt += [int(left[i]), int(right[i])]
        level = nxt
    return out


def numbers(outputs: dict, source: Source, params: dict, follow_rounds: int,
            stride_blocks: int, top_levels: int, cat_cols,
            detail: dict | None = None):
    """-> {name: value}: ``reference_blocks.numbers``' seven, with
    categorical routing and search (this file's docstring). ``outputs``:
    ``trees`` (each with ``sum_hess``, ``gain``, ``value``, and a
    categorical node's ``is_cat``, ``cats`` and every node's
    ``default_left``), ``warm_rounds``, ``base_margin``, ``start_margin``
    (the margin when the window opened), ``margin`` (when it closed),
    ``replica_gap``, ``rounds_claimed``.

    So that it fits the run's time at 115M rows, only the window's first
    ``follow_rounds`` trees are walked over every row, from
    ``start_margin``; the strided blocks walk every tree from the base
    margin, and ``margin_gap`` is the worse of the start and the end
    margin against that walk. ``node_hess_gap``, ``leaf_gap`` and
    ``gain_gap`` are the followed trees'."""
    trees = outputs["trees"]
    warm = min(int(outputs["warm_rounds"]), len(trees))
    followed = list(range(warm, min(len(trees), warm + follow_rounds)))
    base = np.float32(outputs["base_margin"])
    states = [np.asarray(outputs[k], np.float32).reshape(-1)
              for k in ("start_margin", "margin")]
    state_ok = all(v.shape[0] == source.n_rows and np.isfinite(v).all()
                   for v in states)
    start = states[0] if state_ok else np.zeros(source.n_rows, np.float32)
    lam = float(params.get("lambda", 1.0))
    eta = float(params["eta"])
    strided = set(range(0, source.n_blocks, max(1, stride_blocks)))
    tables = [_tables(t) for t in trees]

    def one(group):
        """A run of consecutive blocks as one task: numpy's loops release
        the interpreter lock, and long ones leave it to the other threads
        most of the time (a task of one 65,536-row block ran threads at a
        third of their rate)."""
        parts = [source.get(b) for b in group]
        X = np.concatenate([x for x, _ in parts])
        y = np.concatenate([v for _, v in parts])
        lo = source.span(group[0])[0]
        m = start[lo:lo + len(y)].copy()
        # the strided blocks' rows within the run
        mine = np.concatenate([np.arange(*source.span(b)) - lo
                               for b in group if b in strided] or
                              [np.zeros(0, np.int64)])
        sums, kept = [], {}
        for t in followed:
            g, h = rb._gradients(m, y)
            node = leaf_ids(trees[t], X, tables[t])
            size = len(trees[t]["left"])
            sums.append((np.bincount(node, g.astype(np.float64), size),
                         np.bincount(node, h.astype(np.float64), size)))
            if len(mine):
                kept[t] = (g[mine], h[mine])
            m = m + np.take(np.asarray(trees[t]["value"], np.float32), node)
        if not len(mine):
            return sums, None, None
        Xs = X[mine]
        ms = np.full(len(mine), base, np.float32)
        gaps = []
        for upto, got in ((warm, states[0]), (len(trees), states[1])):
            for t in range(warm if gaps else 0, upto):
                ms = ms + np.take(np.asarray(trees[t]["value"], np.float32),
                                  leaf_ids(trees[t], Xs, tables[t]))
            gaps.append((float(np.abs(got[lo + mine] - ms).max())
                         if state_ok else float("inf"),
                         float(np.abs(ms).max())))
        return sums, gaps, (Xs, kept)

    leaf_G = [np.zeros(len(trees[t]["left"])) for t in followed]
    leaf_H = [np.zeros(len(trees[t]["left"])) for t in followed]
    worst, largest, sample = [0.0, 0.0], [1e-30, 1e-30], []
    run = max(1, GROUP_ROWS // source.block_rows)
    groups = [list(range(b, min(b + run, source.n_blocks)))
              for b in range(0, source.n_blocks, run)]
    step = 2 * ref.THREADS
    for at in range(0, len(groups), step):
        for sums, gaps, keep in ref._pmap(one, groups[at:at + step]):
            for k, (G, H) in enumerate(sums):
                leaf_G[k] += G
                leaf_H[k] += H
            if gaps is not None:
                for k, (w, big) in enumerate(gaps):
                    worst[k], largest[k] = max(worst[k], w), \
                        max(largest[k], big)
                sample.append(keep)

    parts = {"node_hess": [], "leaf": [], "gain": []}
    for k, t in enumerate(followed):
        tree = trees[t]
        G, H = node_sums(tree, leaf_G[k], leaf_H[k])
        left, right = np.asarray(tree["left"]), np.asarray(tree["right"])
        is_leaf = left < 0
        parts["node_hess"].append(float(
            np.abs(np.asarray(tree["sum_hess"], np.float64) - H).max()
            / max(H[0], 1e-300)))
        want = -eta * G / (H + lam)
        got = np.asarray(tree["value"], np.float64)
        parts["leaf"].append(float(np.sqrt(
            np.sum((H * (got - want) ** 2)[is_leaf])
            / max(np.sum((H * want ** 2)[is_leaf]), 1e-300))))
        inner = np.flatnonzero(~is_leaf)
        if len(inner):
            ref_gain = split_gain(G[left[inner]], H[left[inner]],
                                  G[right[inner]], H[right[inner]], lam)
            stated = np.asarray(tree["gain"], np.float64)[inner]
            parts["gain"].append(float(np.abs(stated - ref_gain).max()
                                       / max(ref_gain.max(), 1e-300)))
    missing = follow_rounds - len(followed)
    out = {k + "_gap": float(max(v)) if v and not missing else float("inf")
           for k, v in parts.items()}

    split_parts = {}
    if followed and sample:
        X = np.concatenate([s[0] for s in sample])
        for t in followed:
            g = np.concatenate([s[1][t][0] for s in sample])
            h = np.concatenate([s[1][t][1] for s in sample])
            found = _exact_top_splits(trees[t], X, g, h, top_levels,
                                      cat_cols, params)
            total = max(sum(best for best, _ in found.values()), 1e-300)
            split_parts[t] = {i: max(0.0, best - got) / total
                              for i, (best, got) in found.items()}
    gaps = [sum(per.values()) for per in split_parts.values() if per]
    out["split_gap"] = float(max(gaps)) if gaps and not missing \
        else float("inf")
    out["margin_gap"] = (max(w / big for w, big in zip(worst, largest))
                         if state_ok else float("inf"))
    out["replica_gap"] = float(outputs["replica_gap"])
    claimed = outputs["rounds_claimed"]
    out["rounds_gap"] = abs(len(trees) - claimed) / max(claimed, 1)
    if detail is not None:
        detail.update({k: [float(f"{x:.3g}") for x in v]
                       for k, v in parts.items()})
        detail["split"] = {str(t): {str(i): float(f"{v:.3g}")
                                    for i, v in per.items()}
                           for t, per in split_parts.items()}
        detail["rows_walked"] = int(sum(len(s[0]) for s in sample))
    return out
