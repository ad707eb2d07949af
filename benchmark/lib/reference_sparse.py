"""Plain reference with missing values: histogram gradient boosting for
``binary:logistic`` in numpy with XGBoost's sparsity-aware split search
(Chen & Guestrin 2016, Algorithm 3), importing nothing of the program.

``lib/reference.py`` knows no NaN; this is the same booster (exact quantile
cuts, float64 histogram sums, every cut searched, depthwise growth, gain
``GL^2/(HL+l) + GR^2/(HR+l) - G^2/(H+l)``, leaf ``-eta G/(H+l)``, the stump
start) over a matrix that carries NaN:

- cuts come from a column's PRESENT values alone;
- a (node, feature) histogram sums the present entries; the node's total
  less that sum is the missing mass;
- the present rows' cumulative sums are scanned once with the missing mass
  on the right and once with it on the left, and the better of the two is
  kept as the split's ``default_left``;
- the walker sends NaN by ``default_left`` and everything else by
  ``x <= thr``.

Departures from the paper's Algorithm 3, each for the host's sake and none
of the result's:

- the paper enumerates a column's sorted present entries; here they are
  binned first (256 exact-quantile bins of the present values, as
  ``tree_method=hist`` does) and the scan runs over bins;
- the paper's two scans run ascending and descending; here both are read
  off ONE ascending cumulative sum (left = prefix, or prefix + missing):
  the same candidate partitions, the same sums;
- the present entries are kept column-wise (row index and bin id, 5 bytes
  an entry; 1.1 GB at 1,183,747 x 968 x 19%), built in row blocks, so a
  level costs the present entries and not rows x columns, and a feature's
  [nodes, bins] histogram is searched and dropped before the next one's is
  made;
- a candidate needs ``min_child_weight`` of hessian on both sides (XGBoost's
  rule, not the paper's), ties go to missing-right, then the lower bin,
  then the lower feature.

``precision="bfloat16"`` is the control, as in ``lib/reference.py``. The
planted faults of the window's rounds: ``force_right`` (every missing value
sent right: the search scores both directions and picks its cut by the
better, then the learned direction is dropped: the split states
``default_left`` false and the rows are routed so), ``skip_features``
(columns left out of the histograms), ``row_limit`` (half the batch); "NaN
imputed as 0" is this reference on ``impute_zero(X)``.
"""

from __future__ import annotations

import numpy as np

from . import reference as ref
from .reference import (_pmap, _row_blocks, _sub, gradients, logloss,
                        stump_margin, to_bf16)

NBINS = 256
BLOCK_ROWS = 1 << 14


def make_present(X: np.ndarray):
    """The present entries column-wise: ``ptr [F+1]``, ``rows [nnz] int32``,
    ``vals [nnz] float32``; feature f's entries are ``[ptr[f], ptr[f+1])``,
    rows ascending. Built a row block at a time (a block's transpose is the
    only dense temporary)."""
    n, F = X.shape

    def part(lo):
        blk = np.ascontiguousarray(X[lo:lo + BLOCK_ROWS].T)     # [F, r]
        here = ~np.isnan(blk)
        f_idx, r_idx = np.nonzero(here)
        return (np.bincount(f_idx, minlength=F),
                (r_idx + lo).astype(np.int32), blk[here])
    parts = _pmap(part, range(0, n, BLOCK_ROWS))
    counts = np.stack([c for c, _, _ in parts])                 # [blocks, F]
    ptr = np.concatenate([[0], np.cumsum(counts.sum(axis=0))])
    rows = np.empty(ptr[-1], np.int32)
    vals = np.empty(ptr[-1], np.float32)
    at = ptr[:-1].copy()
    for c, r, v in parts:
        src = np.concatenate([[0], np.cumsum(c)])
        for f in np.flatnonzero(c):
            rows[at[f]:at[f] + c[f]] = r[src[f]:src[f + 1]]
            vals[at[f]:at[f] + c[f]] = v[src[f]:src[f + 1]]
        at += c
    return ptr, rows, vals


def make_cuts(ptr, vals, max_bin: int):
    """Per feature, from its present values: the values at ranks i*m/max_bin
    (i = 1..max_bin-1) of the m sorted present values, deduplicated, and the
    maximum as the last cut; none where the column is wholly missing. A
    present value is in bin ``searchsorted(cuts, x, 'left')``; a split after
    bin b sends ``x <= cuts[b]`` left."""
    def one(f):
        col = vals[ptr[f]:ptr[f + 1]]
        m = len(col)
        if m == 0:
            return np.empty(0, np.float32)
        ranks = np.unique(np.minimum(
            m - 1, (np.arange(1, max_bin) * m) // max_bin)).astype(np.int64)
        at_ranks = np.partition(col, ranks)[ranks]
        return np.unique(np.append(at_ranks, col.max())).astype(np.float32)
    return _pmap(one, range(len(ptr) - 1))


def bin_present(ptr, vals, cuts) -> np.ndarray:
    """[nnz] uint8 bin id of every present entry."""
    out = np.empty(len(vals), np.uint8)

    def one(f):
        lo, hi = ptr[f], ptr[f + 1]
        if hi > lo:
            out[lo:hi] = np.searchsorted(cuts[f], vals[lo:hi], side="left")
    _pmap(one, range(len(ptr) - 1))
    return out


def make_binned(X: np.ndarray, max_bin: int):
    """(ptr, rows, bins, cuts): what ``grow_tree`` reads."""
    if max_bin > NBINS:
        raise ValueError(f"the reference bins into at most {NBINS}")
    ptr, rows, vals = make_present(X)
    cuts = make_cuts(ptr, vals, max_bin)
    return ptr, rows, bin_present(ptr, vals, cuts), cuts


def impute_zero(X: np.ndarray) -> np.ndarray:
    """The planted fault: NaN replaced by 0 before anything is binned."""
    return np.nan_to_num(X, nan=0.0)


def _score(G, H, lam):
    """``G^2 / (H + lambda)``; 0 for an empty side under ``lambda = 0``."""
    G, d = np.asarray(G, np.float64), np.asarray(H, np.float64) + lam
    return np.divide(G ** 2, d, out=np.zeros(np.broadcast(G, d).shape),
                     where=d > 0)


def grow_tree(X, binned, g, h, *, max_depth, eta, lam, min_child_weight,
              force_right=False, skip_features=None):
    """One depthwise tree over the first ``len(g)`` rows. Returns the tree
    (dict of arrays in heap layout: children of i are 2i+1, 2i+2; ``dleft``
    is the split's learned default direction) and each row's leaf id."""
    ptr, rows, bins, cuts = binned
    n, F = len(g), len(ptr) - 1
    size = 2 ** (max_depth + 1) - 1
    feat = np.zeros(size, np.int32)
    thr = np.zeros(size, np.float32)
    dleft = np.zeros(size, bool)
    leaf = np.zeros(size, bool)
    value = np.zeros(size, np.float64)
    sum_h = np.zeros(size, np.float64)
    gain_of = np.zeros(size, np.float64)
    exists = np.zeros(size, bool)
    exists[0] = True
    pos = np.zeros(n, np.int32)            # heap id of each row's node
    blocks = _row_blocks(n)
    torch = ref._torch()
    gh = torch.from_numpy(np.stack([g, h], axis=1).astype(np.float64))
    # a column's entries among the first n rows (rows ascend within it)
    ends = np.array([ptr[f] + np.searchsorted(rows[ptr[f]:ptr[f + 1]], n)
                     for f in range(F)], np.int64)
    n_cuts = np.array([len(c) for c in cuts])
    usable = n_cuts > 0
    if skip_features is not None:
        usable &= ~np.asarray(skip_features, bool)
    for depth in range(max_depth + 1):
        lo, cnt = 2 ** depth - 1, 2 ** depth
        rel = pos.astype(np.int64) - lo
        rel[rel < 0] = cnt                 # rows parked in a leaf above
        tot = np.zeros((cnt + 1, 2))
        for k in (0, 1):
            tot[:, k] = np.bincount(rel, weights=(g, h)[k], minlength=cnt + 1)
        Gt, Ht = tot[:cnt, 0], tot[:cnt, 1]
        ids = lo + np.arange(cnt)
        sum_h[ids] = Ht
        value[ids] = -eta * Gt / (Ht + lam)
        if depth == max_depth:
            leaf[ids] = exists[ids]
            break
        parent = _score(Gt, Ht, lam)
        rel_t = torch.from_numpy(rel)

        def search(f, cnt=cnt, Gt=Gt, Ht=Ht, parent=parent, rel_t=rel_t):
            """Best (gain, bin, default_left) a node for feature f."""
            r = torch.from_numpy(rows[ptr[f]:ends[f]]).to(torch.int64)
            idx = rel_t[r] * NBINS + torch.from_numpy(
                bins[ptr[f]:ends[f]]).to(torch.int64)
            acc = torch.zeros(((cnt + 1) * NBINS, 2), dtype=torch.float64) \
                .index_add_(0, idx, gh[r]).numpy()
            acc = acc[:cnt * NBINS].reshape(cnt, NBINS, 2)[:, :n_cuts[f]]
            G, H = np.cumsum(acc[..., 0], -1), np.cumsum(acc[..., 1], -1)
            Gm, Hm = Gt - G[:, -1], Ht - H[:, -1]          # missing mass
            best = np.full(cnt, -np.inf)
            at = np.zeros(cnt, np.int64)
            to_left = np.zeros(cnt, bool)
            for left in (False, True):
                GL = G + Gm[:, None] if left else G
                HL = H + Hm[:, None] if left else H
                GR, HR = Gt[:, None] - GL, Ht[:, None] - HL
                gain = _score(GL, HL, lam) + _score(GR, HR, lam) \
                    - parent[:, None]
                ok = (HL >= min_child_weight) & (HR >= min_child_weight)
                gain = np.where(ok, gain, -np.inf)
                b = gain.argmax(-1)
                top = gain[np.arange(cnt), b]
                better = top > best        # ties stay with missing-right
                best = np.where(better, top, best)
                at = np.where(better, b, at)
                to_left = np.where(better, left, to_left)
            return best, at, to_left
        found = _pmap(search, np.flatnonzero(usable))
        gains = np.full((cnt, F), -np.inf)
        at = np.zeros((cnt, F), np.int64)
        to_left = np.zeros((cnt, F), bool)
        for f, (bg, bb, bl) in zip(np.flatnonzero(usable), found):
            gains[:, f], at[:, f], to_left[:, f] = bg, bb, bl
        bf = gains.argmax(-1)
        bg = gains[np.arange(cnt), bf]
        split = exists[ids] & (bg > 1e-6)
        for k in range(cnt):
            i = ids[k]
            if not exists[i]:
                continue
            if split[k]:
                feat[i] = bf[k]
                thr[i] = cuts[bf[k]][at[k, bf[k]]]
                dleft[i] = to_left[k, bf[k]] and not force_right
                gain_of[i] = bg[k]
                exists[2 * i + 1] = exists[2 * i + 2] = True
            else:
                leaf[i] = True
        # advance rows of split nodes from the raw values; rows of fresh
        # leaves stay put
        node_split = np.zeros(size, bool)
        node_split[ids] = split

        def advance(b):
            for r0, r1 in _sub(b):
                p = pos[r0:r1]
                x = X[np.arange(r0, r1), feat[p]]
                left = np.where(np.isnan(x), dleft[p], x <= thr[p])
                pos[r0:r1] = np.where(node_split[p],
                                      2 * p + np.where(left, 1, 2), p)
        _pmap(advance, blocks)
    idx = np.arange(size)
    tree = {"left": np.where(leaf | ~exists, -1, 2 * idx + 1),
            "right": np.where(leaf | ~exists, -1, 2 * idx + 2),
            "feat": feat, "thr": thr, "dleft": dleft,
            "value": value.astype(np.float32), "sum_hess": sum_h,
            "gain": gain_of}
    return tree, pos


def walk_nodes(tree, X: np.ndarray) -> np.ndarray:
    """Node id every row of X ends in under one tree: NaN goes the split's
    ``dleft`` way, everything else ``x <= thr`` left. Works on any tree
    given as left/right/feat/thr/dleft arrays."""
    left, right = np.asarray(tree["left"]), np.asarray(tree["right"])
    feat, thr = np.asarray(tree["feat"]), np.asarray(tree["thr"], np.float32)
    dleft = np.asarray(tree["dleft"], bool)
    out = np.empty(X.shape[0], np.int64)

    def part(b):
        for lo, hi in _sub(b):
            rows = np.arange(lo, hi)
            p = np.zeros(hi - lo, np.int64)
            for _ in range(len(left)):
                inner = left[p] >= 0
                if not inner.any():
                    break
                x = X[rows, feat[p]]
                go_left = np.where(np.isnan(x), dleft[p], x <= thr[p])
                p = np.where(inner, np.where(go_left, left[p], right[p]), p)
            out[lo:hi] = p
    _pmap(part, _row_blocks(X.shape[0]))
    return out


def walk(tree, X: np.ndarray) -> np.ndarray:
    """Leaf value of every row of X under one tree."""
    return np.asarray(tree["value"], np.float32)[walk_nodes(tree, X)]


def default_dir_gap(tree, X, g, h, lam, min_child_weight) -> float:
    """Whether a tree's default directions were learned: over every split
    node, from the raw rows in float64, the gain of the stated (feature,
    threshold) with the missing rows sent the OTHER way, less the gain as
    stated, over the tree's largest stated gain, where positive; the largest
    over the nodes. A direction that loses nothing by being flipped, or whose
    flip leaves a child under ``min_child_weight``, reads 0. A tree with no
    split reads 0."""
    left, right = np.asarray(tree["left"]), np.asarray(tree["right"])
    feat, thr = np.asarray(tree["feat"]), np.asarray(tree["thr"], np.float32)
    dleft = np.asarray(tree["dleft"], bool)
    size = len(left)
    # sums of the present-left, present-right and missing rows of each node
    sums = np.zeros((size, 3, 2))
    p = np.zeros(X.shape[0], np.int64)
    rows = np.arange(X.shape[0])
    g64, h64 = g.astype(np.float64), h.astype(np.float64)
    for _ in range(size):
        inner = left[p] >= 0
        if not inner.any():
            break
        x = X[rows, feat[p]]
        side = np.where(np.isnan(x), 2, np.where(x <= thr[p], 0, 1))
        key = (p * 3 + side)[inner]
        sums[:, :, 0] += np.bincount(key, weights=g64[inner],
                                     minlength=size * 3).reshape(size, 3)
        sums[:, :, 1] += np.bincount(key, weights=h64[inner],
                                     minlength=size * 3).reshape(size, 3)
        go_left = np.where(side == 2, dleft[p], side == 0)
        p = np.where(inner, np.where(go_left, left[p], right[p]), p)
    nodes = np.flatnonzero(left >= 0)
    if not len(nodes):
        return 0.0
    L, R, M = (sums[nodes, k] for k in range(3))           # [nodes, 2] each

    def gain(to_left):
        a = L + np.where(to_left[:, None], M, 0.0)
        b = R + np.where(to_left[:, None], 0.0, M)
        ok = (a[:, 1] >= min_child_weight) & (b[:, 1] >= min_child_weight)
        return _score(a[:, 0], a[:, 1], lam) + _score(b[:, 0], b[:, 1], lam), ok
    stated, _ = gain(dleft[nodes])
    flipped, ok = gain(~dleft[nodes])
    tot = L + R + M
    top = float(np.max(stated - _score(tot[:, 0], tot[:, 1], lam)))
    if not top > 0:
        return float("inf")
    return float(np.max(np.where(ok, np.maximum(flipped - stated, 0.0), 0.0))
                 / top)


def train(X, y, params, rounds, *, precision="float32", row_limit=None,
          start_margin=None, binned=None, force_right=False,
          skip_features=None):
    """Boost ``rounds`` trees. Returns what the program would hand over:
    trees, the starting margin, the training margin after the last round and
    the training loss after each round, plus ``grad`` (the gradient pairs the
    FIRST round took). ``start_margin`` continues from a state instead of the
    stump. ``row_limit`` plants the half-batch fault: only the first rows are
    trained on (cuts, histograms and leaves; the margin and the losses are
    still over all rows). ``binned``: ``make_binned`` of the same rows, where
    several runs share it."""
    low = precision == "bfloat16"
    if precision not in ("float32", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    rnd = to_bf16 if low else (lambda a: np.asarray(a, np.float32))
    n = X.shape[0]
    k = n if row_limit is None else row_limit
    if binned is None or k != n:
        binned = make_binned(X[:k], int(params["max_bin"]))
    base = stump_margin(y[:k])
    margin = rnd(np.full(n, base, np.float32) if start_margin is None
                 else start_margin)
    trees, losses, grad = [], [], None
    for _ in range(rounds):
        g, h = gradients(margin, y, rnd)
        if grad is None:
            grad = (g, h)
        tree, pos = grow_tree(
            X, binned, g[:k], h[:k], max_depth=int(params["max_depth"]),
            eta=float(params["eta"]), lam=float(params.get("lambda", 1.0)),
            min_child_weight=float(params.get("min_child_weight", 1.0)),
            force_right=force_right, skip_features=skip_features)
        tree["value"] = rnd(tree["value"])
        trees.append(tree)
        delta = tree["value"][pos] if k == n else walk(tree, X)
        margin = rnd(margin + delta)
        losses.append(logloss(margin, y))
    return {"trees": trees, "base_margin": base, "margin": margin,
            "losses": losses, "grad": grad}
