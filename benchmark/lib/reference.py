"""Plain reference: histogram gradient boosting for ``binary:logistic`` in
numpy, written from the published algorithm (XGBoost, Chen & Guestrin 2016;
``tree_method=hist`` semantics) and importing nothing of the program.

Exact per-feature quantile cuts (no sketch), float64 histogram sums, depthwise
growth, gain ``GL^2/(HL+l) + GR^2/(HR+l) - G^2/(H+l)``, leaf ``-eta G/(H+l)``,
starting margin from one Newton step at margin 0 (XGBoost 2.0's stump fit).
No kernels, no subtraction trick, no row sorting: one scatter-add per feature
per level (``torch.index_add_``, the same sum as ``numpy.bincount``,
but it lets threads run side by side). ``precision="bfloat16"`` is the control: the margin, the gradient
pairs and the leaf values are held in bfloat16 (sums still accumulate wide, as
an MXU would), the step that would tempt a later PR.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = max(1, min(12, (os.cpu_count() or 2) - 1))


def to_bf16(x: np.ndarray) -> np.ndarray:
    """float32 -> nearest-even bfloat16, kept in a float32 array."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    r = (u + np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return r.view(np.float32)


_pool = None


def _pmap(fn, items):
    """Map over a few threads (numpy releases the lock in its inner loops).
    One pool for the process, made at first use, so that each thread keeps
    its scratch array."""
    global _pool
    if _pool is None:
        _pool = ThreadPoolExecutor(THREADS, thread_name_prefix="bench-ref")
    return list(_pool.map(fn, items))


SUB = 1 << 16      # rows per inner block: temporaries stay in cache and
                   # under malloc's mmap threshold (fresh pages are slow)


def _torch():
    """torch, for ``index_add_`` only: numpy's ``bincount`` holds the
    interpreter lock, so threads gain nothing from it, and torch's loop
    releases it. Imported at first use, after the window: importing it costs
    seconds, and capping its threads caps OpenMP for the whole process, which
    would slow the program's own ingest."""
    import torch

    torch.set_num_threads(1)
    return torch


def _row_blocks(n: int):
    step = max(1, -(-n // THREADS))
    return [(lo, min(n, lo + step)) for lo in range(0, n, step)]


def _sub(b):
    return [(lo, min(b[1], lo + SUB)) for lo in range(b[0], b[1], SUB)]


def sigmoid(m: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-m.astype(np.float64)))


def logloss(margin: np.ndarray, y: np.ndarray) -> float:
    """Mean binary cross-entropy of a margin, float64, blockwise."""
    def part(b):
        tot = 0.0
        for lo, hi in _sub(b):
            m = margin[lo:hi].astype(np.float64)
            tot += float(np.sum(np.logaddexp(0.0, m) - y[lo:hi] * m))
        return tot
    return sum(_pmap(part, _row_blocks(len(y)))) / len(y)


def gradients(margin, y, rnd):
    """g = p - y, h = max(p (1 - p), 1e-16), float32 (then ``rnd``)."""
    g = np.empty(len(y), np.float32)
    h = np.empty(len(y), np.float32)

    def part(b):
        for lo, hi in _sub(b):
            p = sigmoid(margin[lo:hi])
            g[lo:hi] = rnd((p - y[lo:hi]).astype(np.float32))
            h[lo:hi] = rnd(np.maximum(p * (1.0 - p), 1e-16).astype(np.float32))
    _pmap(part, _row_blocks(len(y)))
    return g, h


def make_cuts(X: np.ndarray, max_bin: int):
    """Per feature: the values at ranks i*n/max_bin (i=1..max_bin-1) of the
    sorted column, deduplicated, and the maximum as the last cut. A row is in
    bin ``searchsorted(cuts, x, 'left')``; a split after bin b sends
    ``x <= cuts[b]`` left (the program's model format says ``<=``, so the
    reference states its trees the same way and one walker reads both)."""
    n = X.shape[0]
    ranks = np.unique(np.minimum(
        n - 1, (np.arange(1, max_bin) * n) // max_bin)).astype(np.int64)

    def one(f):
        col = np.ascontiguousarray(X[:, f])
        at_ranks = np.partition(col, ranks)[ranks]
        return np.unique(np.append(at_ranks, col.max())).astype(np.float32)
    return _pmap(one, range(X.shape[1]))


def bin_columns(X: np.ndarray, cuts) -> np.ndarray:
    """[F, n] uint8 bin ids, feature-major."""
    out = np.empty((X.shape[1], X.shape[0]), np.uint8)

    def part(b):
        for lo, hi in _sub(b):
            blk = np.ascontiguousarray(X[lo:hi].T)
            for f in range(X.shape[1]):
                out[f, lo:hi] = np.searchsorted(cuts[f], blk[f], side="left")
    _pmap(part, _row_blocks(X.shape[0]))
    return out


def stump_margin(y: np.ndarray) -> float:
    """One Newton step from margin 0: -sum(g)/sum(h), g = 0.5 - y, h = 0.25."""
    return float((np.mean(y, dtype=np.float64) - 0.5) / 0.25)


def grow_tree(bins_t, cuts, g, h, *, max_depth, eta, lam, min_child_weight,
              nbins=256):
    """One depthwise tree. Returns the tree (dict of arrays in heap layout:
    children of i are 2i+1, 2i+2) and each row's leaf id."""
    F, n = bins_t.shape
    size = 2 ** (max_depth + 1) - 1
    feat = np.zeros(size, np.int32)
    thr = np.zeros(size, np.float32)
    sbin = np.zeros(size, np.int32)
    leaf = np.zeros(size, bool)
    value = np.zeros(size, np.float64)
    sum_h = np.zeros(size, np.float64)
    gain_of = np.zeros(size, np.float64)
    exists = np.zeros(size, bool)
    exists[0] = True
    pos = np.zeros(n, np.int32)            # heap id of each row's node
    blocks = _row_blocks(n)
    torch = _torch()
    bins = torch.from_numpy(bins_t)
    gh = torch.from_numpy(np.stack([g, h], axis=1)).to(torch.float64)
    for depth in range(max_depth + 1):
        lo, cnt = 2 ** depth - 1, 2 ** depth
        last = depth == max_depth          # last level: leaf sums only
        width = (cnt + 1) * (1 if last else nbins)

        def hist(b, lo=lo, cnt=cnt, last=last, width=width):
            r0, r1 = b
            rel = torch.from_numpy(pos[r0:r1]).to(torch.int64) - lo
            rel[rel < 0] = cnt         # rows parked in a leaf above: spare slot
            pair = gh[r0:r1]
            if last:
                return torch.zeros((width, 2), dtype=torch.float64) \
                    .index_add_(0, rel, pair).numpy()[None]
            rel *= nbins
            acc = torch.zeros((F, width, 2), dtype=torch.float64)
            for f in range(F):
                acc[f].index_add_(0, rel + bins[f, r0:r1], pair)
            return acc.numpy()
        acc = sum(_pmap(hist, blocks))
        if last:
            Gt, Ht = acc[0, :cnt, 0], acc[0, :cnt, 1]
        else:
            acc = acc[:, :cnt * nbins].reshape(F, cnt, nbins, 2)
            G, H = acc[..., 0].swapaxes(0, 1), acc[..., 1].swapaxes(0, 1)
            Gt, Ht = G[:, 0, :].sum(-1), H[:, 0, :].sum(-1)    # [cnt]
        ids = lo + np.arange(cnt)
        sum_h[ids] = Ht
        value[ids] = -eta * Gt / (Ht + lam)
        if depth == max_depth:
            leaf[ids] = exists[ids]
            break
        GL, HL = np.cumsum(G, -1), np.cumsum(H, -1)
        GR, HR = Gt[:, None, None] - GL, Ht[:, None, None] - HL
        gain = (GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam)
                - (Gt ** 2 / (Ht + lam))[:, None, None])
        ok = (HL >= min_child_weight) & (HR >= min_child_weight)
        for f in range(F):                 # only real cuts are candidates
            ok[:, f, len(cuts[f]) - 1:] = False
        gain = np.where(ok, gain, -np.inf)
        flat = gain.reshape(cnt, -1)
        best = flat.argmax(-1)
        bg = flat[np.arange(cnt), best]
        split = exists[ids] & (bg > 1e-6)
        bf, bb = best // nbins, best % nbins
        for k in range(cnt):
            i = ids[k]
            if not exists[i]:
                continue
            if split[k]:
                feat[i], sbin[i] = bf[k], bb[k]
                thr[i] = cuts[bf[k]][bb[k]]
                gain_of[i] = bg[k]
                exists[2 * i + 1] = exists[2 * i + 2] = True
            else:
                leaf[i] = True
        # advance rows of split nodes; rows of fresh leaves stay put
        node_split = np.zeros(size, bool)
        node_split[ids] = split

        def advance(b, lo=lo, cnt=cnt):
            for r0, r1 in _sub(b):
                p = pos[r0:r1]
                mv = node_split[p] & (p >= lo) & (p < lo + cnt)
                left = bins_t[feat[p], np.arange(r0, r1)] <= sbin[p]
                pos[r0:r1] = np.where(mv, 2 * p + np.where(left, 1, 2), p)
        _pmap(advance, blocks)
    idx = np.arange(size)
    tree = {"left": np.where(leaf | ~exists, -1, 2 * idx + 1),
            "right": np.where(leaf | ~exists, -1, 2 * idx + 2),
            "feat": feat, "thr": thr, "value": value.astype(np.float32),
            "sum_hess": sum_h, "gain": gain_of}
    return tree, pos


def walk(tree, X: np.ndarray) -> np.ndarray:
    """Leaf value of every row of X under one tree: ``x <= thr`` goes left.
    Works on any tree given as left/right/feat/thr/value arrays."""
    left, right = np.asarray(tree["left"]), np.asarray(tree["right"])
    feat, thr = np.asarray(tree["feat"]), np.asarray(tree["thr"], np.float32)
    value = np.asarray(tree["value"], np.float32)
    depth_cap = len(left)
    out = np.empty(X.shape[0], np.float32)

    def part(b):
        for lo, hi in _sub(b):
            rows = np.arange(lo, hi)
            p = np.zeros(hi - lo, np.int64)
            for _ in range(depth_cap):
                inner = left[p] >= 0
                if not inner.any():
                    break
                x = X[rows, feat[p]]
                nxt = np.where(x <= thr[p], left[p], right[p])
                p = np.where(inner, nxt, p)
            out[lo:hi] = value[p]
    _pmap(part, _row_blocks(X.shape[0]))
    return out


def train(X, y, params, rounds, *, precision="float32", X_eval=None,
          y_eval=None, row_limit=None, start_margin=None,
          start_eval_margin=None):
    """Boost ``rounds`` trees. Returns what the program would hand over:
    trees, the starting margin, the training margin after the last round, the
    training loss after each round, and (with an eval set) its loss after each
    round. ``start_margin`` (and ``start_eval_margin``) continue from a state
    instead of the stump: the rounds of a continuation call. ``row_limit``
    plants the half-batch fault: only the first rows are trained on (the
    margin and losses are still over all rows)."""
    low = precision == "bfloat16"
    if precision not in ("float32", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    rnd = to_bf16 if low else (lambda a: np.asarray(a, np.float32))
    n = X.shape[0]
    k = n if row_limit is None else row_limit
    cuts = make_cuts(X[:k], int(params["max_bin"]))
    bins_t = bin_columns(X, cuts)
    base = stump_margin(y[:k])
    margin = rnd(np.full(n, base, np.float32) if start_margin is None
                 else start_margin)
    m_eval = None
    if X_eval is not None:
        m_eval = rnd(np.full(X_eval.shape[0], base, np.float32)
                     if start_eval_margin is None else start_eval_margin)
    trees, losses, eval_losses = [], [], []
    for _ in range(rounds):
        g, h = gradients(margin, y, rnd)
        tree, pos = grow_tree(
            bins_t[:, :k], cuts, g[:k], h[:k],
            max_depth=int(params["max_depth"]), eta=float(params["eta"]),
            lam=float(params.get("lambda", 1.0)),
            min_child_weight=float(params.get("min_child_weight", 1.0)))
        tree["value"] = rnd(tree["value"])
        trees.append(tree)
        delta = tree["value"][pos] if k == n else walk(tree, X)
        margin = rnd(margin + delta)
        losses.append(logloss(margin, y))
        if X_eval is not None:
            m_eval = rnd(m_eval + walk(tree, X_eval))
            eval_losses.append(logloss(m_eval, y_eval))
    return {"trees": trees, "base_margin": base, "margin": margin,
            "eval_margin": m_eval, "losses": losses,
            "eval_losses": eval_losses}
