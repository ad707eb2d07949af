"""Plain reference, block by block: ``reference.py``'s booster semantics
(``binary:logistic``, exact quantile cuts, float64 sums, depthwise growth,
the same gain and leaf formulas) worked over a seeded stream of row blocks,
so that neither the raw matrix nor a per-row float64 state of the job's 100M
rows is ever whole on the host. numpy, and ``reference.py`` beside it;
imports nothing of the program.

Two uses:

``train``    a whole blockwise booster, for SMALL sizes (tests, the control
             and the planted faults): its exact cuts need every value of a
             column, so it holds the bin matrix (one byte a value) and
             float32 per-row state, and sums every histogram block by
             block. It equals ``reference.train`` on the same rows
             (``tests/test_mesh_reference.py``).

``numbers``  at the cell's size: from the trees the program STATES
             (thresholds, ``sum_hessian``, ``loss_changes``, leaf values)
             and its margin when the window closed, in ONE streaming pass
             over all rows, the numbers that decide ``correct``:

    node_hess_gap  every node's stated sum of hessians against the sum over
                   the rows that raw-value routing sends there, for the
                   warm-up's trees and the window's first ``follow_rounds``:
                   worst node, over the tree's root sum. The gradient pairs
                   come from the walked margin (float32, tree by tree), as
                   the booster's own rounds take them. A shard left out
                   reads 1/4, half a batch 1/2, a stale margin the
                   hessian's drift.
    leaf_gap       every leaf's stated value against -eta G / (H + lambda)
                   from those sums, as the per-row update it implies: the
                   norm of the difference over the rows (each leaf weighted
                   by its sum of hessians) over the norm of what the sums
                   imply; worst tree. Weighted, because the program keeps
                   its node sums in float32 and a right child's are its
                   parent's less its sibling's: a leaf of a few rows beside
                   a sibling of millions is stated to a few percent, and
                   holds next to no rows. bfloat16 leaves read 2^-9 / sqrt(3)
                   whatever their size.
    gain_gap       every split's stated ``loss_changes`` against the gain
                   from those sums: worst split, over the tree's largest
                   gain.
    split_gap      on one block in ``stride_blocks``: for the followed
                   trees' nodes above ``top_levels``, the gain an exact
                   search over every cut finds (float64 histograms of those
                   rows, routed by the tree's own splits) less the gain of
                   the split the tree states, summed over the nodes, over
                   the sum of the former; worst tree. 0 where every stated
                   split is the argmax of the rows looked at. Summed, so
                   that a node of a few sampled rows, whose argmax is the
                   sample's noise, weighs what its gain weighs.
    margin_gap     on the same blocks: the program's margin when the window
                   closed against the walk of ALL its trees over the raw
                   rows, largest gap over the largest margin. A row clamped
                   into a bin its value does not belong to trains one way
                   and walks the other.
    replica_gap    handed over by the driver: the chips' copies of the
                   newest tree; exact.
    rounds_gap     trees stated against calls x rounds a call; exact.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import numpy as np

from . import reference as ref

NBINS = 256


class Source(NamedTuple):
    """A seeded stream of row blocks: ``block(seed, stream, b, rows)`` gives
    the first ``rows`` rows of block ``b`` as (X float32 [rows, F], y)."""

    block: Callable
    block_rows: int
    seed: int
    stream: int
    n_rows: int

    @property
    def n_blocks(self) -> int:
        return -(-self.n_rows // self.block_rows)

    def span(self, b: int):
        lo = b * self.block_rows
        return lo, min(self.n_rows, lo + self.block_rows)

    def get(self, b: int):
        lo, hi = self.span(b)
        return self.block(self.seed, self.stream, b, hi - lo)


def array_source(X: np.ndarray, y: np.ndarray, block_rows: int) -> Source:
    """Rows that are already in memory, as a stream (tests)."""
    def block(_seed, _stream, b, rows):
        lo = b * block_rows
        return X[lo:lo + rows], y[lo:lo + rows]
    return Source(block, block_rows, 0, 0, X.shape[0])


# ---- routing -----------------------------------------------------------------

def leaf_ids(tree, X: np.ndarray) -> np.ndarray:
    """The node every row of X ends in, by raw values: ``x <= thr`` goes
    left. ``np.take`` over the flat block (it releases the interpreter
    lock, so blocks run side by side on threads)."""
    left, right = np.asarray(tree["left"]), np.asarray(tree["right"])
    feat = np.asarray(tree["feat"], np.int64)
    thr = np.asarray(tree["thr"], np.float32)
    n, F = X.shape
    flat = np.ascontiguousarray(X).reshape(-1)
    base = np.arange(n, dtype=np.int64) * F
    p = np.zeros(n, np.int64)
    for _ in range(len(left)):
        lp = np.take(left, p)
        inner = lp >= 0
        if not inner.any():
            break
        x = np.take(flat, base + np.take(feat, p))
        nxt = np.where(x <= np.take(thr, p), lp, np.take(right, p))
        p = np.where(inner, nxt, p)
    return p


def _gradients(margin, y, rnd=None):
    """``reference.gradients`` for one block, without its thread pool."""
    p = ref.sigmoid(margin)
    g = (p - y).astype(np.float32)
    h = np.maximum(p * (1.0 - p), 1e-16).astype(np.float32)
    return (g, h) if rnd is None else (rnd(g), rnd(h))


def node_sums(tree, leaf_G, leaf_H):
    """Every node's (G, H) from the sums at the nodes rows ended in: a
    parent is the sum of its children (children come after their parent)."""
    left, right = np.asarray(tree["left"]), np.asarray(tree["right"])
    G, H = leaf_G.copy(), leaf_H.copy()
    for i in range(len(left) - 1, -1, -1):
        if left[i] >= 0:
            if not (left[i] > i and right[i] > i):
                raise ValueError("a child's id is not above its parent's")
            G[i] += G[left[i]] + G[right[i]]
            H[i] += H[left[i]] + H[right[i]]
    return G, H


def split_gain(GL, HL, GR, HR, lam):
    return (GL ** 2 / (HL + lam) + GR ** 2 / (HR + lam)
            - (GL + GR) ** 2 / (HL + HR + lam))


# ---- the blockwise booster (small sizes) -------------------------------------

def _grow_tree(bins_blocks, cuts, g, h, spans, weight, *, max_depth, eta,
               lam, min_child_weight):
    """``reference.grow_tree`` with every level's histogram summed block by
    block (``numpy.bincount`` into float64). ``weight[b]`` (None: all rows)
    is the 0/1 mask of block b's rows that the histograms count: a planted
    fault; every row still gets a position and a leaf."""
    F = bins_blocks[0].shape[0]
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
    pos = [np.zeros(hi - lo, np.int32) for lo, hi in spans]
    for depth in range(max_depth + 1):
        lo_n, cnt = 2 ** depth - 1, 2 ** depth
        G = np.zeros((cnt + 1, F, NBINS))
        H = np.zeros((cnt + 1, F, NBINS))
        for b, (lo, hi) in enumerate(spans):
            rel = pos[b].astype(np.int64) - lo_n
            rel[rel < 0] = cnt                 # parked in a leaf above
            gb, hb = g[lo:hi].astype(np.float64), h[lo:hi].astype(np.float64)
            if weight is not None and weight[b] is not None:
                gb, hb = gb * weight[b], hb * weight[b]
            for f in range(F if depth < max_depth else 1):
                idx = rel * NBINS + bins_blocks[b][f]
                G[:, f] += np.bincount(idx, gb, (cnt + 1) * NBINS) \
                    .reshape(cnt + 1, NBINS)
                H[:, f] += np.bincount(idx, hb, (cnt + 1) * NBINS) \
                    .reshape(cnt + 1, NBINS)
        G, H = G[:cnt], H[:cnt]
        Gt, Ht = G[:, 0, :].sum(-1), H[:, 0, :].sum(-1)
        ids = lo_n + np.arange(cnt)
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
        for f in range(F):                     # only real cuts are candidates
            ok[:, f, len(cuts[f]) - 1:] = False
        gain = np.where(ok, gain, -np.inf)
        flat = gain.reshape(cnt, -1)
        best = flat.argmax(-1)
        bg = flat[np.arange(cnt), best]
        split = exists[ids] & (bg > 1e-6)
        bf, bb = best // NBINS, best % NBINS
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
        node_split = np.zeros(size, bool)
        node_split[ids] = split
        for b in range(len(spans)):
            p = pos[b]
            mv = node_split[p] & (p >= lo_n) & (p < lo_n + cnt)
            go_left = bins_blocks[b][feat[p], np.arange(len(p))] <= sbin[p]
            pos[b] = np.where(mv, 2 * p + np.where(go_left, 1, 2),
                              p).astype(np.int32)
    idx = np.arange(size)
    tree = {"left": np.where(leaf | ~exists, -1, 2 * idx + 1),
            "right": np.where(leaf | ~exists, -1, 2 * idx + 2),
            "feat": feat, "thr": thr, "value": value.astype(np.float32),
            "sum_hess": sum_h, "gain": gain_of}
    return tree, np.concatenate(pos)


def train(source: Source, params, rounds, *, precision="float32",
          start_margin=None, row_weight=None):
    """Boost ``rounds`` trees over the stream. Returns what
    ``reference.train`` returns (trees, ``base_margin``, ``margin``,
    ``losses``). Small sizes only: the exact cuts need each column whole, so
    the rows are gathered once for ``reference.make_cuts`` and kept as
    bins. ``row_weight(b, rows) -> 0/1 mask or None`` plants a fault: rows
    the histograms do not count (cuts, base margin, margin and losses are
    over all rows)."""
    if precision not in ("float32", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    rnd = ref.to_bf16 if precision == "bfloat16" \
        else (lambda a: np.asarray(a, np.float32))
    spans = [source.span(b) for b in range(source.n_blocks)]
    parts = [source.get(b) for b in range(source.n_blocks)]
    X = np.concatenate([p[0] for p in parts])
    y = np.concatenate([p[1] for p in parts])
    cuts = ref.make_cuts(X, int(params["max_bin"]))
    bins_t = ref.bin_columns(X, cuts)
    del X, parts
    bins_blocks = [bins_t[:, lo:hi] for lo, hi in spans]
    weight = None if row_weight is None else [
        row_weight(b, hi - lo) for b, (lo, hi) in enumerate(spans)]
    base = ref.stump_margin(y)
    margin = rnd(np.full(len(y), base, np.float32) if start_margin is None
                 else start_margin)
    trees, losses = [], []
    for _ in range(rounds):
        g, h = _gradients(margin, y, rnd)
        tree, pos = _grow_tree(
            bins_blocks, cuts, g, h, spans, weight,
            max_depth=int(params["max_depth"]), eta=float(params["eta"]),
            lam=float(params.get("lambda", 1.0)),
            min_child_weight=float(params.get("min_child_weight", 1.0)))
        tree["value"] = rnd(tree["value"])
        trees.append(tree)
        margin = rnd(margin + tree["value"][pos])
        losses.append(ref.logloss(margin, y))
    return {"trees": trees, "base_margin": base, "margin": margin,
            "losses": losses}


# ---- the streaming comparison --------------------------------------------------

def _exact_top_splits(tree, X, g, h, top_levels: int, max_bin: int, lam: float,
                      min_child_weight: float):
    """Per node above ``top_levels`` that the tree splits: (best gain an
    exact-cut search finds over rows X routed by the tree's own splits, gain
    of the split the tree states), both from float64 sums of (g, h)."""
    cuts = ref.make_cuts(X, max_bin)
    bins_t = ref.bin_columns(X, cuts)
    left, right = np.asarray(tree["left"]), np.asarray(tree["right"])
    feat, thr = np.asarray(tree["feat"]), np.asarray(tree["thr"], np.float32)
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
            best = -np.inf
            for f in range(X.shape[1]):
                b = bins_t[f, rows]
                GL = np.cumsum(np.bincount(b, gi, NBINS))[:len(cuts[f]) - 1]
                HL = np.cumsum(np.bincount(b, hi, NBINS))[:len(cuts[f]) - 1]
                gain = split_gain(GL, HL, Gt - GL, Ht - HL, lam)
                ok = (HL >= min_child_weight) & (Ht - HL >= min_child_weight)
                if ok.any():
                    best = max(best, float(gain[ok].max()))
            go_left = X[rows, feat[i]] <= thr[i]
            GL, HL = gi[go_left].sum(), hi[go_left].sum()
            out[int(i)] = (best, float(split_gain(GL, HL, Gt - GL, Ht - HL,
                                                  lam)))
            rows_of[int(left[i])] = rows[go_left]
            rows_of[int(right[i])] = rows[~go_left]
            nxt += [int(left[i]), int(right[i])]
        level = nxt
    return out


def numbers(outputs: dict, source: Source, params: dict, follow_rounds: int,
            stride_blocks: int, top_levels: int, detail: dict | None = None):
    """-> {name: value} (this file's docstring). ``outputs``: ``trees`` (each
    with ``sum_hess``, ``gain``, ``value``), ``warm_rounds``,
    ``base_margin``, ``margin`` (when the window closed), ``replica_gap``,
    ``rounds_claimed``."""
    trees = outputs["trees"]
    warm = min(int(outputs["warm_rounds"]), len(trees))
    n_summed = min(len(trees), warm + follow_rounds)
    followed = list(range(warm, n_summed))
    base = np.float32(outputs["base_margin"])
    state = np.asarray(outputs["margin"], np.float32).reshape(-1)
    state_ok = state.shape[0] == source.n_rows and np.isfinite(state).all()
    lam = float(params.get("lambda", 1.0))
    eta = float(params["eta"])
    mcw = float(params.get("min_child_weight", 1.0))
    strided = set(range(0, source.n_blocks, max(1, stride_blocks)))

    def one(b):
        X, y = source.get(b)
        lo, hi = source.span(b)
        m = np.full(hi - lo, base, np.float32)
        sums, kept = [], {}
        for t in range(n_summed):
            g, h = _gradients(m, y)
            node = leaf_ids(trees[t], X)
            size = len(trees[t]["left"])
            sums.append((np.bincount(node, g.astype(np.float64), size),
                         np.bincount(node, h.astype(np.float64), size)))
            if b in strided and t in followed:
                kept[t] = (g, h)
            m = m + np.take(np.asarray(trees[t]["value"], np.float32), node)
        gap = None
        if b in strided:
            for t in range(n_summed, len(trees)):
                m = m + np.take(np.asarray(trees[t]["value"], np.float32),
                                leaf_ids(trees[t], X))
            gap = (float(np.abs(state[lo:hi] - m).max()) if state_ok
                   else float("inf"), float(np.abs(m).max()))
        return sums, gap, ((X, kept) if b in strided else None)

    leaf_G = [np.zeros(len(trees[t]["left"])) for t in range(n_summed)]
    leaf_H = [np.zeros(len(trees[t]["left"])) for t in range(n_summed)]
    worst, largest, sample = 0.0, 1e-30, []
    todo = list(range(source.n_blocks))
    step = 4 * ref.THREADS            # results are folded as they come
    for at in range(0, len(todo), step):
        for sums, gap, keep in ref._pmap(one, todo[at:at + step]):
            for t, (G, H) in enumerate(sums):
                leaf_G[t] += G
                leaf_H[t] += H
            if gap is not None:
                worst, largest = max(worst, gap[0]), max(largest, gap[1])
                sample.append(keep)

    parts = {"node_hess": [], "leaf": [], "gain": []}
    for t in range(n_summed):
        tree = trees[t]
        G, H = node_sums(tree, leaf_G[t], leaf_H[t])
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
    missing = warm + follow_rounds - n_summed   # trees that are not there
    out = {k + "_gap": float(max(v)) if v and not missing else float("inf")
           for k, v in parts.items()}

    split_parts = {}
    if followed and sample:
        X = np.concatenate([s[0] for s in sample])
        for t in followed:
            g = np.concatenate([s[1][t][0] for s in sample])
            h = np.concatenate([s[1][t][1] for s in sample])
            found = _exact_top_splits(trees[t], X, g, h, top_levels,
                                      int(params["max_bin"]), lam, mcw)
            total = max(sum(best for best, _ in found.values()), 1e-300)
            split_parts[t] = {i: max(0.0, best - got) / total
                              for i, (best, got) in found.items()}
    gaps = [sum(per.values()) for per in split_parts.values() if per]
    out["split_gap"] = float(max(gaps)) if gaps and not missing \
        else float("inf")
    out["margin_gap"] = worst / largest if state_ok else float("inf")
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


# ---- the control and the planted faults ------------------------------------------

def control_outputs(source: Source, params: dict, rounds_per_call: int,
                    follow_rounds: int, shards: int = 4) -> dict:
    """{case: outputs}, the reference in the program's place. Every case
    shares one sound warm-up call and differs in the window's first rounds:

    sound            the float32 reference itself
    control_bf16     the window's rounds with margin, gradient pairs and leaf
                     values held in bfloat16: the nearest precision below
                     float32
    shard_left_out   the window's histograms miss the last of ``shards``
                     equal row shards (a chip's rows not in the exchange)
    half_batch       the window's histograms miss the second half of every
                     block (a batch half binned)
    state_unchanged  the window's first call returned its state as it got it
    stale_margin     the window's rounds took their gradients from the margin
                     the warm-up started with: they boost its first trees again
    clamped_bin      the margin the window carried took every value above its
                     column's 90th percentile for that percentile: rows
                     clamped into a bin below their own
    replica_differs  one chip's copy of the newest tree differs
    """
    warm = train(source, params, rounds_per_call)
    n = source.n_rows

    def window(**fault):
        run = train(source, params, follow_rounds,
                    start_margin=warm["margin"], **fault)
        return {"trees": warm["trees"] + run["trees"],
                "warm_rounds": rounds_per_call,
                "base_margin": warm["base_margin"], "margin": run["margin"],
                "replica_gap": 0.0,
                "rounds_claimed": rounds_per_call + follow_rounds}

    def last_shard(b, rows):
        lo, _ = source.span(b)
        return (np.arange(lo, lo + rows) < n - n // shards).astype(np.float64)

    def first_half(_b, rows):
        return (np.arange(rows) < rows // 2).astype(np.float64)

    sound = window()
    parts = [source.get(b) for b in range(source.n_blocks)]
    X = np.concatenate([p[0] for p in parts])
    again = warm["trees"][:follow_rounds]
    clamped = np.minimum(X, np.quantile(X[:1 << 16], 0.9, axis=0)
                         .astype(np.float32))
    return {
        "sound": sound,
        "control_bf16": window(precision="bfloat16"),
        "shard_left_out": window(row_weight=last_shard),
        "half_batch": window(row_weight=first_half),
        "state_unchanged": dict(sound, trees=warm["trees"],
                                margin=warm["margin"],
                                rounds_claimed=2 * rounds_per_call),
        "stale_margin": dict(
            sound, trees=warm["trees"] + again,
            margin=warm["margin"] + sum(ref.walk(t, X) for t in again)),
        "clamped_bin": dict(sound, margin=np.float32(sound["base_margin"])
                            + sum(ref.walk(t, clamped)
                                  for t in sound["trees"])),
        "replica_differs": dict(sound, replica_gap=1.0 / 11),
    }
