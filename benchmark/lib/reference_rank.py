"""Plain reference for ``rank:ndcg``: LambdaMART as published (Burges, "From
RankNet to LambdaRank to LambdaMART: An Overview", MSR-TR-2010-82), in numpy
float64, importing nothing of the program. Trees, cuts, bins and the walker
are ``lib/reference.py``'s.

For each query: rank its rows by current score (stable, best first). Take
every unordered pair of rows with different labels whose better-ranked row is
among the top ``truncation`` (XGBoost's ``MakePairs`` rule for ``topk``:
``for i < k: for j > i`` over the rank order), EACH PAIR ONCE, oriented so that
i has the higher label:

    p        = sigmoid(-(s_i - s_j))
    |delta|  = |(2^y_i - 2^y_j) (1/log2(r_i + 2) - 1/log2(r_j + 2))| / IDCG
    lambda   = -p |delta|             added to g_i, subtracted from g_j
    hessian  = max(p (1 - p) |delta|, 1e-16)       added to h_i and to h_j

with ranks r from 0 and IDCG the query's ideal DCG over all its rows (0: the
query gives no pair). No ``/(|s_i - s_j| + 0.01)``, no hessian x 2, no
``log2(1 + sum)/sum`` per query: those are XGBoost 2.0's own additions
(``lambdarank_obj``), which the configuration lists under ``departures``.

A plain loop over queries; a few threads each take a run of them.
"""

from __future__ import annotations

import numpy as np

from . import reference as ref


def _query_blocks(n_queries: int):
    step = max(1, -(-n_queries // (4 * ref.THREADS)))
    return [(lo, min(n_queries, lo + step))
            for lo in range(0, n_queries, step)]


def _discount(rank):
    return 1.0 / np.log2(rank + 2.0)


def query_lambdas(s: np.ndarray, y: np.ndarray, truncation: int):
    """(g, h) float64 of one query's rows. ``truncation`` 0: every pair."""
    n = len(y)
    g, h = np.zeros(n), np.zeros(n)
    if n < 2:
        return g, h
    order = np.argsort(-s, kind="stable")          # row at each rank
    gain = np.exp2(y) - 1.0
    idcg = float(np.sum(np.sort(gain)[::-1] * _discount(np.arange(n))))
    if idcg <= 0.0:
        return g, h
    k = n if truncation <= 0 else min(truncation, n)
    ys, ss, gs = y[order], s[order], gain[order]   # in rank order
    disc = _discount(np.arange(n))
    a = np.arange(k)[:, None]                      # better-ranked row's rank
    b = np.arange(n)[None, :]                      # the other's
    pair = (b > a) & (ys[a] != ys[b])
    hi_is_a = ys[a] > ys[b]                        # who has the higher label
    s_hi = np.where(hi_is_a, ss[a], ss[b])
    s_lo = np.where(hi_is_a, ss[b], ss[a])
    p = 1.0 / (1.0 + np.exp(s_hi - s_lo))          # sigmoid(-(s_i - s_j))
    delta = np.abs((gs[a] - gs[b]) * (disc[a] - disc[b])) / idcg
    lam = np.where(pair, -p * delta, 0.0)
    hes = np.where(pair, np.maximum(p * (1.0 - p) * delta, 1e-16), 0.0)
    sign = np.where(hi_is_a, 1.0, -1.0)            # +lambda to the higher
    g_rank, h_rank = np.zeros(n), np.zeros(n)
    g_rank[:k] += (sign * lam).sum(axis=1)
    g_rank += -(sign * lam).sum(axis=0)
    h_rank[:k] += hes.sum(axis=1)
    h_rank += hes.sum(axis=0)
    g[order], h[order] = g_rank, h_rank
    return g, h


def lambda_gradients(margin, y, ptr, *, truncation=32, rnd=np.asarray,
                     query_limit=None):
    """(g, h) float32 over all rows (then ``rnd``). ``query_limit`` plants
    the half-the-queries fault: later queries give no gradient."""
    g = np.zeros(len(y), np.float32)
    h = np.zeros(len(y), np.float32)
    n_q = len(ptr) - 1 if query_limit is None else query_limit

    def part(b):
        for q in range(*b):
            lo, hi = int(ptr[q]), int(ptr[q + 1])
            gq, hq = query_lambdas(margin[lo:hi].astype(np.float64),
                                   y[lo:hi].astype(np.float64), truncation)
            g[lo:hi] = rnd(gq.astype(np.float32))
            h[lo:hi] = rnd(hq.astype(np.float32))
    ref._pmap(part, _query_blocks(n_q))
    return g, h


def ndcg_at(margin, y, ptr, k: int) -> float:
    """Mean over queries of NDCG@k with gain 2^label - 1 and discount
    1/log2(rank + 2), ties in score kept in row order; a query with no
    label above 0 counts 1 (XGBoost's ``ndcg`` without the ``-``)."""
    def part(b):
        tot = 0.0
        for q in range(*b):
            lo, hi = int(ptr[q]), int(ptr[q + 1])
            gain = np.exp2(y[lo:hi].astype(np.float64)) - 1.0
            top = min(k, hi - lo) if k > 0 else hi - lo
            disc = _discount(np.arange(top))
            ideal = float(np.sum(np.sort(gain)[::-1][:top] * disc))
            if ideal <= 0.0:
                tot += 1.0
                continue
            order = np.argsort(-margin[lo:hi].astype(np.float64),
                               kind="stable")
            tot += float(np.sum(gain[order[:top]] * disc)) / ideal
        return tot
    n_q = len(ptr) - 1
    return sum(ref._pmap(part, _query_blocks(n_q))) / n_q


def train(X, y, ptr, params, rounds, *, precision="float32",
          start_margin=None, query_limit=None, truncation=None,
          grad_ptr=None, binned=None):
    """Boost ``rounds`` trees from margin 0 (``rank:ndcg`` fits no stump) or
    from ``start_margin``. Returns trees, the margin after the last round,
    ``ndcg@10`` after each round, and the gradient pairs of the FIRST round
    (``grad``: [n, 2] float32). Faults: ``query_limit`` (later queries give no
    gradient), ``truncation`` (0: all pairs), ``grad_ptr`` (the gradient's
    group boundaries, shifted). ``binned``: ``(cuts, bins_t)`` made once and
    shared."""
    low = precision == "bfloat16"
    if precision not in ("float32", "bfloat16"):
        raise ValueError(f"unknown precision {precision!r}")
    rnd = ref.to_bf16 if low else (lambda a: np.asarray(a, np.float32))
    n = X.shape[0]
    cuts, bins_t = binned or make_binned(X, params)
    trunc = int(params["lambdarank_num_pair_per_sample"]) \
        if truncation is None else truncation
    margin = rnd(np.zeros(n, np.float32) if start_margin is None
                 else start_margin)
    trees, ndcgs, first = [], [], None
    for _ in range(rounds):
        g, h = lambda_gradients(
            margin, y, ptr if grad_ptr is None else grad_ptr,
            truncation=trunc, rnd=rnd, query_limit=query_limit)
        if first is None:
            first = np.stack([g, h], axis=1)
        tree, pos = ref.grow_tree(
            bins_t, cuts, g, h, max_depth=int(params["max_depth"]),
            eta=float(params["eta"]), lam=float(params.get("lambda", 1.0)),
            min_child_weight=float(params.get("min_child_weight", 1.0)))
        tree["value"] = rnd(tree["value"])
        trees.append(tree)
        margin = rnd(margin + tree["value"][pos])
        ndcgs.append(ndcg_at(margin, y, ptr, 10))
    return {"trees": trees, "base_margin": 0.0, "margin": margin,
            "ndcgs": ndcgs, "grad": first}


def make_binned(X, params):
    cuts = ref.make_cuts(X, int(params["max_bin"]))
    return cuts, ref.bin_columns(X, cuts)
