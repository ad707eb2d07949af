"""Seeded learning-to-rank data at a configuration's shape: ``rows`` x
``features`` float32, ``groups`` ragged query groups, graded labels 0-4.

Group sizes: one lognormal draw a query (sigma 0.6, stream ``(seed, 0)``),
scaled so that the clipped, rounded sizes add up to ``rows`` (a bisection on
the scale: the sum is monotone in it), clipped to [8, 1024]; what the rounding
leaves over is trimmed off the last groups. Given ``(rows, groups, seed)`` the
sizes are fixed.

Rows are made a block of ``QUERY_BLOCK`` queries at a time, and block b depends
only on ``(seed, b)`` and the sizes of its queries. Features are standard
normal; a query shifts the first ``QUERY_FEATURES`` of them by an offset of its
own (query-level features: the same value in every row of a query, as a real
LETOR set has them). A row's relevance score is a nonlinear function of ten
features plus noise. Labels are cut from the score's rank WITHIN the query: a
query draws a richness r in (0, 1] and, with probability ``BARREN``, none at
all; its top r x (1%, 3%, 8%, 20%) of rows get labels 4, 3, 2, 1 (Istella's
labels are sparse: most rows of most queries read 0). So every query has a
spread of labels and some have none above 0.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

QUERY_BLOCK = 256
THREADS = 6
SIGMA = 0.6
MIN_SIZE, MAX_SIZE = 8, 1024
QUERY_FEATURES = 12
BARREN = 0.04
LABEL_SHARES = (0.01, 0.03, 0.08, 0.20)      # labels 4, 3, 2, 1 (cumulative)


def group_sizes(rows: int, groups: int, seed: int) -> np.ndarray:
    """[groups] int64 sizes in [MIN_SIZE, MAX_SIZE] that add up to ``rows``."""
    if not groups * MIN_SIZE <= rows <= groups * MAX_SIZE:
        raise ValueError(f"{rows} rows do not fit {groups} groups of "
                         f"{MIN_SIZE} to {MAX_SIZE}")
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0]))
    z = np.exp(SIGMA * rng.standard_normal(groups))

    def sizes_at(scale):
        return np.clip(np.rint(scale * z), MIN_SIZE, MAX_SIZE).astype(np.int64)

    lo, hi = 0.0, float(MAX_SIZE) / float(z.min())
    for _ in range(80):                      # least scale whose sum >= rows
        mid = 0.5 * (lo + hi)
        if sizes_at(mid).sum() >= rows:
            hi = mid
        else:
            lo = mid
    sizes = sizes_at(hi)
    over = int(sizes.sum() - rows)
    g = groups - 1
    while over > 0:                          # trim from the last groups
        cut = min(over, int(sizes[g] - MIN_SIZE))
        sizes[g] -= cut
        over -= cut
        g -= 1
    return sizes


def _block(seed: int, block: int, sizes: np.ndarray, features: int):
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1, block]))
    n, q = int(sizes.sum()), len(sizes)
    X = rng.standard_normal((n, features), dtype=np.float32)
    qof = np.repeat(np.arange(q), sizes)
    shift = rng.standard_normal((q, QUERY_FEATURES), dtype=np.float32)
    X[:, :QUERY_FEATURES] += shift[qof]
    a = X[:, QUERY_FEATURES:QUERY_FEATURES + 10]
    z = (a[:, 0] * a[:, 1] + 0.9 * np.abs(a[:, 2]) - 0.5 * a[:, 3] ** 2
         + 0.8 * a[:, 4] + 0.6 * np.sin(2.0 * a[:, 5]) + 0.4 * a[:, 6] * a[:, 7]
         + 0.5 * np.maximum(a[:, 8], 0.0) - 0.3 * a[:, 9])
    z = z + 0.8 * rng.standard_normal(n, dtype=np.float32)
    rich = np.where(rng.random(q) < BARREN, 0.0, rng.random(q) ** 0.5)
    # rank of each row's score within its query, best first
    order = np.lexsort((-z, qof))
    start = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n) - start[qof]
    share = (rank + 0.5) / sizes[qof]
    y = np.zeros(n, np.float32)
    for cut in LABEL_SHARES:                 # each threshold passed adds one
        y += share < cut * rich[qof]
    return X, y


def istella_like(rows: int, features: int, groups: int, seed: int):
    """(X [rows, features] float32, y [rows] float32 in 0..4, ptr [groups+1]
    int64 group offsets)."""
    if features < QUERY_FEATURES + 10:
        raise ValueError("the LETOR-shaped score reads 22 features")
    sizes = group_sizes(rows, groups, seed)
    ptr = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)

    def fill(b):
        g0, g1 = b * QUERY_BLOCK, min(groups, (b + 1) * QUERY_BLOCK)
        lo, hi = int(ptr[g0]), int(ptr[g1])
        X[lo:hi], y[lo:hi] = _block(seed, b, sizes[g0:g1], features)
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(-(-groups // QUERY_BLOCK))))
    return X, y, ptr


GENERATORS = {"istella_like": istella_like}
