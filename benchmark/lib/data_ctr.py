"""Seeded click-log data at the shape of the XGBoost paper's Criteo job
(Chen & Guestrin 2016, Table 2: 67 features after their preprocessing of
the terabyte click log). Synthetic: the real logs are not in the repository
(the configuration lists this under ``assumed``).

Block b of a stream depends only on (seed, stream, b), so the driver's
iterator and the blockwise reference regenerate any block, in any order, and
neither ever holds the matrix whole. Columns, all float32, none missing:

    0-12   the log's 13 integer columns: integer-valued, heavy-tailed
           (``floor(scale x E^2)``, E standard exponential: a stretched
           exponential tail, most mass at 0-3, maxima in the thousands)
    13-38  26 count columns (how often a row's id was seen in the first ten
           days): integer-valued, wider (``floor(scale x E^3)``)
    39-64  26 rate columns (the id's average click-through rate): in [0, 1],
           skewed to small values (``u^2``)
    65-66  two more rate columns: Table 2 counts 67 where the text's
           13 + 26 + 26 gives 65

The label is a click with probability about 3%: a nonlinear score over four
integer, three count and five rate columns plus logistic noise, cut at a
fixed threshold.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 18
THREADS = max(1, min(12, (os.cpu_count() or 2) - 1))
FEATURES = 67
N_INT, N_COUNT = 13, 26
THRESHOLD = 6.1       # of the score: about 3% of rows click

_pool = None


def _scales(n: int, lo: float, hi: float) -> np.ndarray:
    return np.geomspace(lo, hi, n).astype(np.float32)


INT_SCALE = _scales(N_INT, 0.6, 40.0)
COUNT_SCALE = _scales(N_COUNT, 2.0, 600.0)


CHUNK = 1 << 14        # rows drawn at a time: temporaries stay in cache


def block(seed: int, stream: int, b: int, rows: int = BLOCK, out=None):
    """(X [rows, 67] float32, y [rows] float32 in {0, 1}): the first ``rows``
    rows of block ``b``, written into ``out = (X, y)`` where given. Drawn
    ``CHUNK`` rows at a time from one generator, so a row's values depend on
    (seed, stream, b) and its place in the block alone."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, stream, b]))
    X, y = out if out is not None else (
        np.empty((rows, FEATURES), np.float32), np.empty(rows, np.float32))
    for lo in range(0, rows, CHUNK):
        n = min(CHUNK, rows - lo)
        Xc = X[lo:lo + n]
        # whole chunks are drawn even where the block is cut short, so the
        # rows before the cut do not depend on it
        e = rng.standard_exponential((CHUNK, N_INT + N_COUNT),
                                     dtype=np.float32)[:n]
        u = rng.random((CHUNK, FEATURES - N_INT - N_COUNT),
                       dtype=np.float32)[:n]
        noise = rng.random(CHUNK, dtype=np.float32)[:n]
        ints, counts = e[:, :N_INT], e[:, N_INT:]
        np.floor(ints * ints * INT_SCALE, out=Xc[:, :N_INT])
        np.floor(counts * counts * counts * COUNT_SCALE,
                 out=Xc[:, N_INT:N_INT + N_COUNT])
        np.multiply(u, u, out=Xc[:, N_INT + N_COUNT:])
        r = Xc[:, N_INT + N_COUNT:]
        z = (0.9 * np.log1p(Xc[:, 0]) - 0.5 * np.log1p(Xc[:, 3])
             + 0.6 * np.sqrt(Xc[:, 5]) * (Xc[:, 7] < 2.0)
             + 0.35 * np.log1p(Xc[:, 13]) - 0.25 * np.log1p(Xc[:, 20])
             + 0.15 * np.log1p(Xc[:, 30]) * r[:, 1]
             + 3.0 * r[:, 0] + 2.0 * r[:, 2] * r[:, 4] - 1.5 * r[:, 9]
             + 1.2 * (r[:, 26] > 0.5))
        # logistic noise from one uniform: log(u / (1 - u))
        noise = np.clip(noise, 1e-7, 1.0 - 1e-7)
        y[lo:lo + n] = z + 0.8 * np.log(noise / (1.0 - noise)) > THRESHOLD
    return X, y


def rows_of_blocks(n_rows: int, first: int, count: int) -> int:
    """Rows that blocks ``first .. first + count`` hold of ``n_rows``."""
    return max(0, min(n_rows, (first + count) * BLOCK) - first * BLOCK)


def blocks(seed: int, stream: int, first: int, count: int, n_rows: int):
    """Blocks ``first .. first + count`` of a stream of ``n_rows`` rows as one
    (X, y), filled by a few threads; the stream's last block is cut short."""
    global _pool
    if _pool is None:
        _pool = ThreadPoolExecutor(THREADS, thread_name_prefix="bench-ctr")
    rows = rows_of_blocks(n_rows, first, count)
    X = np.empty((rows, FEATURES), np.float32)
    y = np.empty(rows, np.float32)

    def fill(i):
        lo = i * BLOCK
        hi = min(rows, lo + BLOCK)
        if hi > lo:
            block(seed, stream, first + i, hi - lo, out=(X[lo:hi], y[lo:hi]))
    list(_pool.map(fill, range(count)))
    return X, y


def n_blocks(n_rows: int) -> int:
    return -(-n_rows // BLOCK)


def criteo_like(rows: int, features: int, seed: int, stream: int = 0):
    """(X [rows, 67] float32, y [rows]) whole: small sizes only (tests, the
    rehearsal). The cell itself never calls this."""
    if features != FEATURES:
        raise ValueError(f"the click-log shape has {FEATURES} features")
    return blocks(seed, stream, 0, n_blocks(rows), rows)


GENERATORS = {"criteo_like": criteo_like}
