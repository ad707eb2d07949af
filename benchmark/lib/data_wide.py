"""Seeded dense data at the shape of the PASCAL Large Scale Learning
Challenge's *epsilon* set (LIBSVM's ``epsilon_normalized``: 400,000 training
rows x 2,000 dense float features, a balanced binary label). Synthetic: the
file is not in the repository (the configuration lists this under
``assumed``).

Block b of a stream depends only on (seed, stream, b), so the first k rows
do not depend on n, a few threads fill the matrix side by side, and the
comparison can regenerate any block.

    values    2,000 standard-normal columns (zero mean, unit spread: the
              set's features are standardised), each row then scaled to
              unit L2 norm as ``epsilon_normalized``'s are; float32, none
              missing, every column continuous, so all 256 bins fill
    label     1 with probability about one half: a nonlinear score over 32
              columns of the standardised values plus logistic noise, cut
              at 0. One column (``layout()["strong"]``) carries most of the
              signal; the other 31 lie across the width, so every feature
              block and group of the program's kernels holds some

The layout (which columns the label reads) depends on the feature count
alone, not on the seed.
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 14
THREADS = max(1, min(12, (os.cpu_count() or 2) - 1))
LAYOUT_SEED = 20080101
SIGNAL = 32                   # columns the label reads
STRONG_WEIGHT = 2.5

_pool = None


@functools.lru_cache(maxsize=4)
def layout(features: int) -> dict:
    """The columns the label reads and their weights: ``strong``, the one
    that carries most of the signal, and ``cols`` / ``weights`` of the
    other 31 (``cols[k]`` takes form ``k % 4`` of ``_score``)."""
    if features < SIGNAL:
        raise ValueError(f"the score reads {SIGNAL} columns: at least as "
                         "many features")
    rng = np.random.default_rng(np.random.SeedSequence(
        [LAYOUT_SEED, features]))
    # one column in each of SIGNAL equal slices of the width
    edges = np.linspace(0, features, SIGNAL + 1).astype(np.int64)
    picked = np.array([rng.integers(lo, hi) for lo, hi in
                       zip(edges[:-1], edges[1:])], np.int64)
    strong = int(picked[len(picked) * 5 // 8])
    cols = np.array([c for c in picked if c != strong], np.int64)
    weights = rng.uniform(0.3, 0.8, len(cols)).astype(np.float32)
    return {"features": features, "strong": strong, "cols": cols,
            "weights": weights}


def _score(lay: dict, Z: np.ndarray) -> np.ndarray:
    """The label's score over standardised values ``Z``: each term has
    mean 0, so the label is balanced at 0."""
    s = STRONG_WEIGHT * np.tanh(1.5 * Z[:, lay["strong"]])
    cols, w = lay["cols"], lay["weights"]
    for k, (c, wk) in enumerate(zip(cols, w)):
        z = Z[:, c]
        form = k % 4
        if form == 0:
            s += wk * z
        elif form == 1:
            s += wk * (np.abs(z) - np.float32(0.7978846))   # E|z| = sqrt(2/pi)
        elif form == 2:
            s += wk * np.sin(2.0 * z)
        else:
            s += wk * z * Z[:, cols[k - 1]]
    return s


def block(seed: int, stream: int, b: int, features: int, rows: int = BLOCK,
          out=None):
    """(X [rows, features] float32, y [rows] float32 in {0, 1}): the first
    ``rows`` rows of block ``b``, written into ``out = (X, y)`` where
    given. A whole block is always drawn, so the rows before a cut do not
    depend on it."""
    lay = layout(features)
    rng = np.random.default_rng(np.random.SeedSequence([seed, stream, b]))
    X, y = out if out is not None else (
        np.empty((rows, features), np.float32), np.empty(rows, np.float32))
    whole = rows == BLOCK
    Z = X if whole else np.empty((BLOCK, features), np.float32)
    rng.standard_normal((BLOCK, features), dtype=np.float32, out=Z)
    noise = np.clip(rng.random(BLOCK, dtype=np.float32), 1e-7, 1.0 - 1e-7)
    label = _score(lay, Z) + np.log(noise / (1.0 - noise)) > 0.0
    Z /= np.sqrt(np.einsum("ij,ij->i", Z, Z))[:, None]     # unit L2 rows
    if not whole:
        X[:] = Z[:rows]
    y[:] = label[:rows]
    return X, y


def n_blocks(n_rows: int) -> int:
    return -(-n_rows // BLOCK)


def epsilon_like(rows: int, features: int, seed: int, stream: int = 0):
    """(X [rows, features] float32, y [rows] float32 in {0, 1})."""
    global _pool
    if _pool is None:
        _pool = ThreadPoolExecutor(THREADS, thread_name_prefix="bench-wide")
    layout(features)                       # once, before the threads ask
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)

    def fill(b):
        lo = b * BLOCK
        hi = min(rows, lo + BLOCK)
        block(seed, stream, b, features, hi - lo, out=(X[lo:hi], y[lo:hi]))
    list(_pool.map(fill, range(n_blocks(rows))))
    return X, y


GENERATORS = {"epsilon_like": epsilon_like}
