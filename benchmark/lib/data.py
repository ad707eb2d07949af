"""Seeded data at a configuration's shape. The HIGGS-shaped generator is the
one ``chip_smoke.py`` has (copied: the yardstick may not import a file later
PRs may edit), made blockwise so that a few threads fill it: 28 standard-normal
float32 features, a nonlinear score over the first eight, label noise. Block b
of a stream depends only on (seed, stream, b), so the first k rows do not
depend on n."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 20
THREADS = 6


def _higgs_block(seed: int, stream: int, block: int, rows: int, features: int):
    rng = np.random.default_rng(np.random.SeedSequence([seed, stream, block]))
    X = rng.standard_normal((rows, features), dtype=np.float32)
    z = (X[:, 0] * X[:, 1] + 0.8 * np.abs(X[:, 2]) - 0.6 * X[:, 3] ** 2
         + 0.7 * X[:, 4] + 0.5 * np.sin(2.0 * X[:, 5]) + 0.4 * X[:, 6] * X[:, 7])
    noise = rng.standard_normal(rows, dtype=np.float32)
    return X, (z + 0.7 * noise > 0.05).astype(np.float32)


def higgs_like(rows: int, features: int, seed: int, stream: int = 0):
    """(X [rows, features] float32, y [rows] float32 in {0, 1})."""
    if features < 8:
        raise ValueError("the HIGGS-shaped score reads eight features")
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)

    def fill(b):
        lo = b * BLOCK
        hi = min(rows, lo + BLOCK)
        X[lo:hi], y[lo:hi] = _higgs_block(seed, stream, b, hi - lo, features)
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(-(-rows // BLOCK))))
    return X, y


GENERATORS = {"higgs_like": higgs_like}
