"""Seeded flights at the shape of the ASA Data Expo 2009 airline on-time file
(US domestic flights 1987-2008: 115,069,017 rows x 13 columns, the set NVIDIA
gbm-bench's ``airline`` job trains on). Synthetic: the file is not in the
repository (the configuration lists this under ``assumed``).

Block b of a stream depends only on (seed, stream, b), so the first k rows
do not depend on n, a few threads fill the matrix side by side, and the
comparison can regenerate any block.

    columns   Year, Month, DayofMonth, DayofWeek, CRSDepTime, CRSArrTime,
              UniqueCarrier, FlightNum, ActualElapsedTime, Origin, Dest,
              Distance, Diverted: gbm-bench's 13, in its order, float32.
              UniqueCarrier (30 categories), Origin and Dest (351 each) are
              category codes 0..n-1, drawn with a Zipf-like share a category
              (a few hubs and carriers carry most flights); the others are
              numbers. ActualElapsedTime is missing for about 2% of the
              flights (the cancelled and the diverted)
    label     ArrDelay > 0, about 45% of the flights: a logistic score in
              which the airports and the carrier weigh most (a tenth of the
              airports are delay-prone hubs), the departure hour, the month,
              the scheduled block time against the flown one and a diversion
              the rest

The layout (airport positions, each category's share and effect) depends
on nothing but ``LAYOUT_SEED``.
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 16
THREADS = max(1, min(12, (os.cpu_count() or 2) - 1))
LAYOUT_SEED = 20090101
COLUMNS = ("Year", "Month", "DayofMonth", "DayofWeek", "CRSDepTime",
           "CRSArrTime", "UniqueCarrier", "FlightNum", "ActualElapsedTime",
           "Origin", "Dest", "Distance", "Diverted")
CATEGORIES = {"UniqueCarrier": 30, "Origin": 351, "Dest": 351}
CAT_COLS = tuple(COLUMNS.index(c) for c in CATEGORIES)      # (6, 9, 10)
MISSING_SHARE = 0.02          # ActualElapsedTime of cancelled flights
HUB_SHARE = 0.1               # delay-prone airports

_pool = None


def feature_types() -> list:
    """``"c"`` for the three categorical columns, ``"q"`` for the rest."""
    return ["c" if c in CATEGORIES else "q" for c in COLUMNS]


def _zipf_cdf(n: int, s: float, rng) -> np.ndarray:
    """Cumulative shares of n categories under a Zipf-like law, the ranks
    given to the codes in a seeded order (a code's share is not its rank)."""
    share = 1.0 / np.arange(1, n + 1) ** s
    share = share[rng.permutation(n)]
    return np.cumsum(share / share.sum())


@functools.lru_cache(maxsize=1)
def layout() -> dict:
    """The fixed world the flights are drawn from."""
    rng = np.random.default_rng(LAYOUT_SEED)
    n_air, n_car = CATEGORIES["Origin"], CATEGORIES["UniqueCarrier"]
    hubs = rng.random(n_air) < HUB_SHARE
    return {
        "airport_cdf": _zipf_cdf(n_air, 1.0, rng),
        "carrier_cdf": _zipf_cdf(n_car, 1.1, rng),
        # positions on a 2,500 x 1,200 mile map
        "xy": rng.random((n_air, 2)) * np.array([2500.0, 1200.0]),
        "airport_eff": np.where(hubs, 1.6, 0.0)
        + rng.normal(0.0, 0.35, n_air),
        "dest_eff": rng.normal(0.0, 0.5, n_air),
        "carrier_eff": rng.normal(0.0, 0.6, n_car),
        "hubs": hubs,
    }


def block(seed: int, stream: int, b: int, rows: int = BLOCK, out=None):
    """(X [rows, 13] float32, y [rows] float32 in {0, 1}): the first
    ``rows`` rows of block ``b``, written into ``out = (X, y)`` where given.
    A whole block is always drawn, so the rows before a cut do not depend
    on it."""
    lay = layout()
    rng = np.random.default_rng(np.random.SeedSequence([seed, stream, b]))
    n = BLOCK
    u = rng.random((n, 12))
    year = np.minimum(1987 + np.floor(22 * np.sqrt(u[:, 0])), 2008)
    month = 1 + np.floor(12 * u[:, 1])
    dom = 1 + np.floor(31 * u[:, 2])
    dow = 1 + np.floor(7 * u[:, 3])
    # departures between 05:00 and 23:55, busiest in the morning and evening
    minute = 300 + np.floor(1140 * (0.5 - 0.5 * np.cos(np.pi * u[:, 4])))
    minute = 5 * np.floor(minute / 5)
    carrier = np.searchsorted(lay["carrier_cdf"], u[:, 5])
    origin = np.searchsorted(lay["airport_cdf"], u[:, 6])
    dest = np.searchsorted(lay["airport_cdf"], u[:, 7])
    dest = np.where(dest == origin, (dest + 1 + np.floor(
        (CATEGORIES["Dest"] - 1) * u[:, 8]).astype(np.int64))
        % CATEGORIES["Dest"], dest)
    d = lay["xy"][origin] - lay["xy"][dest]
    distance = np.maximum(np.round(np.sqrt((d * d).sum(1))), 31.0)
    block_min = np.round(25 + distance / 7.5)
    flight = 1 + np.floor(7500 * u[:, 9] ** 2)
    diverted = u[:, 10] < 0.0025
    flown = block_min + np.round(rng.normal(0.0, 0.08, n) * block_min
                                 + rng.gamma(1.2, 6.0, n) - 6.0)
    arr = (minute + block_min) % 1440
    hour = np.floor(minute / 60)
    score = (lay["airport_eff"][origin] + lay["dest_eff"][dest]
             + lay["carrier_eff"][carrier]
             + 0.09 * (hour - 13.0)
             + 0.35 * np.isin(month, (6, 7, 12)) - 0.25 * (month == 9)
             + 0.15 * ((dow == 5) | (dow == 4))
             + 0.04 * (flown - block_min) + 3.0 * diverted
             + 0.02 * (year - 1997) - 0.95)
    noise = np.clip(u[:, 11], 1e-7, 1.0 - 1e-7)
    late = score + np.log(noise / (1.0 - noise)) > 0.0
    elapsed = np.where(diverted | (rng.random(n) < MISSING_SHARE - 0.0025),
                       np.nan, flown)
    X, y = out if out is not None else (
        np.empty((rows, len(COLUMNS)), np.float32), np.empty(rows, np.float32))
    for j, col in enumerate((year, month, dom, dow,
                             100 * hour + minute % 60,
                             100 * np.floor(arr / 60) + arr % 60,
                             carrier, flight, elapsed, origin, dest,
                             distance, diverted)):
        X[:, j] = col[:rows]
    y[:] = late[:rows]
    return X, y


def n_blocks(n_rows: int) -> int:
    return -(-n_rows // BLOCK)


def airline_like(rows: int, features: int, seed: int, stream: int = 0):
    """(X [rows, 13] float32, y [rows] float32 in {0, 1}), made once, in
    blocks on a few threads."""
    global _pool
    if features != len(COLUMNS):
        raise ValueError(f"the airline file has {len(COLUMNS)} columns")
    if _pool is None:
        _pool = ThreadPoolExecutor(THREADS, thread_name_prefix="bench-air")
    layout()                               # once, before the threads ask
    X = np.empty((rows, len(COLUMNS)), np.float32)
    y = np.empty(rows, np.float32)

    def fill(b):
        lo = b * BLOCK
        hi = min(rows, lo + BLOCK)
        block(seed, stream, b, hi - lo, out=(X[lo:hi], y[lo:hi]))
    list(_pool.map(fill, range(n_blocks(rows))))
    return X, y


GENERATORS = {"airline_like": airline_like}
