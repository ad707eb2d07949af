"""Seeded production-line data at the shape of Kaggle's *Bosch Production
Line Performance* ``train_numeric.csv``: a wide float32 matrix of anonymised
measurements, about 81% of it NaN because a part is measured only at the
stations on its route. Synthetic: the file is not in the repository (the
configuration lists this under ``assumed``).

Block b of a stream depends only on (seed, stream, b), so the first k rows
do not depend on n and a few threads fill the matrix side by side.

**The layout** is the deployment's, not the run's: it depends on the
feature count alone (``layout(features)``, from a fixed seed), so every
``--seed`` draws parts for the same line.

    columns   52 station blocks of uneven width that add up to ``features``
              (lognormal widths, every station at least one column), the
              stations in 4 lines: 0-23, 24-25, 26-28, 29-51 as the file's
              ``L<line>_S<station>_F<n>`` names have them
    routes    a part draws one of 40 route families (Zipf frequencies: the
              most common about 31% of the parts, the rarest under 0.4%);
              a family is a set of stations, and every column of a station
              is present or absent together. The families are adjusted
              until 19% +- 0.3% of the entries are present; every station
              lies on some route, and line 2's stations lie only on the
              three rarest, so their columns are present in under 1% of
              the rows
    values    float32 in [-1, 1], rounded to 3 decimals as the file's are:
              a clipped normal with the column's own mean and spread; one
              column in nine takes three values only
    label     a failure with probability about 0.6%: a nonlinear score over
              four present values AND over which stations were visited (a
              part that skipped station A or B is as risky as one measured
              LOW there, so the splits on their columns learn to send the
              missing left; a part that went through station C is riskier),
              plus logistic noise, cut at a threshold fixed by the layout.
              Station A's first column carries most of the signal
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK = 1 << 14
THREADS = max(1, min(8, (os.cpu_count() or 2) - 1))
STATIONS = 52
LINES = ((0, 24), (24, 26), (26, 29), (29, 52))
FAMILIES = 40
LAYOUT_SEED = 20160816
PRESENT_SHARE = 0.19          # 81% missing
POSITIVE_RATE = 0.006
NOISE = 0.1

_pool = None


def _widths(rng, features: int) -> np.ndarray:
    if features < STATIONS:
        raise ValueError(f"the line has {STATIONS} stations: at least as "
                         "many columns")
    raw = rng.lognormal(0.0, 0.8, STATIONS)
    share = (features - STATIONS) * raw / raw.sum()
    w = 1 + np.floor(share).astype(np.int64)
    short = features - int(w.sum())
    w[np.argsort(-(share - np.floor(share)))[:short]] += 1
    return w


def _families(rng, widths: np.ndarray):
    """(member [FAMILIES, STATIONS] bool, freq [FAMILIES])."""
    freq = 1.0 / np.arange(1, FAMILIES + 1) ** 1.2
    freq /= freq.sum()
    on_line = (0.55, 0.35, 0.0, 0.95)      # share of families on each line
    at_station = (0.30, 0.50, 0.0, 0.30)   # ... and at a station of it
    member = np.zeros((FAMILIES, STATIONS), bool)
    for k in range(FAMILIES):
        for (lo, hi), q, r in zip(LINES, on_line, at_station):
            if rng.random() < q:
                member[k, lo:hi] = rng.random(hi - lo) < r
    # line 2 lies on the three rarest routes alone
    lo, hi = LINES[2]
    for i, s in enumerate(range(lo, hi)):
        member[FAMILIES - 1 - i, s] = True
    # no station off every route: the rare families take the leftovers
    for i, s in enumerate(np.flatnonzero(~member.any(axis=0))):
        member[FAMILIES - 4 - i % 8, s] = True
    # toggle (family, station) pairs until the present share fits
    total = float(widths.sum())
    line2 = np.zeros(STATIONS, bool)
    line2[lo:hi] = True
    for _ in range(400):
        share = float(freq @ (member @ widths)) / total
        if abs(share - PRESENT_SHARE) <= 0.003:
            break
        delta = np.where(member, -1.0, 1.0) * freq[:, None] \
            * widths[None, :] / total
        allowed = ~line2[None, :] & np.where(
            member, (member.sum(axis=1) > 2)[:, None]
            & (member.sum(axis=0) > 1)[None, :], True)
        miss = np.where(allowed, np.abs(share + delta - PRESENT_SHARE),
                        np.inf)
        k, s = np.unravel_index(np.argmin(miss), miss.shape)
        member[k, s] = ~member[k, s]
    else:
        raise RuntimeError("the route families do not reach the present "
                           f"share {PRESENT_SHARE} at this width")
    return member, freq


@functools.lru_cache(maxsize=4)
def layout(features: int) -> dict:
    """The line at ``features`` columns: station widths and starts, route
    families and their frequencies, every column's mean, spread and kind,
    the stations and columns the label reads, and the score's threshold."""
    rng = np.random.default_rng(np.random.SeedSequence(
        [LAYOUT_SEED, features]))
    widths = _widths(rng, features)
    starts = np.concatenate([[0], np.cumsum(widths)])
    member, freq = _families(rng, widths)
    visit = freq @ member                   # share of parts at each station
    mu = rng.uniform(-0.3, 0.3, features).astype(np.float32)
    sigma = rng.uniform(0.05, 0.4, features).astype(np.float32)
    ternary = rng.random(features) < 1.0 / 9.0

    def nearest(lo, hi, want, taken=()):
        order = [s for s in lo + np.argsort(np.abs(visit[lo:hi] - want))
                 if s not in taken and widths[s] >= 2]
        return int(order[0]) if order else int(
            lo + np.argmin(np.abs(visit[lo:hi] - want)))
    A = nearest(*LINES[3], 0.6)
    B = nearest(*LINES[0], 0.35)
    C = nearest(0, STATIONS, 0.08, taken=(A, B) + tuple(range(*LINES[2])))
    cols = np.array([starts[A], starts[A] + min(1, widths[A] - 1),
                     starts[B], starts[B] + min(1, widths[B] - 1)])
    mu[cols], sigma[cols], ternary[cols] = 0.0, 0.35, False
    out = {"features": features, "widths": widths, "starts": starts,
           "member": member, "freq": freq, "visit": visit, "mu": mu,
           "sigma": sigma, "ternary": ternary, "A": A, "B": B, "C": C,
           "signal_cols": cols,
           "station_of": np.repeat(np.arange(STATIONS), widths)}
    # the threshold that leaves POSITIVE_RATE of the parts above it, from a
    # fixed sample of routes and signal values (no bulk values are drawn)
    z = np.concatenate([_score(out, *_head(out, np.random.default_rng(
        np.random.SeedSequence([LAYOUT_SEED, features, b]))))
        for b in range(32)])
    out["threshold"] = float(np.quantile(z, 1.0 - POSITIVE_RATE))
    return out


def _values(lay: dict, cols, normal: np.ndarray) -> np.ndarray:
    """Standard normals -> the columns' values: clipped to [-1, 1], three
    decimals; a ternary column takes -0.5, 0 or 0.5 times its spread."""
    x = lay["mu"][cols] + lay["sigma"][cols] * normal
    tern = lay["ternary"][cols]
    if tern.any():
        three = (np.sign(normal) * (np.abs(normal) > 0.6)
                 * (0.5 * lay["sigma"][cols]))
        x = np.where(tern, three, x)
    return np.round(np.clip(x, -1.0, 1.0), 3).astype(np.float32)


def _head(lay: dict, rng):
    """What a block draws first: each part's route family, the four values
    the label reads, and the label's noise."""
    fam = rng.choice(FAMILIES, size=BLOCK, p=lay["freq"])
    sig = _values(lay, lay["signal_cols"],
                  rng.standard_normal((BLOCK, 4), dtype=np.float32))
    noise = np.clip(rng.random(BLOCK, dtype=np.float32), 1e-7, 1.0 - 1e-7)
    return fam, sig, noise


def _score(lay: dict, fam, sig, noise) -> np.ndarray:
    at = lay["member"][fam]                                # [BLOCK, 52]
    a, b, c = at[:, lay["A"]], at[:, lay["B"]], at[:, lay["C"]]
    a1, a2, b1, b2 = sig.T
    z = (np.where(a, -4.0 * a1, 1.2)
         + np.where(b, 2.0 * (b1 < -0.3), 1.6)
         + 1.0 * c
         + np.where(a & b, 1.5 * a2 * b2, 0.0))
    return z + NOISE * np.log(noise / (1.0 - noise))


def block(seed: int, stream: int, b: int, features: int, rows: int = BLOCK,
          out=None):
    """(X [rows, features] float32 with NaN, y [rows] float32 in {0, 1}):
    the first ``rows`` rows of block ``b``, written into ``out = (X, y)``
    where given. A whole block is always drawn, so the rows before a cut
    do not depend on it."""
    lay = layout(features)
    rng = np.random.default_rng(np.random.SeedSequence([seed, stream, b]))
    fam, sig, noise = _head(lay, rng)
    X, y = out if out is not None else (
        np.empty((rows, features), np.float32), np.empty(rows, np.float32))
    whole = rows == BLOCK
    Xb = X if whole else np.empty((BLOCK, features), np.float32)
    Xb.fill(np.nan)
    at = lay["member"][fam]
    for s in range(STATIONS):
        idx = np.flatnonzero(at[:, s])
        c0, c1 = lay["starts"][s], lay["starts"][s + 1]
        Xb[idx, c0:c1] = _values(
            lay, np.arange(c0, c1),
            rng.standard_normal((len(idx), c1 - c0), dtype=np.float32))
    for j, c in enumerate(lay["signal_cols"]):
        here = at[:, lay["station_of"][c]]
        Xb[here, c] = sig[here, j]
    if not whole:
        X[:] = Xb[:rows]
    y[:] = (_score(lay, fam, sig, noise) > lay["threshold"])[:rows]
    return X, y


def n_blocks(n_rows: int) -> int:
    return -(-n_rows // BLOCK)


def bosch_like(rows: int, features: int, seed: int, stream: int = 0):
    """(X [rows, features] float32 with NaN, y [rows] float32 in {0, 1})."""
    global _pool
    if _pool is None:
        _pool = ThreadPoolExecutor(THREADS, thread_name_prefix="bench-line")
    layout(features)                       # once, before the threads ask
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)

    def fill(b):
        lo = b * BLOCK
        hi = min(rows, lo + BLOCK)
        block(seed, stream, b, features, hi - lo, out=(X[lo:hi], y[lo:hi]))
    list(_pool.map(fill, range(n_blocks(rows))))
    return X, y


GENERATORS = {"bosch_like": bosch_like}
