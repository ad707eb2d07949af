"""Weighted quantile sketch -> histogram cuts.

TPU-native replacement for the reference's GK-style weighted quantile machinery
(``src/common/quantile.h:34-1000``, ``src/common/hist_util.cc:32-69``): per-feature
merge-able weighted summaries (value, total weight) built on host with numpy,
pruned to ``max_bin`` cut points at evenly spaced weighted ranks. Summaries from
different row shards merge by concatenate+sort+re-accumulate, which is how the
distributed sketch sync (``src/common/quantile.cc:147-390`` allgatherv + merge) is
realised here (see parallel/collective.py).

Cut storage is ragged on host (``values``/``ptrs`` over REAL bins only, exactly
like ``common::HistogramCuts``); the device-side training layout pads every
feature to a uniform ``max_nbins`` slot count with a trailing missing-value slot
(see data/binned.py) so histograms are dense ``[nodes, features, bins]`` tensors.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np


def _sorted_unique_sums(v: np.ndarray, w: Optional[np.ndarray]):
    """Sorted values -> (unique values, per-unique weight sums); counts when
    ``w`` is None. One pass, no second sort (unlike ``np.unique``)."""
    new = np.empty(len(v), bool)
    new[0] = True
    np.not_equal(v[1:], v[:-1], out=new[1:])
    start = np.flatnonzero(new)
    if w is None:
        wsum = np.diff(np.append(start, len(v))).astype(np.float64)
    else:
        wsum = np.add.reduceat(w, start)
    return v[start], wsum


@dataclass
class FeatureSummary:
    """Merge-able weighted summary of one feature: sorted unique values and the
    total weight on each (exact when built from in-memory data; a pruned version
    bounds memory like ``WQSummary::Prune``)."""

    values: np.ndarray   # [k] f64 sorted unique
    weights: np.ndarray  # [k] f64 total weight per value

    @staticmethod
    def from_data(col: np.ndarray, weights: Optional[np.ndarray] = None) -> "FeatureSummary":
        mask = ~np.isnan(col)
        v = col[mask].astype(np.float64)
        if v.size == 0:
            return FeatureSummary(np.empty(0), np.empty(0))
        # one sort, and unique boundaries straight off the sorted array
        # (np.unique would sort a second time — at 11M rows the sketch cost
        # is entirely sorting; tie order is irrelevant because every equal
        # value's weight is summed)
        if weights is None:
            uniq, wsum = _sorted_unique_sums(np.sort(v), None)
        else:
            order = np.argsort(v)
            uniq, wsum = _sorted_unique_sums(
                v[order], weights[mask].astype(np.float64)[order])
        return FeatureSummary(uniq, wsum)

    def merge(self, other: "FeatureSummary") -> "FeatureSummary":
        if self.values.size == 0:
            return other
        if other.values.size == 0:
            return self
        v = np.concatenate([self.values, other.values])
        w = np.concatenate([self.weights, other.weights])
        order = np.argsort(v)
        return FeatureSummary(*_sorted_unique_sums(v[order], w[order]))

    def prune(self, max_size: int) -> "FeatureSummary":
        """Keep ~max_size entries at evenly spaced weighted ranks (plus extremes);
        dropped weight is re-aggregated onto the kept representative at/after it."""
        k = self.values.size
        if k <= max_size:
            return self
        cum = np.cumsum(self.weights)
        total = cum[-1]
        ranks = np.linspace(0.0, total, max_size)
        idx = np.searchsorted(cum, ranks, side="left")
        idx = np.unique(np.clip(idx, 0, k - 1))
        if idx[0] != 0:
            idx = np.concatenate([[0], idx])
        if idx[-1] != k - 1:
            idx = np.concatenate([idx, [k - 1]])
        seg = np.searchsorted(idx, np.arange(k), side="left")
        seg = np.clip(seg, 0, idx.size - 1)
        w = np.bincount(seg, weights=self.weights, minlength=idx.size)
        return FeatureSummary(self.values[idx], w)

    def to_arrays(self):
        return self.values, self.weights


@dataclass
class HistogramCuts:
    """Quantile cut points, the analogue of ``common::HistogramCuts``
    (reference ``src/common/hist_util.h:37-127``).

    ``values[ptrs[f] + i]`` is the inclusive upper bound of REAL bin ``i`` of
    feature ``f`` (value v falls in bin i iff values[i-1] < v <= values[i]);
    ``min_vals[f]`` is below the smallest observed value. Missing values are not
    represented here — the device layout (binned.py) appends one uniform
    missing slot per feature.
    """

    values: np.ndarray    # [total_real_bins] f32
    ptrs: np.ndarray      # [n_features + 1] int32
    min_vals: np.ndarray  # [n_features] f32
    max_bin: int = 256
    feature_types: Optional[list] = None  # 'c' marks categorical features

    @property
    def n_features(self) -> int:
        return len(self.ptrs) - 1

    @property
    def total_bins(self) -> int:
        return int(self.ptrs[-1])

    def n_bins(self, f: int) -> int:
        """REAL bins of feature f (no missing slot)."""
        return int(self.ptrs[f + 1] - self.ptrs[f])

    def n_real_bins(self) -> np.ndarray:
        return np.diff(self.ptrs).astype(np.int32)

    def search_bin(self, values: np.ndarray) -> np.ndarray:
        """Vectorized SearchBin over a dense [n, n_features] float matrix ->
        LOCAL real-bin indices; missing (NaN) -> -1."""
        n, nf = values.shape
        out = np.empty((n, nf), dtype=np.int32)
        for f in range(nf):
            lo, hi = int(self.ptrs[f]), int(self.ptrs[f + 1])
            cuts = self.values[lo:hi]
            col = values[:, f]
            miss = np.isnan(col)
            b = np.searchsorted(cuts, col, side="left")
            b = np.minimum(b, hi - lo - 1)  # clamp overflow into last real bin
            b[miss] = -1
            out[:, f] = b
        return out

    def split_value(self, f: int, local_bin: int) -> float:
        """Raw-feature threshold of a split at (f, local_bin): x goes left iff
        x <= split_value."""
        return float(self.values[int(self.ptrs[f]) + int(local_bin)])

    def split_values(self, split_feature: np.ndarray,
                     split_bin: np.ndarray) -> np.ndarray:
        """Vectorised raw thresholds for per-node (feature, local bin) pairs;
        entries with split_feature < 0 (leaves) map to 0."""
        sf = np.asarray(split_feature)
        sb = np.asarray(split_bin)
        out = np.zeros(sf.shape, np.float32)
        mask = sf >= 0
        gb = self.ptrs[np.maximum(sf, 0)] + sb
        out[mask] = self.values[np.clip(gb[mask], 0, len(self.values) - 1)]
        return out

    def is_cat(self) -> np.ndarray:
        if not self.feature_types:
            return np.zeros(self.n_features, dtype=bool)
        return np.asarray([t == "c" for t in self.feature_types])

    def to_json(self) -> dict:
        return {
            "values": np.asarray(self.values, dtype=np.float64).tolist(),
            "ptrs": self.ptrs.tolist(),
            "min_vals": np.asarray(self.min_vals, dtype=np.float64).tolist(),
            "max_bin": self.max_bin,
            "feature_types": self.feature_types,
        }

    @staticmethod
    def from_json(obj: dict) -> "HistogramCuts":
        return HistogramCuts(
            values=np.asarray(obj["values"], dtype=np.float32),
            ptrs=np.asarray(obj["ptrs"], dtype=np.int32),
            min_vals=np.asarray(obj["min_vals"], dtype=np.float32),
            max_bin=int(obj.get("max_bin", 256)),
            feature_types=obj.get("feature_types"),
        )


def cuts_from_summaries(summaries: Sequence[FeatureSummary], max_bin: int,
                        feature_types: Optional[List[str]] = None
                        ) -> HistogramCuts:
    """Build cuts at evenly spaced weighted ranks, mirroring
    ``HistogramCuts::Build`` semantics (last cut strictly above the max value so
    every observed value lands in a real bin). Categorical features ('c' in
    feature_types) get one bin per category code: bin i == category i."""
    values: List[np.ndarray] = []
    ptrs = [0]
    min_vals = []
    for f, s in enumerate(summaries):
        if feature_types is not None and f < len(feature_types) \
                and feature_types[f] == "c":
            n_cat = int(s.values.max()) + 1 if s.values.size else 1
            cuts = np.arange(n_cat, dtype=np.float32)
            min_vals.append(-0.5)
            values.append(cuts)
            ptrs.append(ptrs[-1] + len(cuts))
            continue
        if s.values.size == 0:
            cuts = np.asarray([np.inf], dtype=np.float32)
            min_vals.append(0.0)
        else:
            vmin, vmax = float(s.values[0]), float(s.values[-1])
            if s.values.size <= max_bin:
                pts = s.values.astype(np.float64)
            else:
                cum = np.cumsum(s.weights)
                total = cum[-1]
                ranks = (np.arange(1, max_bin + 1) / max_bin) * total
                idx = np.searchsorted(cum, ranks, side="left")
                idx = np.unique(np.clip(idx, 0, s.values.size - 1))
                pts = s.values[idx].astype(np.float64)
            last = vmax + (abs(vmax) * 1e-5 + 1e-5)
            cuts = np.unique(np.concatenate([pts[:-1], [last]])).astype(np.float32)
            min_vals.append(vmin - (abs(vmin) * 1e-5 + 1e-5))
        values.append(cuts)
        ptrs.append(ptrs[-1] + len(cuts))
    out = (np.concatenate(values) if values
           else np.empty(0, dtype=np.float32)).astype(np.float32)
    return HistogramCuts(values=out, ptrs=np.asarray(ptrs, dtype=np.int32),
                         min_vals=np.asarray(min_vals, dtype=np.float32),
                         max_bin=max_bin, feature_types=feature_types)


def cover_maxima(cuts: HistogramCuts, col_max: np.ndarray) -> HistogramCuts:
    """Raise each numeric feature's LAST cut above the column's true
    maximum, where a sketch over a row sample left it below. A cut is its
    bin's inclusive upper bound, and ``search_bin`` clamps a value past the
    last cut into the last real bin: such a row then trains on one side of
    a split at that bin and is predicted, by its raw value against the
    threshold, on the other. With the last cut over the true maximum no row
    is clamped, so the model the cuts state is the model that was trained.
    ``col_max``: NaN-ignoring maxima of ALL rows (``-inf`` for an empty
    column). The one helper of ``sketch_matrix`` and the iterator's
    sketch."""
    ptrs = np.asarray(cuts.ptrs, np.int64)
    last = ptrs[1:] - 1
    m = np.asarray(col_max, np.float64)[:len(last)]
    fix = (ptrs[1:] > ptrs[:-1]) & ~cuts.is_cat() & np.isfinite(m)
    fix[fix] = m[fix] > cuts.values[last[fix]]
    if not fix.any():
        return cuts
    values = np.array(cuts.values, np.float32)
    values[last[fix]] = (m[fix] + (np.abs(m[fix]) * 1e-5 + 1e-5)
                         ).astype(np.float32)
    return HistogramCuts(values=values, ptrs=cuts.ptrs,
                         min_vals=cuts.min_vals, max_bin=cuts.max_bin,
                         feature_types=cuts.feature_types)


def _sketch_matrix_native(X: np.ndarray, max_bin: int,
                          weights: Optional[np.ndarray],
                          feature_types: Optional[List[str]]
                          ) -> Optional[HistogramCuts]:
    """Threaded C++ sketch (native/sketch.cc) — same cuts as the Python path.
    Categorical features are overridden host-side (their cuts are just
    ``arange(n_cat)``)."""
    import ctypes

    from .. import native

    lib = native.load()
    n, nf = X.shape
    # f64 input keeps full precision only on the Python path — don't narrow
    if lib is None or n == 0 or nf == 0 or max_bin < 1 \
            or X.dtype != np.float32:
        return None
    X = np.ascontiguousarray(X)
    w = None
    if weights is not None:
        weights = np.asarray(weights)
        if weights.shape[0] != n:
            raise ValueError(
                f"weights has {weights.shape[0]} entries, expected {n}")
        if weights.dtype.itemsize > 8:
            return None
        w = np.ascontiguousarray(weights, np.float64)
    skip = None
    if feature_types is not None:
        skip = np.asarray([f < len(feature_types) and feature_types[f] == "c"
                           for f in range(nf)], dtype=np.uint8)
        if not skip.any():
            skip = None
    vals = np.empty((nf, max_bin), np.float32)
    counts = np.empty(nf, np.int32)
    mins = np.empty(nf, np.float32)
    fn = lib.xtpu_sketch_cuts
    fn.restype = None
    fn(X.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
       ctypes.c_int64(n), ctypes.c_int64(nf),
       (w.ctypes.data_as(ctypes.POINTER(ctypes.c_double)) if w is not None
        else None),
       (skip.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
        if skip is not None else None),
       ctypes.c_int(max_bin),
       vals.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
       counts.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
       mins.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    values: List[np.ndarray] = []
    ptrs = [0]
    min_vals: List[float] = []
    for f in range(nf):
        if feature_types is not None and f < len(feature_types) \
                and feature_types[f] == "c":
            col = X[:, f]
            finite = col[~np.isnan(col)]
            n_cat = int(finite.max()) + 1 if finite.size else 1
            values.append(np.arange(n_cat, dtype=np.float32))
            min_vals.append(-0.5)
        else:
            values.append(vals[f, :counts[f]].copy())
            min_vals.append(float(mins[f]))
        ptrs.append(ptrs[-1] + len(values[-1]))
    return HistogramCuts(values=np.concatenate(values).astype(np.float32),
                         ptrs=np.asarray(ptrs, dtype=np.int32),
                         min_vals=np.asarray(min_vals, dtype=np.float32),
                         max_bin=max_bin, feature_types=feature_types)


# Rows used for quantile sketching on large unweighted matrices: above this
# the sketch runs on a deterministic strided row sample. The reference's
# sketch is itself approximate (GK summaries with eps ~ 1/max_bin); at 2M
# sampled rows the order-statistic error is ~0.07% of rank = ~0.2 of one
# 256-bin width, far inside that budget, while an 11M x 28 exact sketch
# costs 21 s of single-core sort time. The last cut is raised over the true
# maximum of all rows (``cover_maxima``), so no value is clamped. 0 disables.
SKETCH_SAMPLE_ROWS = int(__import__("os").environ.get(
    "XTPU_SKETCH_SAMPLE_ROWS", 2_000_000))


def sketch_matrix(X: np.ndarray, max_bin: int,
                  weights: Optional[np.ndarray] = None,
                  feature_types: Optional[List[str]] = None,
                  sample_rows: Optional[int] = None) -> HistogramCuts:
    """``SketchOnDMatrix`` analogue (reference ``src/common/hist_util.cc:32-69``)
    for an in-memory dense matrix with NaN as missing."""
    limit = SKETCH_SAMPLE_ROWS if sample_rows is None else sample_rows
    col_max = None
    if weights is None and limit and X.shape[0] > limit:
        stride = -(-X.shape[0] // limit)
        col_max = np.fmax.reduce(X, axis=0, initial=-np.inf)
        X = np.ascontiguousarray(X[::stride])
    out = _sketch_matrix_native(X, max_bin, weights, feature_types)
    if out is None:
        summaries = [FeatureSummary.from_data(X[:, f], weights)
                     for f in range(X.shape[1])]
        out = cuts_from_summaries(summaries, max_bin, feature_types)
    return out if col_max is None else cover_maxima(out, col_max)
