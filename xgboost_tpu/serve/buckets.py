"""Bucketed batch shapes and the recompile counter.

XLA compiles one executable per input shape, so a predict service fed
raw request sizes recompiles on every new batch size — a compile stall
per shape (boosting/predict.py pads the TREE axes for the same reason;
this module is the ROW-axis twin for serving).
The :class:`BucketLadder` quantizes every device batch to a small fixed
set of row counts: after one warmup pass per bucket every request hits
a warm jitted executable, bounding the compiled-program set to
``len(ladder)`` per model chunk-step.

:class:`RecompileCounter` makes the "zero recompiles after warmup"
guarantee *testable*: it samples the trace-cache sizes of the jitted
walk programs, so a post-warmup cache miss shows up as a counted
recompile instead of an unexplained latency spike.
"""

from __future__ import annotations

from typing import Iterable, List, Sequence, Tuple

import numpy as np


class BucketLadder:
    """A sorted set of batch row counts every device dispatch pads to."""

    def __init__(self, sizes: Iterable[int]) -> None:
        uniq = sorted({int(s) for s in sizes})
        if not uniq:
            raise ValueError("bucket ladder needs at least one size")
        if uniq[0] < 1:
            raise ValueError(f"bucket sizes must be >= 1, got {uniq[0]}")
        self.sizes: Tuple[int, ...] = tuple(uniq)

    @classmethod
    def pow2(cls, max_batch: int, min_bucket: int = 1) -> "BucketLadder":
        """Powers of two from ``min_bucket`` up to ``max_batch`` (always
        included) — padded compute is bounded by 2x the real rows while
        the executable set stays O(log max_batch)."""
        sizes = []
        b = max(1, int(min_bucket))
        while b < max_batch:
            sizes.append(b)
            b *= 2
        sizes.append(int(max_batch))
        return cls(sizes)

    @property
    def max_batch(self) -> int:
        return self.sizes[-1]

    def bucket_for(self, n_rows: int) -> int:
        """Smallest bucket >= n_rows; the top bucket for anything larger
        (oversize requests are chunked by :meth:`chunks`)."""
        if n_rows < 1:
            raise ValueError(f"n_rows must be >= 1, got {n_rows}")
        for s in self.sizes:
            if s >= n_rows:
                return s
        return self.sizes[-1]

    def chunks(self, n_rows: int) -> List[int]:
        """Split an arbitrary request size into per-dispatch row counts:
        full top buckets plus one remainder chunk."""
        out, top = [], self.sizes[-1]
        while n_rows > top:
            out.append(top)
            n_rows -= top
        out.append(n_rows)
        return out

    def pad(self, X: np.ndarray, bucket: int,
            fill: float = 0.0) -> np.ndarray:
        """Pad rows of ``X`` up to ``bucket``. Fill value is irrelevant to
        results (pad rows are sliced off host-side before anyone reads
        them; the tree walk is row-independent) — 0.0 keeps the walk off
        the missing-value path, which is marginally cheaper than NaN."""
        n = X.shape[0]
        if n == bucket:
            return X
        if n > bucket:
            raise ValueError(f"batch of {n} rows exceeds bucket {bucket}")
        return np.concatenate(
            [X, np.full((bucket - n,) + X.shape[1:], fill, X.dtype)])


class RecompileCounter:
    """Counts XLA trace-cache misses of registered jitted callables.

    ``jax.jit`` wrappers expose ``_cache_size()`` — the number of
    distinct (shape, static-args) executables traced so far. The sum
    over the forest-walk programs is exactly the number of compiles the
    serving path has triggered; ``mark()`` snapshots it after warmup and
    ``since_mark()`` is the SLO number: recompiles after warmup.
    """

    def __init__(self, fns: Sequence = ()) -> None:
        self._fns: List = []
        self._mark = 0
        for f in fns:
            self.register(f)

    @classmethod
    def for_forest_predictor(cls) -> "RecompileCounter":
        """Counter over every serving walk program: the stock
        ForestPredictor twins, the packed-forest walk, and the device
        TreeSHAP kernel cache (all four feed the serve hot paths)."""
        import types

        from ..boosting import predict as _p
        from ..ops import shap as _shap
        from ..ops import walk as _walk

        shap_cache = types.SimpleNamespace(
            _cache_size=_shap._shap_cache_size)
        return cls([_p._predict_margin, _p._predict_margin_binned,
                    _walk.walk_packed, shap_cache])

    def register(self, fn) -> None:
        if not hasattr(fn, "_cache_size"):
            raise TypeError(f"{fn!r} is not a jitted callable "
                            "(no _cache_size)")
        self._fns.append(fn)

    def compiles(self) -> int:
        return sum(int(f._cache_size()) for f in self._fns)

    def mark(self) -> None:
        self._mark = self.compiles()

    def absorb(self, n: int) -> None:
        """Fold ``n`` EXPECTED compiles into the baseline (a hot-swapped
        model's warmup compiles are planned work, not an SLO violation)."""
        self._mark += int(n)

    def since_mark(self) -> int:
        # max(0): an external cache clear (tests drop jax caches between
        # modules) can shrink the count below the mark; that is not a
        # recompile
        return max(0, self.compiles() - self._mark)
