"""xtpuinsight — in-trace training telemetry, in-carry eval, model forensics.

PRs 8 and 13 instrument the *systems* (spans, counters, the flight
recorder); this module instruments the *learning*. Three instruments,
one arming discipline:

- **In-trace training telemetry** — per-round scalars (best-gain
  distribution per level, leaf count, realized depth, leaf-value stats,
  gradient/hessian norms, NaN-guard hit count) computed as EXTRA OUTPUTS
  of the round programs the drivers already dispatch. Armed resident
  tiers use ``core._fused_round_insight_fn`` (same ≤2-dispatch budget as
  the unarmed round — ``tools/xtpuverify`` pins the
  ``resident.*.insight`` contracts); the non-fused tiers (lossguide /
  paged / mesh / general) derive the same scalars host-side from the
  round's committed node arrays (:func:`round_telemetry_host` — zero
  extra dispatches by construction).
- **In-carry eval** — ``XTPU_INSIGHT_EVAL=1`` folds the eval-set margin
  update (a binned heap walk of the freshly grown tree,
  :func:`walk_leaf_delta`) plus the metric reductions
  (:func:`metric_partial`) into the SAME fused round program, so
  ``eval_set`` costs one scalar fetch per round instead of a
  host-predict pass per DMatrix.
- **Model inspector & diff** — :func:`model_inspect` (all five
  importance types, tree-shape histograms) and :func:`model_diff`
  (prediction-drift attribution to features/trees), consumed by
  ``Booster.inspect()``, ``tools/model_report.py``, the pipeline's
  gate-rejection reports and serve's ``GET /v1/model/<name>/report``.

Everything lands in a :class:`TrainingLog` — the ``evals_result``
mapping the callbacks already consume, extended with a ``.records``
list of per-round telemetry — and streams into the PR-8
``MetricsRegistry`` as ``xtpu_insight_*`` / ``xtpu_eval_*`` gauges plus
flight-recorder instants, with the zero-alloc-when-off discipline of
``obs/trace.py``: disarmed, every producer call site pays one module
predicate and nothing else.

Knobs (read at import; flip with :func:`enable` / :func:`disable`):

- ``XTPU_INSIGHT``       — ``1`` arms per-round training telemetry.
- ``XTPU_INSIGHT_EVAL``  — ``1`` additionally arms the in-carry eval
  (implies ``XTPU_INSIGHT``).
"""

from __future__ import annotations

import collections
import math
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["enable", "disable", "enabled", "eval_enabled", "TrainingLog",
           "SUPPORTED_EVAL_METRICS", "metric_specs", "metric_partial",
           "finalize_partial", "grown_telemetry", "walk_leaf_delta",
           "round_telemetry_host", "model_inspect", "model_diff"]


# ------------------------------------------------------------- arming state

_ON = False
_EVAL = False


def enable(eval: Optional[bool] = None) -> None:
    """Arm insight telemetry; ``eval=True`` also arms the in-carry eval."""
    global _ON, _EVAL
    _ON = True
    if eval is not None:
        _EVAL = bool(eval)


def disable() -> None:
    global _ON, _EVAL
    _ON = False
    _EVAL = False


def enabled() -> bool:
    return _ON


def eval_enabled() -> bool:
    return _ON and _EVAL


# -------------------------------------------------------------- TrainingLog

class TrainingLog(collections.OrderedDict):
    """``evals_result``-shaped mapping {data: {metric: [scores]}} plus a
    ``.records`` list of per-round telemetry dicts. The callback
    container's ``history`` IS a TrainingLog, so ``EarlyStopping`` /
    ``evals_result`` consume it through the plain dict API while insight
    producers append structured rounds — one log, two views. Snapshots
    persist it via :meth:`to_obj` so patience windows and telemetry
    survive checkpoint resume."""

    def __init__(self, records: Optional[List[Dict[str, Any]]] = None
                 ) -> None:
        super().__init__()
        self.records: List[Dict[str, Any]] = list(records or [])

    # -- producers ---------------------------------------------------------
    def log_round(self, round_: int, scalars: Dict[str, Any]) -> None:
        """Append one round's telemetry; streams gauges + a trace instant
        only while insight is armed."""
        rec: Dict[str, Any] = {"round": int(round_)}
        for k, v in scalars.items():
            if np.ndim(v) == 0:
                rec[k] = float(v)
            else:
                rec[k] = [float(x) for x in np.asarray(v).reshape(-1)]
        self.records.append(rec)
        if _ON:
            from .metrics import get_registry
            from . import trace

            reg = get_registry()
            for k, v in rec.items():
                if k != "round" and np.ndim(v) == 0:
                    reg.set_gauge(f"xtpu_insight_{k}", float(v),
                                  help="per-round training telemetry "
                                       "(xtpuinsight)")
            reg.set_gauge("xtpu_insight_round", float(rec["round"]),
                          help="last telemetered boosting round")
            trace.instant("insight/round", cat="insight", args=rec)

    def log_eval(self, data_name: str, metric_name: str,
                 value: float) -> None:
        """Append one eval score (the ``evals_result`` write path)."""
        self.setdefault(data_name, collections.OrderedDict()).setdefault(
            metric_name, []).append(float(value))
        if _ON:
            from .metrics import get_registry

            get_registry().set_gauge(
                "xtpu_eval_score", float(value),
                labels=(("data", data_name), ("metric", metric_name)),
                help="latest eval-set metric score (xtpuinsight)")

    # -- persistence -------------------------------------------------------
    def to_obj(self) -> Dict[str, Any]:
        return {"history": {d: {m: list(v) for m, v in metrics.items()}
                            for d, metrics in self.items()},
                "records": [dict(r) for r in self.records]}

    @classmethod
    def from_obj(cls, obj: Optional[Dict[str, Any]]) -> "TrainingLog":
        log = cls(records=(obj or {}).get("records"))
        for d, metrics in ((obj or {}).get("history") or {}).items():
            for m, vals in metrics.items():
                log.setdefault(d, collections.OrderedDict())[m] = \
                    [float(v) for v in vals]
        return log


# ----------------------------------------------- in-trace round telemetry
#
# These run INSIDE the fused round jit (core._fused_round_insight_fn):
# pure jnp reductions over arrays the program already computes, so the
# scalars ride the existing dispatch as extra outputs.

def _heap_depths(max_nodes: int):
    """Static heap-depth table: node i lives at depth floor(log2(i+1))."""
    import jax.numpy as jnp

    return jnp.asarray(np.floor(np.log2(np.arange(max_nodes) + 1))
                       .astype(np.int32))


def grown_telemetry(grown, gpair, levels: int) -> Dict[str, Any]:
    """Per-round learning-health scalars from a freshly grown tree (the
    GrownTree heap, or the stacked multiclass dict) and its gradient
    pairs. Returns a dict of device scalars plus the per-level best-gain
    vector — all outputs of the enclosing jit."""
    import jax.numpy as jnp

    if isinstance(grown, dict):
        arrs = grown
    else:
        arrs = {"is_leaf": grown.is_leaf, "active": grown.active,
                "gain": grown.gain, "leaf_value": grown.leaf_value}
    active = arrs["active"]
    leaf = arrs["is_leaf"] & active
    split = active & ~arrs["is_leaf"]
    gain = arrs["gain"]
    lv = arrs["leaf_value"]
    depths = _heap_depths(active.shape[-1])

    leaf_count = jnp.sum(leaf)
    split_count = jnp.sum(split)
    depth = jnp.max(jnp.where(leaf, depths, 0))
    gain_total = jnp.sum(jnp.where(split, gain, 0.0))
    gain_max = jnp.max(jnp.where(split, gain, 0.0))
    gain_mean = gain_total / jnp.maximum(split_count, 1)
    gain_per_level = jnp.stack(
        [jnp.max(jnp.where(split & (depths == d), gain, 0.0))
         for d in range(max(int(levels), 1))])
    leaf_sum = jnp.sum(jnp.where(leaf, lv, 0.0))
    return {
        "leaf_count": leaf_count,
        "split_count": split_count,
        "depth": depth,
        "gain_total": gain_total,
        "gain_max": gain_max,
        "gain_mean": gain_mean,
        "gain_per_level": gain_per_level,
        "leaf_value_min": jnp.min(jnp.where(leaf, lv, jnp.inf)),
        "leaf_value_max": jnp.max(jnp.where(leaf, lv, -jnp.inf)),
        "leaf_value_mean": leaf_sum / jnp.maximum(leaf_count, 1),
        "grad_norm": jnp.sqrt(jnp.sum(jnp.square(gpair[..., 0]))),
        "hess_norm": jnp.sqrt(jnp.sum(jnp.square(gpair[..., 1]))),
    }


# ------------------------------------------------------- in-carry eval walk

def walk_leaf_delta(grown, ebins, missing_bin: int, max_depth: int,
                    numeric: bool = False):
    """Per-row leaf value of ``grown`` over a BINNED eval matrix — the
    eval-set margin update folded into the round program. Valid because
    eval DMatrices are binned against the training cuts
    (``core._state_of`` passes ``ref_cuts``), so the tree's ``split_bin``
    thresholds index the same bin space. Routing replicates
    ``ops.partition.advance_positions_level``: strict ``bin > thr`` goes
    right, category-bit-set goes left, missing follows ``default_left``.

    ``numeric`` (no categorical feature in the grower): where the
    gather-free heap walk takes the shape (a TPU: ``ops/histogram.py
    heap_walk_takes``) it states the same leaf values; the per-row gather
    walk below stays for every other case."""
    import jax.numpy as jnp

    from ..ops.histogram import heap_walk_delta, heap_walk_takes
    from ..ops.partition import cat_goes_right

    if numeric and heap_walk_takes(ebins.shape[1], missing_bin, max_depth):
        return heap_walk_delta(grown._asdict(), ebins, missing_bin,
                               max_depth)
    b32 = ebins.astype(jnp.int32)                       # [n, F]
    n = b32.shape[0]
    rows = jnp.arange(n)
    pos = jnp.zeros(n, jnp.int32)
    for _ in range(max(int(max_depth), 1)):
        leaf = grown.is_leaf[pos]
        feat = jnp.maximum(grown.split_feature[pos], 0)
        b = b32[rows, feat]                              # [n]
        go_right = b > grown.split_bin[pos]
        go_right = jnp.where(grown.is_cat_split[pos],
                             cat_goes_right(b, grown.cat_words[pos]),
                             go_right)
        go_right = jnp.where(b == missing_bin,
                             ~grown.default_left[pos], go_right)
        child = 2 * pos + 1 + go_right.astype(jnp.int32)
        pos = jnp.where(leaf, pos, child)
    return grown.leaf_value[pos]


# ------------------------------------------------------ in-trace metrics
#
# jnp twins of the metric/elementwise.py weighted-mean formulas. Each
# returns (numerator, denominator) partial sums; the host finalizer
# routes them through metric.base.global_mean so distributed semantics
# (GlobalRatio over the communicator) match the host metrics exactly.

SUPPORTED_EVAL_METRICS = ("rmse", "mae", "logloss", "error")


def metric_specs(metrics: Sequence[Any]
                 ) -> Optional[Tuple[Tuple[str, float], ...]]:
    """Static (name, param) spec tuple for a Metric list, or None when
    any metric has no in-trace twin (callers then keep the host path)."""
    specs: List[Tuple[str, float]] = []
    for m in metrics:
        name = getattr(m, "name", None)
        if name not in SUPPORTED_EVAL_METRICS:
            return None
        if name == "error":
            try:
                t = float(m.param) if m.param is not None else 0.5
            except (TypeError, ValueError):
                return None
            specs.append((name, t))
        else:
            if m.param is not None:
                return None
            specs.append((name, 0.0))
    return tuple(specs)


def metric_partial(name: str, p, y, w, t: float):
    """(sum(loss * w), sum(w)) for one supported metric, traced."""
    import jax.numpy as jnp

    if name == "rmse":
        loss = jnp.square(p - y)
    elif name == "mae":
        loss = jnp.abs(p - y)
    elif name == "logloss":
        eps = 1e-16
        pc = jnp.clip(p, eps, 1.0 - eps)
        loss = -(y * jnp.log(pc) + (1.0 - y) * jnp.log1p(-pc))
    elif name == "error":
        loss = ((p > t) != (y > 0.5)).astype(jnp.float32)
    else:  # pragma: no cover - guarded by metric_specs
        raise ValueError(f"no in-trace twin for metric {name!r}")
    return jnp.sum(loss * w), jnp.sum(w)


def finalize_partial(name: str, num: float, den: float, info) -> float:
    """Host finalizer: communicator-aware ratio + the metric's finalize."""
    from ..metric.base import global_mean

    mean = global_mean(float(num), float(den), info)
    return float(math.sqrt(mean)) if name == "rmse" else float(mean)


# --------------------------------------- host telemetry (non-fused tiers)

def _entry_arrays(entry) -> Optional[Dict[str, np.ndarray]]:
    """Host node arrays of one committed round tree: a TreeModel, a
    ``_PendingTree`` (device arrays, fetched here — node arrays are tiny),
    or a stacked-dict slice."""
    arrays = getattr(entry, "arrays", None)
    if arrays is None:
        return None  # TreeModel: handled by the caller (compact layout)
    idx = getattr(entry, "index", None)
    out = {}
    for k in ("is_leaf", "active", "gain", "leaf_value"):
        if k not in arrays:
            return None
        v = np.asarray(arrays[k])
        if idx is not None:    # shared stacked dict: leading [K] axis
            v = v[idx]
        out[k] = v
    return out


def round_telemetry_host(trees: Sequence[Any]) -> Optional[Dict[str, Any]]:
    """The general/lossguide/paged/mesh twin of :func:`grown_telemetry`:
    derive the round's scalars host-side from the trees it committed —
    no extra device dispatch (node arrays are fetched, not computed).
    ``grad_norm``/``hess_norm`` are fused-path-only and absent here."""
    leaves = depth = splits = 0
    gain_vals: List[float] = []
    leaf_vals: List[float] = []
    gain_per_level: Dict[int, float] = {}
    saw = False
    for t in trees:
        arrs = _entry_arrays(t)
        if arrs is not None:                   # heap layout (GrownTree)
            active = np.asarray(arrs["active"], bool)
            leaf = np.asarray(arrs["is_leaf"], bool) & active
            split = active & ~np.asarray(arrs["is_leaf"], bool)
            depths = np.floor(np.log2(np.arange(active.shape[-1]) + 1)
                              ).astype(np.int32)
            gv = np.asarray(arrs["gain"], np.float64)
            lv = np.asarray(arrs["leaf_value"], np.float64)
            leaves += int(leaf.sum())
            splits += int(split.sum())
            if leaf.any():
                depth = max(depth, int(depths[leaf].max()))
                leaf_vals.extend(lv[leaf].tolist())
            if split.any():
                gain_vals.extend(gv[split].tolist())
                for d in np.unique(depths[split]):
                    sel = split & (depths == d)
                    gain_per_level[int(d)] = max(
                        gain_per_level.get(int(d), 0.0),
                        float(gv[sel].max()))
            saw = True
        elif hasattr(t, "is_leaf") and hasattr(t, "depths"):  # TreeModel
            is_leaf = np.asarray(t.is_leaf, bool)
            depths = np.asarray(t.depths())
            gv = np.asarray(t.gain, np.float64)
            lv = np.asarray(t.leaf_value, np.float64)
            leaves += int(is_leaf.sum())
            splits += int((~is_leaf).sum())
            if is_leaf.any():
                depth = max(depth, int(depths[is_leaf].max()))
                leaf_vals.extend(np.atleast_1d(
                    lv[is_leaf].reshape(len(depths[is_leaf]), -1)
                    .sum(axis=-1)).tolist())
            if (~is_leaf).any():
                gain_vals.extend(gv[~is_leaf].tolist())
                for d in np.unique(depths[~is_leaf]):
                    sel = ~is_leaf & (depths == d)
                    gain_per_level[int(d)] = max(
                        gain_per_level.get(int(d), 0.0),
                        float(gv[sel].max()))
            saw = True
    if not saw:
        return None
    n_levels = (max(gain_per_level) + 1) if gain_per_level else 1
    out: Dict[str, Any] = {
        "leaf_count": leaves,
        "split_count": splits,
        "depth": depth,
        "gain_total": float(np.sum(gain_vals)) if gain_vals else 0.0,
        "gain_max": float(np.max(gain_vals)) if gain_vals else 0.0,
        "gain_mean": (float(np.mean(gain_vals)) if gain_vals else 0.0),
        "gain_per_level": [gain_per_level.get(d, 0.0)
                           for d in range(n_levels)],
    }
    if leaf_vals:
        out["leaf_value_min"] = float(np.min(leaf_vals))
        out["leaf_value_max"] = float(np.max(leaf_vals))
        out["leaf_value_mean"] = float(np.mean(leaf_vals))
    return out


# --------------------------------------------------- model inspector / diff

_IMPORTANCE_TYPES = ("weight", "gain", "cover", "total_gain", "total_cover")


def model_inspect(booster) -> Dict[str, Any]:
    """Structural + importance report of a Booster: every reference
    importance type (``get_score`` semantics), tree-shape histograms and
    per-model totals. JSON-serializable — the pipeline manifest records
    one per epoch and serve renders it on ``/v1/model/<name>/report``."""
    booster._configure(None)
    report: Dict[str, Any] = {
        "num_trees": int(booster.num_boosted_rounds()),
        "num_features": int(booster.num_features()),
        "importance": {t: booster.get_score(importance_type=t)
                       for t in _IMPORTANCE_TYPES},
    }
    bi = booster.attr("best_iteration")
    if bi is not None:
        report["best_iteration"] = int(bi)
    trees = getattr(booster.gbm, "trees", None)
    if trees is None:
        return report
    depth_hist: Dict[str, int] = {}
    leaf_hist: Dict[str, int] = {}
    nodes = leaves = 0
    for t in trees:
        d = int(t.max_depth())
        nl = int(t.num_leaves())
        depth_hist[str(d)] = depth_hist.get(str(d), 0) + 1
        leaf_hist[str(nl)] = leaf_hist.get(str(nl), 0) + 1
        nodes += int(t.num_nodes())
        leaves += nl
    report["tree_shape"] = {
        "trees": len(trees),
        "nodes_total": nodes,
        "leaves_total": leaves,
        "depth_hist": dict(sorted(depth_hist.items(),
                                  key=lambda kv: int(kv[0]))),
        "leaf_hist": dict(sorted(leaf_hist.items(),
                                 key=lambda kv: int(kv[0]))),
    }
    return report


def _normalized_importance(booster, kind: str) -> Dict[str, float]:
    imp = booster.get_score(importance_type=kind)
    total = sum(imp.values())
    if total <= 0:
        return {k: 0.0 for k in imp}
    return {k: v / total for k, v in imp.items()}


def model_diff(a, b, dm=None, top: int = 5) -> Dict[str, Any]:
    """Attribute the drift between two models to features (and tree-shape
    deltas). With a probe ``dm``, prediction drift is measured directly
    and attributed per feature via the Saabas contribution delta
    (``approx_contribs`` — the same walk serving uses); without one, the
    attribution falls back to normalized total_gain importance deltas.
    ``b`` is the candidate, ``a`` the baseline."""
    a._configure(None)
    b._configure(None)
    imp_a = _normalized_importance(a, "total_gain")
    imp_b = _normalized_importance(b, "total_gain")
    feats = sorted(set(imp_a) | set(imp_b))
    imp_delta = {f: imp_b.get(f, 0.0) - imp_a.get(f, 0.0) for f in feats}

    report: Dict[str, Any] = {
        "num_trees": [int(a.num_boosted_rounds()),
                      int(b.num_boosted_rounds())],
        "importance_delta": imp_delta,
    }
    contrib_drift: Dict[str, float] = {}
    if dm is not None:
        pa = np.asarray(a.predict(dm), np.float64)
        pb = np.asarray(b.predict(dm), np.float64)
        report["prediction_drift"] = float(np.mean(np.abs(pb - pa)))
        try:
            ca = np.asarray(a.predict(dm, pred_contribs=True,
                                      approx_contribs=True), np.float64)
            cb = np.asarray(b.predict(dm, pred_contribs=True,
                                      approx_contribs=True), np.float64)
            if ca.shape == cb.shape and ca.ndim >= 2:
                per_feat = np.mean(np.abs(cb - ca), axis=0).reshape(-1)
                names = a.feature_names or [f"f{i}" for i in
                                            range(per_feat.shape[0] - 1)]
                for i in range(min(len(names), per_feat.shape[0] - 1)):
                    contrib_drift[names[i]] = float(per_feat[i])
                report["contrib_drift"] = contrib_drift
        except Exception:   # contribs unsupported for this booster kind
            pass

    score_of = contrib_drift if contrib_drift else \
        {f: abs(d) for f, d in imp_delta.items()}
    ranked = sorted(score_of.items(), key=lambda kv: (-kv[1], kv[0]))
    report["top_features"] = [
        {"feature": f, "score": float(s),
         "importance_delta": float(imp_delta.get(f, 0.0))}
        for f, s in ranked[:max(int(top), 1)] if s > 0.0]
    return report


# --------------------------------------------------------- env-knob arming

if os.environ.get("XTPU_INSIGHT", "0") not in ("0", ""):
    enable()
if os.environ.get("XTPU_INSIGHT_EVAL", "0") not in ("0", ""):
    enable(eval=True)
