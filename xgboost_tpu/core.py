"""Booster (learner) + train loop — the user-facing training orchestrator.

Reference analogues: ``LearnerImpl`` (``src/learner.cc:1263`` UpdateOneIter /
EvalOneIter / Predict / model IO) and the Python ``Booster`` + ``train()``
(``python-package/xgboost/core.py:1623``, ``training.py:178``). One Booster owns
the objective, the gradient booster (tree forest), the base score, and per-DMatrix
margin caches (the reference's ``PredictionContainer`` version-cache: only trees
added since the cached version are walked, ``src/gbm/gbtree.cc:506-544``).
"""

from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .boosting.dart import Dart
from .boosting.gblinear import GBLinear
from .boosting.gbtree import GBTree
from .context import Context
from .data.dmatrix import DMatrix
from .logging_utils import console, logger
from .metric import get_metric
from .objective import get_objective
from .objective.base import _nan_policy
from .tree.param import TrainParam
from .utils import observer
from .obs import memory as obs_memory
from .obs.metrics import count_degrade, count_round_dispatch
from .obs import trace as obs_trace
from .obs.trace import stage
from .utils.timer import Monitor

_VERSION = (0, 1, 0)

# learner-level keys that are not TrainParam fields
_LEARNER_KEYS = {
    "objective", "num_class", "base_score", "eval_metric", "booster",
    "num_parallel_tree", "tree_method", "device", "seed", "random_state",
    "nthread", "n_jobs", "verbosity", "disable_default_eval_metric",
    "hist_method", "validate_parameters", "seed_per_iteration",
    "multi_strategy", "data_split_mode",
    # objective-specific passthroughs
    "scale_pos_weight", "huber_slope", "tweedie_variance_power",
    "quantile_alpha", "aft_loss_distribution", "aft_loss_distribution_scale",
    "lambdarank_pair_method", "lambdarank_num_pair_per_sample",
    "lambdarank_unbiased", "lambdarank_bias_norm", "ndcg_exp_gain",
    "max_delta_step",
    # dart
    "rate_drop", "one_drop", "skip_drop", "sample_type", "normalize_type",
    # gblinear
    "updater", "feature_selector", "top_k",
}


import functools as _functools


@_functools.partial(jax.jit, static_argnames=("n_valid",))
def _margin_bad_rows(margin, n_valid: int):
    """The NaN-guard reduction as ONE compiled program (op-by-op eager
    jnp here would cost several extra launches per fused round, breaking
    the round programs' <=2-dispatch-per-round budget —
    tests/test_fused_hist.py test_dispatch_count_resident pins the
    count)."""
    return jnp.sum(~jnp.isfinite(margin[:n_valid]).all(axis=-1))


@jax.jit
@stage("margin")
def _add_margin_delta(margin, delta):
    """The general path's margin update as one named program, under the
    root the fused round body gives the same add."""
    return margin + delta


@_functools.partial(jax.jit, static_argnames=("rows", "sharding"))
def _broadcast_rows(base, *, rows: int, sharding):
    """``base`` ``[K]`` as a ``[rows, K]`` margin laid out under
    ``sharding``: every device fills its own rows."""
    return jax.lax.with_sharding_constraint(
        jnp.broadcast_to(base[None, :], (rows, base.shape[0])), sharding)


def _check_margin_finite(margin, n_valid: int, objective: str,
                         first_round: int, n_rounds: int = 1,
                         bad=None) -> None:
    """Post-round half of the NaN guard for the TRACED gradient paths
    (``objective.base.guard_gradient`` raises eagerly on the general path,
    but cannot raise from inside the fused programs). Called on the fused
    round's output margin BEFORE its trees are committed, so under the
    default ``XTPU_NAN_POLICY=raise`` a divergence aborts with the model
    still clean. One scalar device pull per fused round/batch — overlapped
    with the per-round host work that already exists on those paths."""
    from .objective.base import NumericalDivergence, _nan_policy

    if _nan_policy() != "raise":
        return
    # insight-armed rounds pass the guard scalar in (they pull it once and
    # reuse it as the telemetry NaN-guard count — still exactly one guard
    # dispatch per round)
    # the blocking pull: the host waits here for the round program
    with obs_trace.span("round/guard", "train", {"iteration": first_round}):
        bad = int(bad if bad is not None
                  else _margin_bad_rows(margin, n_valid))
    if not bad:
        return
    where = (f"round {first_round}" if n_rounds == 1 else
             f"rounds {first_round}..{first_round + n_rounds - 1}")
    raise NumericalDivergence(
        f"objective {objective!r} diverged at {where}: {bad} row(s) have "
        "non-finite margins — check labels/weights for NaN/Inf. The "
        "offending tree(s) were NOT committed; set XTPU_NAN_POLICY=zero "
        "to drop the bad rows and continue instead.",
        iteration=first_round, objective=objective, bad_rows=bad)


def _fused_round_body(margin, seed, iteration, bins, labels, weights,
                      n_real, monotone, constraint_sets, cat, *,
                      obj_cls, obj_params, param, max_nbins, hist_method,
                      has_missing):
    """The ONE fused round: gradient -> sample -> colsample -> grow ->
    margin update. Shared verbatim by the single-round and round-batched
    jits — the fold_in constants (k, 0xC0, 0x5EED) define the PRNG stream
    that keeps fused, batched, and general paths model-identical.

    Multiclass (K > 1, one_output_per_tree): the K class trees all grow
    from the same margin snapshot (exactly the general path's per-round
    gradient), so a ``lax.scan`` over the class axis folds the whole round
    into this one program — K grow dispatches become zero extra dispatches.
    Returns the grown tree (K == 1) or a dict of per-node arrays stacked on
    a leading [K] class axis."""
    import types

    from .tree.grow import _grow, _sample_features

    from .boosting.gbtree import _grow_classes_scan, sample_gradients

    # identical stream to the general path: fold_in(make_key(it), it)
    key = jax.random.fold_in(jax.random.key(seed), iteration)

    obj = obj_cls(dict(obj_params))
    sinfo = types.SimpleNamespace(labels=labels, weights=weights)
    with stage("gradient"):
        gpair = obj.get_gradient(margin, sinfo, 0)
    K = gpair.shape[1]

    if K == 1:
        # general path key discipline: tkey = fold_in(key, k * npt + p),
        # npt == 1, p == 0, k == 0 on this path
        tkey = jax.random.fold_in(key, 0)
        with stage("gradient"):
            gp = sample_gradients(gpair[:, 0, :], tkey, param)
        tree_mask = _sample_features(jax.random.fold_in(tkey, 0xC0),
                                     n_real > 0, param.colsample_bytree)
        gkey = jax.random.fold_in(tkey, 0x5EED)
        with stage("grow"):     # outermost: nothing inside a tree falls out
            grown = _grow(bins, gp, n_real, tree_mask, gkey, monotone,
                          constraint_sets, cat, param=param,
                          max_nbins=max_nbins, hist_method=hist_method,
                          axis_name=None, has_missing=has_missing)
        with stage("margin"):
            return margin + grown.delta[:, None], grown

    with stage("grow"):
        stacked, delta = _grow_classes_scan(
            bins, gpair, n_real, key, monotone, constraint_sets, cat,
            param=param, max_nbins=max_nbins, hist_method=hist_method,
            has_missing=has_missing)
    with stage("margin"):
        return margin + delta, stacked


@_functools.partial(
    jax.jit,
    donate_argnums=(1,),  # margin: updated in place, caller rebinds
    static_argnames=("obj_cls", "obj_params", "param", "max_nbins",
                     "hist_method", "has_missing", "nan_policy"))
def _fused_round_fn(bins, margin, labels, weights, n_real, seed, iteration,
                    monotone, constraint_sets, cat, *,
                    obj_cls, obj_params, param, max_nbins, hist_method,
                    has_missing, nan_policy="raise"):
    """One boosting round as a single compiled program. Module-level so the
    compile cache is shared across Booster instances.

    ``seed``/``iteration`` arrive as traced scalars and the key is derived
    INSIDE the program: deriving it eagerly cost two extra device dispatches
    per round.

    ``nan_policy`` is never read in the body: XTPU_NAN_POLICY is consulted
    at TRACE time (``objective.base.guard_gradient`` bakes the zero-policy
    ``where`` into the program, or omits it), so the active policy must be
    part of the compile-cache key or a policy change after the first
    compile would silently keep running the old program."""
    return _fused_round_body(
        margin, seed, iteration, bins, labels, weights, n_real, monotone,
        constraint_sets, cat, obj_cls=obj_cls, obj_params=obj_params,
        param=param, max_nbins=max_nbins, hist_method=hist_method,
        has_missing=has_missing)


def steady_round_dispatches():
    """The jitted programs ONE steady resident boosting round dispatches,
    in call order: the fused round itself and the NaN-guard reduction
    (``_fused_step`` below is the driver that calls exactly these two).
    This list is the source of truth for the round programs'
    dispatches-per-round budget — ``tests/test_fused_hist.py
    test_dispatch_count_resident`` pins it at runtime, and
    ``tools/xtpuverify``'s dispatch-budget contract checks
    it statically (xgboost_tpu/programs.py), so the budget survives even
    where cache-hit calls run on the C++ fast path invisible to Python
    hooks. Adding a per-round dispatch means growing this list AND
    raising the contract in tools/xtpuverify/contracts.py — deliberately
    two visible edits."""
    return (_fused_round_fn, _margin_bad_rows)


@_functools.partial(
    jax.jit,
    # margin + eval margins: updated in place, caller rebinds
    donate_argnums=(1, 11),
    static_argnames=("obj_cls", "obj_params", "param", "max_nbins",
                     "hist_method", "has_missing", "nan_policy",
                     "eval_specs", "eval_missing"))
def _fused_round_insight_fn(bins, margin, labels, weights, n_real, seed,
                            iteration, monotone, constraint_sets, cat,
                            eval_bins, eval_margins, eval_labels,
                            eval_weights, *,
                            obj_cls, obj_params, param, max_nbins,
                            hist_method, has_missing, nan_policy="raise",
                            eval_specs=(), eval_missing=()):
    """The insight-armed twin of ``_fused_round_fn``: the SAME round body
    (shared verbatim, so the model-math subgraph is identical and the
    committed trees stay byte-for-byte equal to the unarmed path), plus
    learning-health telemetry and the eval-set update as EXTRA OUTPUTS of
    the one program — never an extra dispatch. ``tools/xtpuverify`` pins
    the ``resident.*.insight`` contracts to the unarmed budget.

    ``eval_*``: parallel tuples, one entry per armed eval DMatrix —
    train-cut bins [n_e, F] u8, carried margin [n_e, K] (donated), labels,
    weights (or None). ``eval_specs``: static ((metric_name, param), ...)
    driving the in-trace partial reductions; ``eval_missing``: static
    per-eval-matrix missing-bin ids. The gradient is recomputed with the
    round body's exact expression, so XLA CSEs it against the round's own.
    """
    from .obs import insight as _insight

    new_margin, grown = _fused_round_body(
        margin, seed, iteration, bins, labels, weights, n_real, monotone,
        constraint_sets, cat, obj_cls=obj_cls, obj_params=obj_params,
        param=param, max_nbins=max_nbins, hist_method=hist_method,
        has_missing=has_missing)

    import types

    obj = obj_cls(dict(obj_params))
    sinfo = types.SimpleNamespace(labels=labels, weights=weights)
    gpair = obj.get_gradient(margin, sinfo, 0)
    telem = _insight.grown_telemetry(grown, gpair,
                                     max(param.max_depth, 1))

    new_eval_margins = []
    partials = []
    for i, (ebins, emargin, elabels, eweights) in enumerate(
            zip(eval_bins, eval_margins, eval_labels, eval_weights)):
        delta = _insight.walk_leaf_delta(grown, ebins, eval_missing[i],
                                         max(param.max_depth, 1),
                                         numeric=cat is None)
        nem = emargin + delta[:, None]
        new_eval_margins.append(nem)
        preds = obj.pred_transform(nem)[:, 0]
        w = eweights if eweights is not None else \
            jnp.ones_like(elabels, dtype=jnp.float32)
        partials.append(tuple(
            _insight.metric_partial(name, preds, elabels, w, mparam)
            for name, mparam in eval_specs))
    return (new_margin, grown, telem, tuple(new_eval_margins),
            tuple(partials))


def steady_round_dispatches_insight():
    """``steady_round_dispatches``'s insight-armed twin: the programs one
    steady ARMED resident round dispatches, in call order. Same length as
    the unarmed list — telemetry and the in-carry eval ride the round
    program as extra outputs; the guard reduction doubles as the
    NaN-telemetry source. ``tools/xtpuverify`` pins the
    ``resident.*.insight`` handles to the unarmed budget (contracts.py),
    so smuggling a telemetry dispatch in here is a gate failure, not a
    silent regression."""
    return (_fused_round_insight_fn, _margin_bad_rows)


@_functools.partial(
    jax.jit,
    static_argnames=("obj_cls", "obj_params", "specs", "rows"))
def _eval_partials_fn(margins, labels, weights, *,
                      obj_cls, obj_params, specs, rows):
    """Every eval DMatrix x every metric as ONE compiled program: the old
    eval_set host loop pulled the transformed predictions per DMatrix and
    reduced per metric on the host — a host round-trip per (dm, metric)
    pair per round. This returns the (weighted-loss-sum, weight-sum)
    partials for all of them in a single dispatch; the host only finalizes
    the ratios (through ``metric.base.global_mean``, so distributed
    semantics are unchanged). ``rows`` is the static per-matrix valid-row
    count (train margins arrive padded)."""
    from .obs import insight as _insight

    obj = obj_cls(dict(obj_params))
    out = []
    for i, (m, y, w) in enumerate(zip(margins, labels, weights)):
        p = obj.pred_transform(m[:rows[i]])[:, 0]
        yy = y[:rows[i]]
        ww = w[:rows[i]] if w is not None else \
            jnp.ones_like(yy, dtype=jnp.float32)
        out.append(tuple(
            _insight.metric_partial(name, p, yy, ww, mparam)
            for name, mparam in specs))
    return tuple(out)


@_functools.partial(
    jax.jit,
    donate_argnums=(1,),  # margin: updated in place, caller rebinds
    static_argnames=("obj_cls", "obj_params", "param", "max_nbins",
                     "hist_method", "has_missing", "nan_policy"))
def _fused_multi_round_fn(bins, margin, labels, weights, n_real, seeds,
                          iterations, monotone, constraint_sets, cat, *,
                          obj_cls, obj_params, param, max_nbins, hist_method,
                          has_missing, nan_policy="raise"):
    """K boosting rounds as ONE dispatch (``lax.scan`` over the shared
    round body — byte-identical numerics to K sequential
    ``_fused_round_fn`` calls), batching away per-dispatch host/enqueue
    latency when nothing consumes per-round output.

    seeds/iterations: [K] arrays. Returns (margin, dict of per-NODE tree
    arrays stacked on a leading [K] axis — the per-ROW positions/delta are
    deliberately NOT stacked: [K, n] outputs would cost hundreds of MB at
    10M-row scale for data the caller never reads)."""
    from .boosting.gbtree import _GROWN_FIELDS

    def body(m, si):
        seed, it = si
        new_margin, grown = _fused_round_body(
            m, seed, it, bins, labels, weights, n_real, monotone,
            constraint_sets, cat, obj_cls=obj_cls, obj_params=obj_params,
            param=param, max_nbins=max_nbins, hist_method=hist_method,
            has_missing=has_missing)
        if isinstance(grown, dict):     # multiclass: already stacked [Kc]
            return new_margin, grown
        node_arrays = {f: getattr(grown, f) for f in _GROWN_FIELDS}
        return new_margin, node_arrays

    new_margin, stacked = jax.lax.scan(body, margin, (seeds, iterations))
    if margin.shape[1] > 1:
        # [R, Kc, ...] -> [R * Kc, ...]: _flush slices trees by flat index
        stacked = {f: v.reshape((v.shape[0] * v.shape[1],) + v.shape[2:])
                   for f, v in stacked.items()}
    return new_margin, stacked


class Booster:
    """A trained / in-training gradient-boosting model."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 cache: Optional[Sequence[DMatrix]] = None,
                 model_file: Optional[str] = None) -> None:
        self.tree_param = TrainParam()
        self.learner_params: Dict[str, Any] = {
            "objective": "reg:squarederror", "booster": "gbtree",
            "num_parallel_tree": 1, "tree_method": "auto", "num_class": 0,
        }
        self.ctx = Context()
        self.attributes_: Dict[str, str] = {}
        self.feature_names: Optional[List[str]] = None
        self.feature_types: Optional[List[str]] = None
        self.obj = None
        self.gbm: Optional[GBTree] = None
        # [K] margin space; device-resident (jnp) right after a
        # single-process stump fit, np after _base_np() materializes it
        self.base_margin_: Optional[Any] = None
        self._configured = False
        self._monitor = Monitor("Booster")
        # fast-path cache: (state_dict, obj_params, grower, labels, weights,
        # n_real); element 0's IDENTITY is the staleness check — a different
        # training DMatrix produces a different state dict and forces rebind
        self._fused_round = None
        self._fused_blocked = False
        self._caches: Dict[int, Dict[str, Any]] = {}
        self._eval_metrics: List = []
        # xtpuinsight (obs/insight.py): the TrainingLog this booster logs
        # into (train() rebinds it to the callback container's history),
        # the armed in-carry state (eval bins/margins riding the fused
        # program), the round's finalized eval scores, the eval sets
        # train() armed, and the insight-only fallback latch
        self.training_log = None
        self._insight_state: Optional[Dict[str, Any]] = None
        self._insight_scores: Optional[Dict[str, Any]] = None
        self._insight_evals: Optional[List[Tuple[DMatrix, str]]] = None
        self._insight_blocked = False
        self._explicit_params: set = set()
        if params:
            self.set_param(params)
        if model_file is not None:
            self.load_model(model_file)

    # ------------------------------------------------------------------ params
    def set_param(self, params: Union[Dict[str, Any], str, List[Tuple[str, Any]]],
                  value: Optional[Any] = None) -> None:
        if isinstance(params, str):
            params = {params: value}
        elif isinstance(params, list):
            params = dict(params)
        params = dict(params)
        self._explicit_params.update(params.keys())
        if "mesh" in params:
            mesh = params.pop("mesh")
            if mesh is not None:
                self.ctx = self.ctx.with_mesh(mesh)
        if "eval_metric" in params:
            em = params.pop("eval_metric")
            names = em if isinstance(em, (list, tuple)) else [em]
            self.learner_params["eval_metric"] = list(names)
            self._eval_metrics = [get_metric(n) for n in names]
        for k in list(params):
            if k in _LEARNER_KEYS:
                self.learner_params[k] = params.pop(k)
        unknown = self.ctx.update_allow_unknown(params)
        unknown = self.tree_param.update_allow_unknown(unknown)
        for k in unknown:
            logger.warning("Unknown parameter: %s", k)
        # param changes invalidate lazy config (objective/eta may differ)
        if self._configured and self.obj is not None:
            new_obj = self.learner_params.get("objective", self.obj.name)
            if new_obj != self.obj.name:
                self.obj = get_objective(
                    new_obj, {k: v for k, v in self.learner_params.items()
                              if k not in ("objective", "booster")})
            else:
                self.obj.configure(
                    {k: v for k, v in self.learner_params.items()
                     if k not in ("objective", "booster")})
            if self.gbm is not None:
                self.gbm.tree_param = self.tree_param
                self.gbm._grower = None  # rebind with new params
            self._fused_round = None     # re-derive objective/tree config
            self._fused_blocked = False
            self._insight_state = None   # eval carry binds per-config too

    # --------------------------------------------------------------- configure
    def _configure(self, dtrain: Optional[DMatrix]) -> None:
        if self._configured:
            return
        if dtrain is None:
            self._configure_learner(None)
            return
        with obs_trace.phase("train/state", "train"):
            self._configure_learner(dtrain)

    def _configure_learner(self, dtrain: Optional[DMatrix]) -> None:
        tm = self.learner_params.get("tree_method", "auto")
        if tm not in ("auto", "hist", "gpu_hist", "tpu_hist", "approx",
                      "exact"):
            raise NotImplementedError(
                f"tree_method={tm} is not implemented; use hist/approx/exact")
        if tm == "exact" and self.ctx.mesh is not None:
            raise ValueError("tree_method=exact does not support "
                             "distributed training (reference ColMaker "
                             "limitation)")
        if self.tree_param.grow_policy not in ("depthwise", "lossguide"):
            raise ValueError(
                f"unknown grow_policy={self.tree_param.grow_policy}; use "
                "'depthwise' or 'lossguide'")
        if self.tree_param.grow_policy == "lossguide" and tm == "exact":
            raise ValueError("tree_method=exact only supports "
                             "grow_policy=depthwise (reference ColMaker)")
        if tm == "exact" and self.tree_param.max_leaves > 0:
            raise NotImplementedError(
                "tree_method=exact does not support max_leaves")
        if (self.tree_param.grow_policy == "depthwise"
                and self.tree_param.max_depth <= 0):
            raise ValueError("grow_policy=depthwise requires max_depth > 0")
        if (dtrain is not None and self._is_vertical_federated()
                and dtrain.info.data_split_mode != "col"):
            # under vertical federated the DMatrix flag drives the
            # row_split guards inside metrics/objectives; with it unset the
            # label rank would issue extra collectives inside
            # apply_with_labels closures and the ranks would deadlock on
            # mismatched collectives instead of erroring
            raise ValueError(
                "vertical federated training requires the DMatrix to be "
                "constructed with data_split_mode='col' (got "
                f"{dtrain.info.data_split_mode!r})")
        obj_name = self.learner_params.get("objective", "reg:squarederror")
        if self.obj is None or getattr(self.obj, "name", None) != obj_name:
            self.obj = get_objective(
                obj_name, {k: v for k, v in self.learner_params.items()
                           if k not in ("objective", "booster")})
        info = dtrain.info if dtrain is not None else None
        n_groups = max(1, self.obj.n_targets(info))
        if dtrain is not None and not getattr(self, "_num_features", 0):
            self._num_features = dtrain.num_col()
        if self.gbm is None:
            self.gbm = self._make_booster(
                n_groups, dtrain.num_col() if dtrain is not None else 0)
        if self.base_margin_ is None:
            if "base_score" in self.learner_params and \
                    self.learner_params["base_score"] is not None:
                bs = float(self.learner_params["base_score"])
                margin = self.obj.prob_to_margin(np.asarray([bs]))
                self.base_margin_ = np.full(n_groups, margin,
                                            dtype=np.float32).reshape(-1)
                if self.base_margin_.shape[0] != n_groups:
                    self.base_margin_ = np.full(n_groups, float(margin),
                                                dtype=np.float32)
            elif dtrain is not None and (dtrain.info.labels is not None
                                         or self._is_vertical_federated()):
                # vertical federated: only the label rank can fit the stump;
                # everyone receives its estimate (reference ApplyWithLabels
                # around InitEstimation, src/objective/init_estimation.cc)
                def _est():
                    return np.asarray(self.obj.init_estimation(dtrain.info),
                                      dtype=np.float32).reshape(-1)

                if self._is_vertical_federated():
                    from .parallel.collective import apply_with_labels

                    est = np.asarray(apply_with_labels(_est), np.float32)
                else:
                    from .objective.base import Objective
                    from .parallel import collective

                    if (not collective.is_distributed()
                            and type(self.obj).init_estimation
                            is Objective.init_estimation):
                        # device-resident stump: no host pull on the
                        # train() critical path (the value materializes
                        # lazily at first predict/serialize)
                        est = self.obj.init_estimation_device(dtrain.info)
                    else:
                        est = _est()
                if est.shape[0] != n_groups:
                    est = np.full(
                        n_groups,
                        float(np.asarray(est)[0]) if est.size else 0.0,
                        np.float32)
                self.base_margin_ = est
            else:
                self.base_margin_ = np.zeros(n_groups, dtype=np.float32)
        if not self._eval_metrics and not bool(self.learner_params.get(
                "disable_default_eval_metric", False)):
            self._eval_metrics = [get_metric(self.obj.default_metric)]
        if dtrain is not None and self.feature_names is None:
            self.feature_names = dtrain.info.feature_names
            self.feature_types = dtrain.info.feature_types
        self._configured = True

    def _make_booster(self, n_groups: int, n_features: int = 0):
        name = self.learner_params.get("booster", "gbtree")
        if name == "gblinear":
            # reference gblinear defaults: lambda/alpha 0 unless set by user
            lam = self.tree_param.reg_lambda if (
                {"lambda", "reg_lambda"} & self._explicit_params) else 0.0
            alpha = self.tree_param.reg_alpha if (
                {"alpha", "reg_alpha"} & self._explicit_params) else 0.0
            return GBLinear(
                n_groups,
                updater=self.learner_params.get("updater", "shotgun"),
                reg_lambda=lam, reg_alpha=alpha, eta=self.tree_param.eta,
                feature_selector=self.learner_params.get(
                    "feature_selector", "cyclic"),
                mesh=self.ctx.mesh)
        from .tree.param import (parse_interaction_constraints,
                                 parse_monotone_constraints)

        # model-load path: the booster is rebuilt before any DMatrix is
        # seen, so the deserialized learner_model_param num_feature is the
        # only feature count available for constraint parsing
        nf = (n_features or getattr(self, "_num_features", 0)
              or (len(self.feature_names) if self.feature_names else 0))
        if self._is_vertical_federated():
            # constraints index GLOBAL features, but nf counts only this
            # party's block — parse against the summed per-party width
            # (symmetric collective; every party passes the same config)
            from .parallel import collective as _coll

            if self.tree_param.monotone_constraints \
                    or self.tree_param.interaction_constraints:
                nf = int(_coll.allreduce(
                    np.asarray([nf], np.float32), op="sum")[0])
            mono = parse_monotone_constraints(
                self.tree_param.monotone_constraints, nf)
            ics = parse_interaction_constraints(
                self.tree_param.interaction_constraints or None, nf, None)
        else:
            mono = parse_monotone_constraints(
                self.tree_param.monotone_constraints, nf)
            ics = parse_interaction_constraints(
                self.tree_param.interaction_constraints or None, nf,
                self.feature_names)
        tm = self.learner_params.get("tree_method", "auto")
        ms = self.learner_params.get("multi_strategy", "one_output_per_tree")
        if ms not in ("one_output_per_tree", "multi_output_tree"):
            raise ValueError(f"unknown multi_strategy: {ms}")
        if ms == "multi_output_tree" and (mono is not None or name == "dart"):
            # reference parity: the reference itself CHECKs monotone empty
            # for vector-leaf trees (src/tree/updater_quantile_hist.cc:500)
            # and rejects dart (src/gbm/gbtree.cc:745); interaction
            # constraints ARE supported (HistMultiEvaluator queries them,
            # src/tree/hist/evaluate_splits.h:666-669)
            raise NotImplementedError(
                "multi_output_tree does not support monotone constraints "
                "or the dart booster (the reference rejects both for "
                "vector-leaf trees)")
        from .tree.grow import HIST_METHODS, TWO_LEVEL_METHODS

        # XTPU_HIST_METHOD overrides the default kernel selection for
        # harness A/Bs without touching params (construction-time env
        # read, docs/env_knobs.md); an explicit param always wins
        hist_method = self.learner_params.get("hist_method")
        hist_from = "hist_method"
        if hist_method is None:
            hist_method = os.environ.get("XTPU_HIST_METHOD", "auto")
            hist_from = "XTPU_HIST_METHOD"
        if hist_method not in HIST_METHODS:
            raise ValueError(
                f"unknown {hist_from} {hist_method!r}: the accepted names "
                f"are {', '.join(HIST_METHODS)} ('scan', 'mega', 'prehot' "
                "and the '+sub'/'+nosub' suffixes were removed in PR 31)")
        if hist_method in TWO_LEVEL_METHODS \
                and (tm in ("approx", "exact")
                     or ms == "multi_output_tree"):
            raise NotImplementedError(
                "hist_method='coarse'/'fused' supports the "
                "hist updaters (depthwise or lossguide, resident or "
                "external-memory depthwise) with scalar trees only")
        dsm = self.learner_params.get("data_split_mode", "row")
        if dsm not in ("row", "col"):
            raise ValueError(f"unknown data_split_mode: {dsm}")
        if dsm == "col":
            from .parallel import collective

            if self.ctx.mesh is None and not collective.is_distributed():
                raise ValueError(
                    "data_split_mode=col requires a mesh (in-process column "
                    "sharding) or an active distributed communicator "
                    "(vertical federated training)")
            if tm == "exact":
                # reference parity: ColMaker has no distributed support
                # (src/tree/updater_colmaker.cc CHECKs kRow); approx shares
                # the hist col-split evaluator (updater_approx.cc runs
                # under DataSplitMode::kCol via evaluate_splits.h:294-409)
                raise NotImplementedError(
                    "data_split_mode=col supports tree_method=hist/approx")
            if self.ctx.mesh is None:
                # vertical federated (communicator ranks, no mesh): the
                # decision-bit protocol covers scalar trees — depthwise
                # and lossguide, gbtree and dart (r5 lift; reference:
                # the col-split evaluator is updater-generic,
                # src/tree/hist/evaluate_splits.h:294-409)
                if ms == "multi_output_tree":
                    raise NotImplementedError(
                        "vertical federated column split supports "
                        "scalar trees only")
                if name == "gblinear":
                    raise NotImplementedError(
                        "vertical federated column split supports tree "
                        "boosters only (the reference's linear updaters "
                        "run under DataSplitMode::kRow)")
        kwargs = dict(
            num_parallel_tree=int(self.learner_params.get(
                "num_parallel_tree", 1)),
            hist_method=hist_method,
            mesh=self.ctx.mesh, monotone=mono, constraint_sets=ics,
            tree_method=tm if tm in ("approx", "exact") else "hist",
            multi_strategy=ms, split_mode=dsm)
        if name == "dart":
            kwargs.pop("multi_strategy")
            gbm = Dart(self.tree_param, n_groups, **kwargs)
            gbm.configure(self.learner_params)
            return gbm
        if name != "gbtree":
            raise ValueError(f"unknown booster: {name}")
        return GBTree(self.tree_param, n_groups, **kwargs)

    def _base_np(self) -> np.ndarray:
        """base_margin_ as a HOST array — the device-resident stump
        estimate materializes here once (first predict/serialize) and is
        cached back, so later calls pay no device pull."""
        if self.base_margin_ is None:
            return np.zeros(self.n_groups, np.float32)
        if not isinstance(self.base_margin_, np.ndarray):
            self.base_margin_ = np.asarray(self.base_margin_, np.float32)
        return self.base_margin_

    @property
    def n_groups(self) -> int:
        return self.gbm.n_groups if self.gbm is not None else 1

    def _is_vertical_federated(self) -> bool:
        """Column split across communicator ranks (no device mesh): rows
        and margins replicate, features partition, labels may live only on
        the label rank — every label-derived quantity must route through
        ``apply_with_labels``."""
        if self.learner_params.get("data_split_mode", "row") != "col" \
                or self.ctx.mesh is not None:
            return False
        from .parallel import collective

        return collective.is_distributed()

    # ---------------------------------------------------------------- training
    def _state_of(self, dm: DMatrix, is_train: bool) -> Dict[str, Any]:
        key = id(dm)
        tm = getattr(self.gbm, "tree_method", "hist")
        needs_binned = tm not in ("approx", "exact")
        if key in self._caches \
                and self._caches[key]["n_valid"] != dm.num_row():
            # rows appended since this entry was built (DMatrix.append):
            # the cached margin/labels/bins are all row-count-dependent.
            # Rebuild from scratch — the continuation bootstrap in
            # update()/update_batch() re-folds the committed trees' margin
            # over the grown matrix, so training continues correctly.
            del self._caches[key]
        if key in self._caches and is_train and (
                not self._caches[key]["is_train"]
                or (needs_binned and self._caches[key]["binned"] is None)):
            # first seen as eval-only; rebuild as a training entry
            del self._caches[key]
        if key not in self._caches:
            if not is_train:
                return self._make_state(key, dm, False, tm)
            with obs_trace.phase("train/state", "train"):
                return self._make_state(key, dm, True, tm)
        elif is_train and self.ctx.mesh is None and not getattr(
                dm, "presharded", False):
            # a communicator activated AFTER the entry was built (training
            # continuation on a persistent booster) must still refuse
            # silently-local resident training — including a matrix the
            # paged collapse already swapped for a resident one. approx/
            # exact entries carry binned=None, so the re-check consults
            # the DMatrix's own quantized form like the build-time path:
            # approx over ITERATOR-PAGED data syncs (sketch merge + paged
            # hist allreduce) and passes; everything else with binned=None
            # still refuses
            self._check_row_comm_sync(paged=(
                getattr(self._caches[key]["binned"], "is_paged", False)
                or (tm == "approx" and getattr(
                    getattr(dm, "_binned", None), "is_paged", False))))
        return self._caches[key]

    def _make_state(self, key: int, dm: DMatrix, is_train: bool,
                    tm: str) -> Dict[str, Any]:
        """``_state_of``'s miss: quantized matrix, starting margin and the
        cache entry for ``dm``."""
        if is_train and getattr(dm, "presharded", False):
            # ShardedDMatrix (parallel/launch.py): the global quantized
            # matrix was already assembled from per-process shards — no
            # host-global arrays exist anywhere. Must be checked before
            # the exact branch: that trains on raw thresholds of the
            # (local-only) X and would silently fit 1/N of the data.
            # approx works: it re-sketches through the distributed
            # merge every iteration (dm.resketch_binned).
            if tm == "exact":
                raise NotImplementedError(
                    "tree_method=exact is not supported with sharded "
                    "multi-process ingestion; use hist or approx")
            base = self._base_np()
            return self._store_cache(
                key, None if tm == "approx" else dm.global_binned(),
                dm.make_margin(base, self.n_groups), True, dm,
                dm.device_info(), dm.num_row())
        if is_train and tm in ("approx", "exact"):
            # approx re-sketches per iteration and exact rank-encodes
            # losslessly — neither trains against a shared binned matrix,
            # so margins always walk raw thresholds (binned=None).
            # approx over an iterator-built PAGED matrix DOES sync under
            # a communicator (per-iteration sketch merge + the paged
            # hist driver's per-level allreduce), so it passes the
            # row-comm check like the hist paged tier; exact still
            # refuses (it rejects paged matrices outright in do_boost).
            binned = None
            self._check_row_comm_sync(paged=(
                tm == "approx" and getattr(
                    getattr(dm, "_binned", None), "is_paged", False)))
        elif is_train:
            if self.ctx.mesh is not None:
                return self._make_sharded_train_state(key, dm)
            binned = dm.binned(self.tree_param.max_bin)
            binned = self._collapse_paged_if_fits(binned)
            self._check_row_comm_sync(
                paged=getattr(binned, "is_paged", False))
        else:
            train_cuts = None
            for st in self._caches.values():
                if st.get("is_train") and st["binned"] is not None:
                    train_cuts = st["binned"].cuts
                    break
            # The binned fast path is only valid against the cuts the
            # trees were grown with; without them (e.g. a loaded model)
            # fall back to raw-threshold prediction (binned=None).
            binned = (dm.binned(self.tree_param.max_bin,
                                ref_cuts=train_cuts)
                      if train_cuts is not None else None)
            if binned is not None:
                binned = self._collapse_paged_if_fits(binned)
        n = dm.num_row()
        margin = jnp.asarray(self._broadcast_base_margin(dm, n))
        return self._store_cache(key, binned, margin, is_train, dm, dm.info,
                                 n)

    def _collapse_paged_if_fits(self, binned):
        """External-memory fast path: when a paged matrix fits the HBM
        page-cache budget on a single-rank, no-mesh config, swap it for a
        device-resident BinnedMatrix (PagedBinnedMatrix.resident_binned)
        — downstream the whole-tree-jitted resident growers, margin
        caches and predictors take over at resident speed. Multi-rank row
        split keeps the paged tier: its per-level histogram allreduce IS
        the cross-rank sync (_check_row_comm_sync). Mesh configs keep it
        too (train and eval alike): collapsing would pull every page onto
        ONE device of a mesh that exists to split memory — the paged-mesh
        kernels stream per-shard instead."""
        if not getattr(binned, "is_paged", False):
            return binned
        if self.ctx.mesh is not None:
            return binned
        from .parallel import collective

        comm = collective.get_communicator()
        if comm.is_distributed() and comm.get_world_size() > 1:
            return binned
        res = binned.resident_binned()
        return binned if res is None else res

    def _check_row_comm_sync(self, paged: bool) -> None:
        """Refuse silently-local training: with an active world>1
        communicator and no device mesh, ROW-split training syncs only on
        the external-memory tier (per-level histogram allreduce,
        tree/paged.py) — the resident growers run the whole tree in one
        jitted program with no communicator hook, so each rank would fit
        only its local rows and diverge without any error. The reference
        allreduces inside its hist builders (src/tree/hist/histogram.h:
        183-190); our multi-host resident path is the global mesh
        (parallel/launch.train_per_host, mesh = world)."""
        if paged or self.learner_params.get(
                "data_split_mode", "row") != "row":
            return
        if self.learner_params.get("process_type") == "update":
            # prune/refresh/sync are rank-local ops on replicated trees
            # (no histogram build) — documented safe under a communicator
            return
        from .parallel import collective

        comm = collective.get_communicator()
        if comm.is_distributed() and comm.get_world_size() > 1:
            raise NotImplementedError(
                "row-split training of a RESIDENT matrix under a "
                "multi-rank communicator is not synchronized (each rank "
                "would silently fit only its local rows); use "
                "parallel.launch.train_per_host (sharded ingestion over "
                "the global mesh) or an external-memory DMatrix (pages "
                "sync through the communicator)")

    def _store_cache(self, key, binned, margin, is_train, dm, info,
                     n_valid):
        """One schema for every training/prediction cache entry."""
        self._caches[key] = {"binned": binned, "margin": margin,
                             "base": margin, "n_trees": 0,
                             "is_train": is_train, "dm": dm, "info": info,
                             "n_valid": n_valid}
        return self._caches[key]

    def _broadcast_base_margin(self, dm: DMatrix, n: int):
        """Per-row starting margin [n, n_groups]: the DMatrix's base_margin
        when set, else the learner's global base score. The global-score
        case broadcasts ON DEVICE — a host [n, K] materialization plus its
        H2D upload on every train() start, for an array that is a
        constant."""
        if dm.info.base_margin is not None:
            bm = np.asarray(dm.info.base_margin, np.float32).reshape(n, -1)
            return np.broadcast_to(bm, (n, self.n_groups)).copy()
        base = jnp.asarray(self.base_margin_, jnp.float32).reshape(-1)
        return jnp.broadcast_to(base[None, :], (n, self.n_groups))

    def _make_sharded_train_state(self, key: int,
                                  dm: DMatrix) -> Dict[str, Any]:
        """Shard the quantized matrix / margin over the mesh ``data`` axis,
        padding rows to a multiple of the axis size. Padded rows carry weight 0
        so gradients vanish (the reference's row shards are simply unequal;
        static XLA shapes want equal shards instead). Every per-row array
        (bins, labels, weights, margin) goes from the host to its shards
        block by block (``data/binned.py put_row_shards``): no device holds
        a whole one. An iterator's bin matrix that is still on the host
        (``DMatrix.place_binned``) is placed that way too, and never pulled
        back.

        With ``data_split_mode=col`` the FEATURE axis is sharded instead
        (reference ``DataSplitMode::kCol``): rows replicate, features pad to
        the axis size with zero-bin columns whose real-bin count is 0 so they
        can never win a split."""
        import jax.sharding as jsh

        from .context import DATA_AXIS
        from .data.binned import BinnedMatrix, put_row_shards
        from .data.dmatrix import MetaInfo
        from .obs.metrics import set_mesh_layout

        mesh = self.ctx.mesh
        world = mesh.shape.get(DATA_AXIS, 1)
        n = dm.num_row()
        sharding = jsh.NamedSharding(mesh, jsh.PartitionSpec(DATA_AXIS, None))
        col = self.learner_params.get("data_split_mode", "row") == "col"
        placed = None if col or not hasattr(dm, "place_binned") \
            else dm.place_binned(sharding)
        binned = placed if placed is not None \
            else dm.binned(self.tree_param.max_bin)
        paged = getattr(binned, "is_paged", False)
        if col:
            if paged:
                raise NotImplementedError(
                    "external-memory (paged) training supports "
                    "data_split_mode=row only")
            from .data.binned import pad_features_for_mesh

            binned_p = pad_features_for_mesh(binned, mesh, DATA_AXIS)
            margin = jnp.asarray(self._broadcast_base_margin(dm, n))
            return self._store_cache(key, binned_p, margin, True, dm,
                                     dm.info, n)
        if paged:
            # mesh x external memory: bins STAY host-resident and stream
            # per-shard (PagedBinnedMatrix.pages_sharded); only the per-row
            # vectors pad to the page-aligned mesh layout and shard
            n_pad = binned.mesh_layout(world)[0]
            binned_p = binned
        elif placed is not None:
            n_pad = placed.n_rows
            binned_p = placed
        else:
            n_pad = ((n + world - 1) // world) * world
            # any in-range bin works: padded rows carry zero gradient,
            # so they never contribute to histograms or leaf sums
            binned_p = BinnedMatrix(
                bins=put_row_shards(
                    np.asarray(binned.bins), sharding, n_pad,
                    min(binned.missing_bin, binned.max_nbins - 1)),
                cuts=binned.cuts, max_nbins=binned.max_nbins,
                has_missing=binned.has_missing)
        pad = n_pad - n
        set_mesh_layout(world, n_pad // world)

        info = dm.info
        labels = info.labels if info.labels is not None else np.zeros(n)
        labels = np.asarray(labels, dtype=np.float32)
        # unweighted rows that fill their shards need no weight vector
        weights = (np.asarray(info.weights, np.float32)
                   if info.weights is not None
                   else np.ones(n, np.float32) if pad else None)
        lb, ub = info.label_lower_bound, info.label_upper_bound

        def padded(a, fill):
            return a if a is None or not pad else np.concatenate(
                [a, np.full((pad,) + a.shape[1:], fill, np.float32)])
        info_p = MetaInfo(
            labels=padded(labels, 0.0), weights=padded(weights, 0.0),
            group_ptr=info.group_ptr,
            label_lower_bound=padded(lb, 1.0),
            label_upper_bound=padded(ub, 1.0),
            feature_names=info.feature_names, feature_types=info.feature_types)
        # the device copies the objective reads every round, sharded like
        # the rows (``MetaInfo.labels_device`` keys them by array identity)
        row_sharding = jsh.NamedSharding(mesh, jsh.PartitionSpec(DATA_AXIS))
        for host, slot in ((info_p.labels, "_labels_dev"),
                           (info_p.weights, "_weights_dev")):
            if host is not None:
                setattr(info_p, slot, (host, put_row_shards(
                    host, sharding if host.ndim == 2 else row_sharding,
                    n_pad)))

        # the whole-array device copies the stump fit left on one device
        # (``_configure`` reads ``dm.info`` before any state exists) go: the
        # rounds read the sharded ones
        for slot in ("_labels_dev", "_weights_dev"):
            dm.info.__dict__.pop(slot, None)

        if dm.info.base_margin is not None:
            margin = put_row_shards(self._broadcast_base_margin(dm, n),
                                    sharding, n_pad)
        else:       # a constant: broadcast on the devices, shard by shard
            margin = _broadcast_rows(
                jnp.asarray(self.base_margin_, jnp.float32).reshape(-1),
                rows=n_pad, sharding=sharding)
        return self._store_cache(key, binned_p, margin, True, dm, info_p, n)

    def update(self, dtrain: DMatrix, iteration: int,
               fobj: Optional[Callable] = None) -> None:
        """One boosting iteration (reference ``XGBoosterUpdateOneIter``)."""
        self._configure(dtrain)
        if self.tree_param.process_type == "update":
            self._update_existing_trees(dtrain, fobj=fobj)
            return
        state = self._state_of(dtrain, is_train=True)
        # training continuation (xgb_model= / loaded checkpoint): a fresh
        # cache starts at the base margin, so fold the existing trees'
        # contribution in before computing gradients (reference PredictRaw
        # with the version cache, src/gbm/gbtree.cc:506-544)
        total = self.gbm.version()
        if state["n_trees"] < total:
            with obs_trace.span("train/bootstrap", "train",
                                {"iteration": iteration,
                                 "trees": total - state["n_trees"]}):
                if self.gbm.supports_margin_cache:
                    # raw-threshold walk, NOT the binned fast path: loaded
                    # trees may have been grown against different quantile
                    # cuts, so their split_bin indices are meaningless here
                    # (same reason the eval path falls back to raw for
                    # loaded models)
                    delta = self.gbm.margin_delta_raw(
                        np.asarray(state["dm"].values()), state["n_trees"],
                        total)
                    state["margin"] = state["margin"] + jnp.asarray(delta)
                else:
                    state["margin"] = self.gbm.compute_margin(state)
            state["n_trees"] = total
        if fobj is None and self._fused_step(state, iteration):
            if obs_memory.enabled():
                self._mem_round(state)
            return
        margin = self.gbm.training_margin(state)
        with obs_trace.span("round/gradient", "train",
                            self._gradient_span_args(state, iteration)), \
                self._monitor.section("GetGradient"):
            if fobj is None:
                if self._is_vertical_federated():
                    # margins replicate across parties, labels do not: the
                    # label rank computes and broadcasts (reference
                    # ApplyWithLabels in ObjFunction::GetGradient,
                    # src/collective/aggregator.h:36)
                    from .parallel.collective import apply_with_labels

                    gpair = jnp.asarray(apply_with_labels(
                        lambda: np.asarray(self.obj.get_gradient(
                            margin, state["info"], iteration), np.float32)))
                elif (getattr(state["dm"], "presharded", False)
                      and getattr(state["dm"], "local_group_ptr", None)
                      is not None):
                    # sharded ingestion with ranking groups: the global
                    # device_info carries no group structure; groups are
                    # whole per process (train_per_host contract), so the
                    # gradient is computed shard-locally and re-assembled
                    # mesh-sharded (ShardedDMatrix.local_gradient)
                    gpair = state["dm"].local_gradient(self.obj, margin,
                                                       iteration)
                else:
                    gpair = self.obj.get_gradient(margin, state["info"],
                                                  iteration)
            else:
                grad, hess = fobj(np.asarray(margin).squeeze(), dtrain)
                gpair = jnp.stack(
                    [jnp.asarray(grad, dtype=jnp.float32).reshape(
                        margin.shape),
                     jnp.asarray(hess, dtype=jnp.float32).reshape(
                         margin.shape)], axis=-1)
                from .objective.base import guard_gradient

                gpair = guard_gradient(gpair, "custom objective", iteration)
        if observer.enabled():
            observer.observe("gpair", gpair, iteration)
        key = self.ctx.make_key(iteration)
        _prior_trees = len(getattr(self.gbm, "_trees", ()))
        # the unfused path has its span too, so a degrade shows in a trace
        with obs_trace.span("round/general", "train",
                            {"iteration": iteration}), \
                self._monitor.section("BoostOneIter"):
            delta = self.gbm.do_boost(state, gpair, iteration,
                                      jax.random.fold_in(key, iteration),
                                      obj=self.obj, margin=margin)
        count_round_dispatch("general")
        with self._monitor.section("UpdateCache"):
            if self.gbm.supports_margin_cache:
                state["margin"] = _add_margin_delta(state["margin"], delta)
            else:
                state["margin"] = self.gbm.compute_margin(state)
        if observer.enabled():
            observer.observe("margin", state["margin"], iteration)
        state["n_trees"] = self.gbm.version()
        self._note_host_round(iteration, _prior_trees)
        if obs_memory.enabled():
            self._mem_round(state)

    def _gradient_span_args(self, state: Dict[str, Any],
                            iteration: int) -> Dict[str, Any]:
        """Args of the general path's ``round/gradient`` span: the objective,
        the query groups it works over (0: none), and for a ranking
        objective what its layout's content key cost at its LAST call
        (``layout_key_ms``: the span opens before this call's is known)."""
        ptr = getattr(state["info"], "group_ptr", None)
        args = {"iteration": iteration, "objective": self.obj.name,
                "groups": 0 if ptr is None else len(ptr) - 1}
        key_ms = getattr(self.obj, "layout_key_ms", None)
        if key_ms is not None:
            args["layout_key_ms"] = round(key_ms, 3)
        return args

    def _mem_round(self, state: Dict[str, Any]) -> None:
        """HBM-accounting round boundary (callers gate on
        ``obs_memory.enabled()`` so the default path stays free): book the
        donated margin carry explicitly — allocator-less backends cannot
        see it — then sample the watermark and close the round window."""
        margin = state.get("margin")
        if margin is not None and hasattr(margin, "nbytes"):
            obs_memory.book("carry/margin", int(margin.nbytes))
        obs_memory.sample("round")
        obs_memory.note_round()

    def _fused_step(self, state: Dict[str, Any], iteration: int) -> bool:
        """One whole boosting round as a SINGLE jitted dispatch (gradient ->
        grow -> margin update): the common single-target hist case fuses
        the per-round op chain. Returns False when the configuration needs the
        general path; numerics and PRNG key derivation replicate do_boost
        exactly, so fused and unfused runs produce identical models."""
        binding = self._fused_binding(state)
        if binding is None:
            return False
        obj_params, grower, labels, weights, n_real = binding
        binned = state["binned"]
        gbm = self.gbm
        from .boosting.gbtree import _PendingTree
        from .obs import insight as obs_insight

        # xtpuinsight arm: same round, telemetry (+ optional in-carry eval)
        # as extra outputs of the one dispatch. One module predicate when
        # disarmed — the hot path stays free.
        ins = None
        if obs_insight.enabled() and not self._insight_blocked:
            ins = self._insight_binding(state, obj_params)
        if ins is not None:
            try:
                with obs_trace.span("round/fused", "train",
                                    {"iteration": iteration}):
                    (new_margin, grown, telem, new_ems,
                     partials) = _fused_round_insight_fn(
                        binned.bins, state["margin"], labels, weights,
                        n_real, self.ctx.raw_seed(iteration),
                        np.int32(iteration), grower.monotone,
                        grower.constraint_sets, grower.cat,
                        ins["bins"], ins["margins"], ins["labels"],
                        ins["weights"],
                        obj_cls=type(self.obj), obj_params=obj_params,
                        param=grower.param, max_nbins=grower.max_nbins,
                        hist_method=grower.hist_method,
                        has_missing=grower.has_missing,
                        nan_policy=_nan_policy(),
                        eval_specs=ins["specs"],
                        eval_missing=ins["missing"])
            except Exception:
                # insight-only failure: disarm and retry THIS round on the
                # unarmed fused path — the model math is unaffected, so
                # blocking fused entirely would punish the wrong tier
                logger.warning("insight-armed fused round failed; "
                               "disarming telemetry and retrying unarmed",
                               exc_info=True)
                self._insight_blocked = True
                self._insight_state = None
                count_degrade("insight_disarm")
                self._recover_donated_margin(state)
                return self._fused_step(state, iteration)
            count_round_dispatch("_fused_round_insight_fn")
            # the guard reduction doubles as the NaN-guard telemetry
            # counter — still exactly the budgeted 2 dispatches per round
            bad = _margin_bad_rows(new_margin, state["n_valid"])
            _check_margin_finite(new_margin, state["n_valid"],
                                 self.obj.name, iteration, bad=bad)
            if isinstance(grown, dict):
                for k in range(gbm.n_groups):
                    gbm._trees.append(
                        _PendingTree(None, grower, arrays=grown, index=k))
                    gbm.tree_info.append(k)
            else:
                gbm._trees.append(_PendingTree(grown, grower))
                gbm.tree_info.append(0)
            gbm.iteration_indptr.append(len(gbm._trees))
            state["margin"] = new_margin
            state["n_trees"] = gbm.version()
            self._note_insight_round(ins, iteration, telem, new_ems,
                                     partials, bad)
            return True

        # hot path: with no profiler session the span is one inert
        # annotation (tests/test_obs.py bounds its cost). An error of this
        # program propagates: a general-path round standing in for it
        # would go unnoticed.
        with obs_trace.span("round/fused", "train",
                            {"iteration": iteration}):
            new_margin, grown = _fused_round_fn(
                binned.bins, state["margin"], labels, weights, n_real,
                self.ctx.raw_seed(iteration), np.int32(iteration),
                grower.monotone, grower.constraint_sets, grower.cat,
                obj_cls=type(self.obj), obj_params=obj_params,
                param=grower.param, max_nbins=grower.max_nbins,
                hist_method=grower.hist_method,
                has_missing=grower.has_missing,
                nan_policy=_nan_policy())
        count_round_dispatch("_fused_round_fn")
        _check_margin_finite(new_margin, state["n_valid"], self.obj.name,
                             iteration)
        if isinstance(grown, dict):     # multiclass: stacked [K] class axis
            for k in range(gbm.n_groups):
                gbm._trees.append(
                    _PendingTree(None, grower, arrays=grown, index=k))
                gbm.tree_info.append(k)
        else:
            gbm._trees.append(_PendingTree(grown, grower))
            gbm.tree_info.append(0)
        gbm.iteration_indptr.append(len(gbm._trees))
        state["margin"] = new_margin
        state["n_trees"] = gbm.version()
        return True

    def _recover_donated_margin(self, state: Dict[str, Any]) -> None:
        """The fused fns donate the margin buffer; a failure DURING execution
        (not tracing) may have consumed it. The un-committed round's margin
        equals base + committed trees, so rebuild it before the general path
        touches it. The rebuild walks RAW thresholds when possible:
        continuation-loaded trees may have been grown under different
        quantile cuts, making their split_bin ids meaningless against this
        binned matrix (same reason update() folds old trees via
        margin_delta_raw)."""
        m = state.get("margin")
        if m is None or not getattr(m, "is_deleted", lambda: False)():
            return
        dm = state.get("dm")
        if getattr(dm, "X", None) is not None and hasattr(
                self.gbm, "margin_delta_raw"):
            delta = self.gbm.margin_delta_raw(np.asarray(dm.X), 0,
                                              self.gbm.version())
            state["margin"] = state["base"] + jnp.asarray(delta)
        else:
            state["margin"] = self.gbm.compute_margin(state)

    def _fused_binding(self, state: Dict[str, Any]):
        """Eligibility + cache binding shared by the single-round and the
        round-batched fused paths; None -> use the general path."""
        gbm = self.gbm
        if (self._fused_blocked or type(gbm) is not GBTree
                or not gbm.supports_margin_cache
                or gbm.tree_method in ("approx", "exact")
                or gbm.num_parallel_tree != 1
                or getattr(gbm, "multi_strategy",
                           "one_output_per_tree") != "one_output_per_tree"
                or gbm.split_mode != "row"
                or self.tree_param.grow_policy != "depthwise"
                or self.tree_param.max_leaves > 0
                or hasattr(self.obj, "update_tree_leaf")
                or state.get("binned") is None
                or getattr(state.get("binned"), "is_paged", False)
                or self.ctx.mesh is not None
                or observer.enabled()
                # XTPU_SCAN_CLASSES=0 opts out of the class-scanned grow
                # everywhere — multiclass must then take the sequential
                # general path, not the (also scanned) fused branch
                or (gbm.n_groups > 1 and os.environ.get(
                    "XTPU_SCAN_CLASSES", "1") == "0")):
            return None
        from .objective.base import Objective

        # custom get_gradient overrides may be host-side or
        # iteration-dependent (lambdarank pair sampling) — general path
        if type(self.obj).get_gradient is not Objective.get_gradient:
            return None
        # the fused fns DONATE the margin buffer; a fresh cache's margin
        # aliases state["base"] (same array), which process_type=update and
        # continuation restarts still need — unalias before first donation
        if state["margin"] is state["base"]:
            state["margin"] = jnp.array(state["margin"], copy=True)
        binned = state["binned"]
        if self._fused_round is None or self._fused_round[0] is not state:
            # (re)bind to THIS training cache — a different dtrain gets
            # fresh labels/weights/bins; set_param resets this cache too
            scalars = {k: v for k, v in self.obj.params.items()
                       if k != "eval_metric"}  # metric list: not a gradient
                       # input, never read by any objective
            if not all(isinstance(v, (int, float, str, bool))
                       for v in scalars.values()):
                self._fused_blocked = True  # non-scalar objective params
                return None                 # can't be static jit args
            obj_params = tuple(sorted(scalars.items()))
            with obs_trace.phase("train/state", "train"):
                grower = gbm._grower_for(binned)
                info = state["info"]
                dev = getattr(info, "labels_device", None)
                wdev = getattr(info, "weights_device", None)
                self._fused_round = (
                    state, obj_params, grower,
                    dev() if dev is not None
                    else jnp.asarray(info.labels, jnp.float32),
                    ((wdev() if wdev is not None
                      else jnp.asarray(info.weights, jnp.float32))
                     if info.weights is not None else None),
                    binned.n_real_bins())
        return self._fused_round[1:]

    def _insight_binding(self, state: Dict[str, Any],
                         obj_params) -> Dict[str, Any]:
        """Arm (or cache-hit) the insight carry for one fused round:
        telemetry always; the in-carry eval only when EVERY armed eval
        DMatrix qualifies (binned against the train cuts, resident,
        fully-addressable unpadded margin, labels present) and every
        configured metric has an in-trace twin — otherwise eval stays on
        the host path and only telemetry rides the carry. The eval margins
        are COPIES of the version-cache margins (the round program donates
        them), re-bound to the program's outputs every committed round."""
        from .obs import insight as obs_insight

        st = self._insight_state
        if (st is not None and st["state"] is state
                and st["version"] == self.gbm.version()):
            return st
        st = {"state": state, "version": self.gbm.version(),
              "bins": (), "margins": (), "labels": (), "weights": (),
              "missing": (), "specs": (), "names": (), "infos": ()}
        self._insight_state = st
        evals = self._insight_evals
        if (not obs_insight.eval_enabled() or not evals
                or self.n_groups != 1 or not self._eval_metrics):
            return st
        specs = obs_insight.metric_specs(self._eval_metrics)
        if specs is None:
            return st
        bins, margins, labels, weights = [], [], [], []
        missing, names, infos = [], [], []
        for dm, name in evals:
            est = self._state_of(dm, is_train=(dm is state.get("dm")))
            eb = est.get("binned")
            if (eb is None or getattr(eb, "is_paged", False)
                    or not hasattr(eb, "missing_bin")):
                return st
            m0 = self._cached_margin(dm)
            y = dm.info.labels
            n = dm.num_row()
            if (y is None or len(y) != n
                    or getattr(eb.bins, "shape", (0,))[0] != n
                    or getattr(m0, "shape", (0,))[0] != n
                    or (isinstance(m0, jax.Array)
                        and not m0.is_fully_addressable)):
                return st
            w = dm.info.weights
            bins.append(eb.bins)
            margins.append(jnp.array(m0, copy=True))  # donated per round
            labels.append(jnp.asarray(y, jnp.float32))
            weights.append(jnp.asarray(w, jnp.float32)
                           if w is not None else None)
            missing.append(int(eb.missing_bin))
            names.append(name)
            infos.append(dm.info)
        st.update(bins=tuple(bins), margins=tuple(margins),
                  labels=tuple(labels), weights=tuple(weights),
                  missing=tuple(missing), specs=specs,
                  names=tuple(names), infos=tuple(infos))
        return st

    def _note_insight_round(self, ins: Dict[str, Any], iteration: int,
                            telem, new_ems, partials, bad) -> None:
        """Land one armed round: ONE host fetch for the round's telemetry
        scalars + eval partials (the per-round pull the unarmed raise-policy
        guard already does), logged into the TrainingLog; the eval carry
        re-binds to the program's output margins. ``eval_set`` then serves
        this round's scores from ``_insight_scores`` without predicting."""
        from .obs import insight as obs_insight

        host_telem, host_partials, host_bad = jax.device_get(
            (telem, partials, bad))
        scalars = dict(host_telem)
        scalars["nan_guard_bad_rows"] = int(host_bad)
        log = self.training_log
        if log is None:
            log = self.training_log = obs_insight.TrainingLog()
        log.log_round(iteration, scalars)
        ins["margins"] = new_ems
        ins["version"] = self.gbm.version()
        if not ins["names"]:
            self._insight_scores = None
            return
        scores: Dict[Tuple[str, str], float] = {}
        for di, name in enumerate(ins["names"]):
            info = ins["infos"][di]
            for mi, metric in enumerate(self._eval_metrics):
                num, den = host_partials[di][mi]
                scores[(name, metric.full_name)] = \
                    obs_insight.finalize_partial(ins["specs"][mi][0],
                                                 num, den, info)
        self._insight_scores = {"iteration": int(iteration),
                                "names": tuple(ins["names"]),
                                "scores": scores}

    def _note_host_round(self, iteration: int, prior_trees: int) -> None:
        """General/lossguide/paged/mesh telemetry twin of
        ``_note_insight_round``: derive the round's learning-health scalars
        host-side from the trees this round committed (obs/insight.py
        ``round_telemetry_host`` — the node arrays were coming to the host
        anyway, so this is zero extra dispatches on every tier). One module
        predicate when disarmed."""
        from .obs import insight as obs_insight

        if not obs_insight.enabled():
            return
        entries = getattr(self.gbm, "_trees", None)
        if entries is None or len(entries) <= prior_trees:
            return
        try:
            scalars = obs_insight.round_telemetry_host(entries[prior_trees:])
        except Exception:   # telemetry must never break training
            logger.warning("host round telemetry failed", exc_info=True)
            return
        if scalars is None:
            return
        if self.training_log is None:
            self.training_log = obs_insight.TrainingLog()
        self.training_log.log_round(iteration, scalars)

    def update_batch(self, dtrain: DMatrix, iterations: Sequence[int]) -> bool:
        """Run ``len(iterations)`` fused boosting rounds as ONE device
        dispatch (lax.scan over the fused round — numerics identical to
        sequential ``update`` calls). Only valid when nothing consumes
        per-round output (no evals/callbacks); the train() loop uses it
        automatically in that case. Returns False when the configuration
        needs the per-round path — the caller falls back to ``update``."""
        self._configure(dtrain)
        if self.tree_param.process_type == "update":
            return False
        state = self._state_of(dtrain, is_train=True)
        if state["n_trees"] < self.gbm.version():
            return False  # continuation bootstrap: update() folds old trees
        binding = self._fused_binding(state)
        if binding is None:
            return False
        obj_params, grower, labels, weights, n_real = binding
        binned = state["binned"]
        gbm = self.gbm
        from .boosting.gbtree import _PendingTree

        seeds = np.asarray([self.ctx.raw_seed(i) for i in iterations],
                           np.uint32)
        iters = np.asarray(list(iterations), np.int32)
        with obs_trace.span("round/batch", "train",
                            {"iteration": int(iters[0]),
                             "rounds": len(iters)}):
            new_margin, growns = _fused_multi_round_fn(
                binned.bins, state["margin"], labels, weights, n_real,
                seeds, iters,
                grower.monotone, grower.constraint_sets, grower.cat,
                obj_cls=type(self.obj), obj_params=obj_params,
                param=grower.param, max_nbins=grower.max_nbins,
                hist_method=grower.hist_method,
                has_missing=grower.has_missing,
                nan_policy=_nan_policy())
        count_round_dispatch("_fused_multi_round_fn", len(iters))
        _check_margin_finite(new_margin, state["n_valid"], self.obj.name,
                             int(iters[0]), len(iters))
        # all R x Kc trees share ONE stacked-array dict; _flush fetches it
        # once and slices host-side (multiclass axes arrive pre-flattened
        # to [R * Kc] by _fused_multi_round_fn)
        stacked = growns
        Kc = gbm.n_groups
        for r in range(len(iters)):
            for k in range(Kc):
                gbm._trees.append(
                    _PendingTree(None, grower, arrays=stacked,
                                 index=r * Kc + k))
                gbm.tree_info.append(k)
            gbm.iteration_indptr.append(len(gbm._trees))
        state["margin"] = new_margin
        state["n_trees"] = gbm.version()
        return True

    def _update_existing_trees(self, dtrain: DMatrix,
                               fobj: Optional[Callable] = None) -> None:
        """``process_type=update`` (reference ``src/gbm/gbtree.cc:115,312-327``):
        on the first boost the model's trees move into a ``trees_to_update``
        queue and the committed model restarts empty; each call pops the next
        iteration's trees, re-processes them with the configured updater
        sequence (refresh / prune / sync) against gradients of the *partial*
        committed margin, and commits them back."""
        from .tree.updaters import prune_tree, refresh_tree, sync_trees

        if not hasattr(self, "_trees_to_update"):
            self._trees_to_update = (
                list(self.gbm.trees), list(self.gbm.tree_info),
                list(self.gbm.iteration_indptr))
            self.gbm.trees = []
            self.gbm.tree_info = []
            self.gbm.iteration_indptr = [0]
            for st in self._caches.values():
                st["margin"] = st["base"]
                st["n_trees"] = 0
        from .tree.multi import MultiTargetTreeModel

        old_trees, old_info, old_indptr = self._trees_to_update
        if old_trees and isinstance(old_trees[0], MultiTargetTreeModel):
            raise NotImplementedError(
                "process_type=update does not support multi_output_tree "
                "models")
        it = self.gbm.num_boosted_rounds()
        if it >= len(old_indptr) - 1:
            raise ValueError(
                "process_type=update: no more trees to update "
                f"(model has {len(old_indptr) - 1} iterations)")
        updaters = [u.strip() for u in str(self.learner_params.get(
            "updater", "refresh")).split(",") if u.strip()]
        refresh_leaf = bool(self.tree_param.refresh_leaf)
        state = self._state_of(dtrain, is_train=True)
        total = self.gbm.version()
        if state["n_trees"] == total and self.gbm.supports_margin_cache:
            margin = state["margin"]
        elif (self.gbm.supports_margin_cache and state["binned"] is not None
              and state["n_trees"] < total):
            from .boosting.gbtree import match_rows

            margin = state["margin"] + match_rows(
                self.gbm.margin_delta_binned(
                    state["binned"], state["n_trees"], total),
                state["margin"].shape[0])
        else:
            margin = self.gbm.compute_margin(state)
        state["margin"] = margin
        state["n_trees"] = total
        if fobj is None:
            gpair = np.asarray(self.obj.get_gradient(
                margin, state["info"], it))
        else:
            grad, hess = fobj(np.asarray(margin).squeeze(), dtrain)
            gpair = np.stack(
                [np.asarray(grad, np.float32).reshape(margin.shape),
                 np.asarray(hess, np.float32).reshape(margin.shape)], axis=-1)
        if gpair.ndim == 2:
            gpair = gpair[:, None, :]
        n = dtrain.num_row()
        X = np.asarray(dtrain.values(), np.float32)
        for t_idx in range(old_indptr[it], old_indptr[it + 1]):
            tree = old_trees[t_idx]
            k = old_info[t_idx]
            for up in updaters:
                if up == "refresh":
                    tree = refresh_tree(tree, X, gpair[:n, k, :],
                                        self.tree_param,
                                        refresh_leaf=refresh_leaf)
                elif up == "prune":
                    tree = prune_tree(tree, self.tree_param)
                elif up == "sync":
                    tree = sync_trees([tree])[0]
                else:
                    raise ValueError(f"unknown updater '{up}' for "
                                     "process_type=update")
            self.gbm.trees.append(tree)
            self.gbm.tree_info.append(k)
        self.gbm.iteration_indptr.append(len(self.gbm.trees))
        # refreshed trees carry NEW leaf values at existing indices — any
        # per-tree cache keyed by tree index (dart's delta ring / margin
        # cache) is stale now
        self.gbm._stat_version += 1
        # committed trees are immutable once appended; the incremental margin
        # cache walks only the newly committed trees on the next predict

    def boost(self, dtrain: DMatrix, grad: np.ndarray, hess: np.ndarray) -> None:
        """Boost with externally computed gradients (reference Booster.boost)."""
        self._configure(dtrain)
        state = self._state_of(dtrain, is_train=True)
        margin = state["margin"]
        gpair = jnp.stack(
            [jnp.asarray(grad, dtype=jnp.float32).reshape(margin.shape),
             jnp.asarray(hess, dtype=jnp.float32).reshape(margin.shape)],
            axis=-1)
        it = self.num_boosted_rounds()
        delta = self.gbm.do_boost(state, gpair, it,
                                  jax.random.fold_in(self.ctx.make_key(it), it))
        if self.gbm.supports_margin_cache:
            state["margin"] = state["margin"] + delta
        else:
            state["margin"] = self.gbm.compute_margin(state)
        state["n_trees"] = self.gbm.version()

    # -------------------------------------------------------------- prediction
    def _cached_margin(self, dm: DMatrix) -> jnp.ndarray:
        """Margin with the version-cache trick: walk only trees added since
        the cache entry was last touched, on the quantized matrix. Boosters
        whose old-tree contributions change over time (DART scaling, linear
        weights) recompute from scratch instead."""
        self._configure(dm)
        state = self._state_of(dm, is_train=False)
        total = self.gbm.version()
        if state["n_trees"] == total:
            return state["margin"]
        if self._is_vertical_federated() and type(self.gbm) is GBTree:
            # no party's local columns can walk the full forest — the
            # incremental delta goes through the decision-bit protocol
            state["margin"] = state["margin"] + jnp.asarray(
                self._vertical_margin_delta(dm, state["n_trees"], total))
        elif not self.gbm.supports_margin_cache:
            state["margin"] = self.gbm.compute_margin(state)
        elif state["binned"] is not None:
            from .boosting.gbtree import match_rows

            state["margin"] = state["margin"] + match_rows(
                self.gbm.margin_delta_binned(
                    state["binned"], state["n_trees"], total),
                state["margin"].shape[0])
        else:
            state["margin"] = state["margin"] + self.gbm.margin_delta_raw(
                dm.values(), state["n_trees"], total)
        state["n_trees"] = total
        return state["margin"]

    def _vertical_margin_delta(self, dm: DMatrix, tree_lo: int,
                               tree_hi: int) -> np.ndarray:
        """Margin contribution of trees [lo, hi) on a vertically partitioned
        DMatrix via the decision-bit protocol (tree/vertical.py)."""
        from .parallel import collective
        from .tree.vertical import federated_vertical_margin

        comm = collective.get_communicator()
        g = getattr(self.gbm, "_grower", None)
        if g is not None and getattr(g, "f_offset", None) is not None:
            offset = g.f_offset
        else:  # loaded model: derive the block offset from column widths
            widths = comm.allgather_objects(int(dm.num_col()))
            offset = int(sum(widths[: comm.get_rank()]))
        w = self.gbm.tree_weights()
        return federated_vertical_margin(
            self.gbm.trees[tree_lo:tree_hi],
            self.gbm.tree_info[tree_lo:tree_hi], self.n_groups,
            np.asarray(dm.values(), np.float32), offset, comm,
            tree_weights=None if w is None else w[tree_lo:tree_hi])

    def _validate_features(self, data: DMatrix) -> None:
        """Shape/name agreement between model and data (reference
        ``Booster._validate_features``, core.py)."""
        nf = self.num_features()
        if nf and data.num_col() != nf:
            raise ValueError(
                f"feature count mismatch: model has {nf}, data has "
                f"{data.num_col()}")
        names = data.info.feature_names
        if self.feature_names and names and self.feature_names != names:
            missing = set(self.feature_names) - set(names)
            extra = set(names) - set(self.feature_names)
            raise ValueError(
                "feature_names mismatch between model and data"
                + (f"; missing from data: {sorted(missing)}" if missing
                   else "")
                + (f"; unexpected in data: {sorted(extra)}" if extra else ""))

    def predict(self, data: DMatrix, output_margin: bool = False,
                pred_leaf: bool = False, pred_contribs: bool = False,
                approx_contribs: bool = False,
                pred_interactions: bool = False,
                iteration_range: Optional[Tuple[int, int]] = None,
                strict_shape: bool = False, training: bool = False,
                validate_features: bool = True) -> np.ndarray:
        self._configure(data if data.info.labels is not None else None)
        if validate_features:
            self._validate_features(data)
        if pred_contribs or pred_interactions:
            from .tree.multi import MultiTargetTreeModel

            first = self.gbm.trees[0] if getattr(
                self.gbm, "trees", None) else None
            if isinstance(first, MultiTargetTreeModel):
                raise NotImplementedError(
                    "SHAP contributions are not supported for "
                    "multi_output_tree models")
            if self._is_vertical_federated():
                raise NotImplementedError(
                    "SHAP contributions are not available under vertical "
                    "federated column split (no party sees all features)")
            return self._predict_contribs(
                data, approx=approx_contribs, interactions=pred_interactions,
                iteration_range=iteration_range, strict_shape=strict_shape)
        if self._is_vertical_federated() and type(self.gbm) is GBTree:
            # decision-bit protocol: every split is resolvable by exactly
            # one party; one OR-allreduce completes the routing
            if pred_leaf:
                raise NotImplementedError(
                    "pred_leaf is not available under vertical federated "
                    "column split")
            lo_t, hi_t = self.gbm._tree_range(iteration_range)
            margin = self._vertical_margin_delta(data, lo_t, hi_t)
            base = self._base_np()
            if data.info.base_margin is not None:
                margin = margin + np.asarray(
                    data.info.base_margin, np.float32).reshape(
                        margin.shape[0], -1)
            else:
                margin = margin + base[None, :]
            out = margin if output_margin else np.asarray(
                self.obj.pred_transform(jnp.asarray(margin)))
            if not strict_shape and out.ndim == 2 and out.shape[1] == 1:
                out = out[:, 0]
            return out
        X = data.values()
        base = self._base_np()
        m, pos, trees = self.gbm.predict_margin(
            X, np.zeros(self.n_groups, np.float32),
            iteration_range=iteration_range)
        margin = np.asarray(m)
        if data.info.base_margin is not None:
            base_rows = np.asarray(data.info.base_margin, np.float32)
            margin = margin + base_rows.reshape(margin.shape[0], -1)
        else:
            margin = margin + base[None, :]
        if pred_leaf:
            if pos is None:
                return np.zeros((data.num_row(), 0), dtype=np.int32)
            # predictor positions are already compact BFS node ids
            return np.asarray(pos, dtype=np.int32)
        out = margin if output_margin else np.asarray(
            self.obj.pred_transform(jnp.asarray(margin)))
        if not strict_shape and out.ndim == 2 and out.shape[1] == 1:
            out = out[:, 0]
        return out

    def _predict_contribs(self, data: DMatrix, approx: bool,
                          interactions: bool, iteration_range, strict_shape):
        """SHAP/Saabas feature contributions (reference
        ``PredictContribution`` / ``PredictInteractionContributions``)."""
        from .boosting import shap as shap_mod
        from .boosting.gblinear import GBLinear

        X = np.asarray(data.values(), np.float32)
        n, F = X.shape
        base = self._base_np()
        if isinstance(self.gbm, GBLinear):
            if interactions:
                raise ValueError(
                    "pred_interactions is not defined for gblinear")
            W = np.asarray(self.gbm.W)              # [F, K]
            b = np.asarray(self.gbm.bias)           # [K]
            out = np.zeros((n, self.n_groups, F + 1), np.float64)
            Xz = np.nan_to_num(X)
            out[:, :, :F] = (Xz[:, None, :] * W.T[None, :, :])
            out[:, :, F] = b[None, :] + np.asarray(base)[None, :]
        else:
            trees, info, weights = self.gbm.forest_slice(iteration_range)
            if interactions:
                if approx:
                    raise NotImplementedError(
                        "approx_contribs with pred_interactions is not "
                        "supported; use exact interactions")
                out = shap_mod.shap_interactions(X, trees, info,
                                                 self.n_groups, base, weights)
            elif approx:
                out = shap_mod.approx_contribs(X, trees, info, self.n_groups,
                                               base, weights)
            else:
                out = shap_mod.tree_shap(X, trees, info, self.n_groups, base,
                                         weights)
        if not strict_shape and self.n_groups == 1:
            out = out[:, 0]
        return out.astype(np.float32)

    def inplace_predict(self, data: Any, iteration_range=None,
                        predict_type: str = "value", missing: float = np.nan,
                        base_margin: Any = None, strict_shape: bool = False
                        ) -> np.ndarray:
        """Predict straight from a raw array (reference InplacePredict path —
        no DMatrix quantization needed since raw prediction walks raw
        thresholds anyway)."""
        dm = DMatrix(data, missing=missing, base_margin=base_margin)
        return self.predict(dm, output_margin=(predict_type == "margin"),
                            iteration_range=iteration_range,
                            strict_shape=strict_shape)

    # ------------------------------------------------------------------- eval
    def eval(self, data: DMatrix, name: str = "eval",
             iteration: int = 0) -> str:
        """Evaluate one DMatrix (reference ``Booster.eval``)."""
        return self.eval_set([(data, name)], iteration)

    def eval_set(self, evals: Sequence[Tuple[DMatrix, str]], iteration: int = 0,
                 feval: Optional[Callable] = None,
                 output_margin: bool = True) -> str:
        """Evaluate on a list of (DMatrix, name); returns the reference-format
        line ``[i]\\tname-metric:value...`` (``src/learner.cc:1307-1342``)."""
        with obs_trace.span("round/eval", "train", {"iteration": iteration}):
            return self._eval_set(evals, iteration, feval, output_margin)

    def _eval_set(self, evals, iteration, feval, output_margin) -> str:
        """``eval_set`` under its span.

        Three tiers, cheapest first: (1) scores the insight-armed fused
        round already computed IN-CARRY for this iteration (obs/insight.py
        — zero predicts, zero dispatches); (2) one jitted partials program
        covering every (DMatrix, metric) pair at once
        (``_eval_partials_fn`` — the old path host-round-tripped per pair);
        (3) the host loop, kept for custom/unsupported metrics, ``feval``,
        vertical federated, and mesh-global margins."""
        self._configure(None)
        vfed = self._is_vertical_federated()
        if feval is None and not vfed:
            ins = self._insight_scores
            if (ins is not None and ins["iteration"] == iteration
                    and tuple(n for _, n in evals) == ins["names"]):
                msg = f"[{iteration}]"
                for _, name in evals:
                    for metric in self._eval_metrics:
                        score = ins["scores"][(name, metric.full_name)]
                        msg += f"\t{name}-{metric.full_name}:{score:.6f}"
                return msg
            scores = self._batched_eval_scores(evals, iteration)
            if scores is not None:
                msg = f"[{iteration}]"
                for _, name in evals:
                    for metric in self._eval_metrics:
                        score = scores[(name, metric.full_name)]
                        msg += f"\t{name}-{metric.full_name}:{score:.6f}"
                return msg
        msg = f"[{iteration}]"
        for dm, name in evals:
            margin = self._cached_margin(dm)
            preds = self.obj.pred_transform(margin)
            preds_np = self._host_rows(preds, dm)
            if preds_np.ndim == 2 and preds_np.shape[1] == 1:
                preds_np = preds_np[:, 0]
            for metric in self._eval_metrics:
                if vfed:
                    # predictions replicate, labels/weights live only on
                    # the label rank (reference ApplyWithLabels around
                    # Metric::Evaluate under vertical federated)
                    from .parallel.collective import apply_with_labels

                    score = apply_with_labels(
                        lambda m=metric: float(m(preds_np, dm.info)))
                else:
                    score = metric(preds_np, dm.info)
                msg += f"\t{name}-{metric.full_name}:{score:.6f}"
            if feval is not None:
                margin_np = self._host_rows(margin, dm)
                if margin_np.ndim == 2 and margin_np.shape[1] == 1:
                    margin_np = margin_np[:, 0]

                def _feval():
                    res = feval(margin_np if output_margin else preds_np, dm)
                    return res if isinstance(res, list) else [res]

                if vfed:
                    from .parallel.collective import apply_with_labels

                    pairs = apply_with_labels(
                        lambda: [(str(k), float(v)) for k, v in _feval()])
                else:
                    pairs = _feval()
                for mname, val in pairs:
                    msg += f"\t{name}-{mname}:{val:.6f}"
        return msg

    def _batched_eval_scores(self, evals: Sequence[Tuple[DMatrix, str]],
                             iteration: int
                             ) -> Optional[Dict[Tuple[str, str], float]]:
        """Score every (DMatrix, metric) pair through ONE
        ``_eval_partials_fn`` dispatch; None -> caller uses the host loop.
        Labels/weights are device-cached on the DMatrix's cache entry so
        steady rounds re-upload nothing."""
        from .obs import insight as obs_insight

        if self.n_groups != 1 or not self._eval_metrics or not evals:
            return None
        specs = obs_insight.metric_specs(self._eval_metrics)
        if specs is None:
            return None
        scalars = {k: v for k, v in self.obj.params.items()
                   if k != "eval_metric"}
        if not all(isinstance(v, (int, float, str, bool))
                   for v in scalars.values()):
            return None
        obj_params = tuple(sorted(scalars.items()))
        margins, labels, weights, rows = [], [], [], []
        for dm, _name in evals:
            m = self._cached_margin(dm)
            y = dm.info.labels
            n = dm.num_row()
            if (y is None or len(y) != n
                    or getattr(m, "shape", (0,))[0] < n
                    or (isinstance(m, jax.Array)
                        and not m.is_fully_addressable)):
                return None
            st = self._caches.get(id(dm))
            if st is None:
                return None
            ydev = st.get("eval_labels_dev")
            if ydev is None or ydev.shape[0] != n:
                ydev = st["eval_labels_dev"] = jnp.asarray(y, jnp.float32)
            w = dm.info.weights
            wdev = None
            if w is not None:
                if len(w) != n:
                    return None
                wdev = st.get("eval_weights_dev")
                if wdev is None or wdev.shape[0] != n:
                    wdev = st["eval_weights_dev"] = jnp.asarray(
                        w, jnp.float32)
            margins.append(m)
            labels.append(ydev)
            weights.append(wdev)
            rows.append(int(n))
        parts = _eval_partials_fn(
            tuple(margins), tuple(labels), tuple(weights),
            obj_cls=type(self.obj), obj_params=obj_params,
            specs=specs, rows=tuple(rows))
        with obs_trace.span("round/eval/pull", "train",
                            {"iteration": iteration}):
            host = jax.device_get(parts)
        out: Dict[Tuple[str, str], float] = {}
        for di, (dm, name) in enumerate(evals):
            for mi, metric in enumerate(self._eval_metrics):
                num, den = host[di][mi]
                out[(name, metric.full_name)] = obs_insight.finalize_partial(
                    specs[mi][0], num, den, dm.info)
        return out

    @staticmethod
    def _host_rows(arr, dm) -> np.ndarray:
        """Host view of this process's valid rows. Fully-addressable arrays
        (single-controller) trim padding; mesh-global arrays from a
        ShardedDMatrix pull only the local shard."""
        if hasattr(dm, "local_rows") and isinstance(arr, jax.Array) \
                and not arr.is_fully_addressable:
            return dm.local_rows(arr)
        return np.asarray(arr)[: dm.num_row()]

    # -------------------------------------------------------------- attributes
    def attr(self, key: str) -> Optional[str]:
        return self.attributes_.get(key)

    def attributes(self) -> Dict[str, str]:
        return dict(self.attributes_)

    def set_attr(self, **kwargs: Any) -> None:
        for k, v in kwargs.items():
            if v is None:
                self.attributes_.pop(k, None)
            else:
                self.attributes_[k] = str(v)

    @property
    def best_iteration(self) -> int:
        b = self.attr("best_iteration")
        if b is None:
            return self.num_boosted_rounds() - 1
        return int(b)

    @property
    def best_score(self) -> float:
        return float(self.attr("best_score"))

    def num_boosted_rounds(self) -> int:
        return self.gbm.num_boosted_rounds() if self.gbm is not None else 0

    def num_features(self) -> int:
        if self.feature_names:
            return len(self.feature_names)
        return getattr(self, "_num_features", 0)

    # ---------------------------------------------------------------- slicing
    def __getitem__(self, val: slice) -> "Booster":
        if not isinstance(val, slice):
            raise TypeError("Booster slicing requires a slice of iterations")
        if not isinstance(self.gbm, GBTree):
            raise NotImplementedError("only tree boosters support slicing")
        begin = val.start or 0
        end = val.stop if val.stop is not None else self.num_boosted_rounds()
        step = val.step if val.step is not None else 1
        import copy
        new = copy.copy(self)
        new.gbm = GBTree(self.tree_param, self.n_groups,
                         num_parallel_tree=self.gbm.num_parallel_tree,
                         multi_strategy=getattr(self.gbm, "multi_strategy",
                                                "one_output_per_tree"))
        indptr = self.gbm.iteration_indptr
        new.gbm.trees = []
        new.gbm.tree_info = []
        new.gbm.iteration_indptr = [0]
        for it in range(begin, min(end, self.num_boosted_rounds()), step):
            lo, hi = indptr[it], indptr[it + 1]
            new.gbm.trees.extend(self.gbm.trees[lo:hi])
            new.gbm.tree_info.extend(self.gbm.tree_info[lo:hi])
            new.gbm.iteration_indptr.append(len(new.gbm.trees))
        new._caches = {}
        new.attributes_ = dict(self.attributes_)
        return new

    # ------------------------------------------------------------------- IO
    def save_model(self, fname: str) -> None:
        obj = self._model_to_json()
        if str(fname).endswith(".ubj"):
            from .utils.ubjson import dump_ubjson
            with open(fname, "wb") as fh:
                dump_ubjson(obj, fh)
        else:
            with open(fname, "w") as fh:
                json.dump(obj, fh)

    def save_raw(self, raw_format: str = "ubj") -> bytearray:
        obj = self._model_to_json()
        if raw_format == "json":
            return bytearray(json.dumps(obj).encode())
        from .utils.ubjson import dumps_ubjson
        return bytearray(dumps_ubjson(obj))

    @staticmethod
    def _reject_legacy_binary(head: bytes) -> None:
        # reference legacy "binf" binary models (src/learner.cc binary
        # path, deprecated there in 1.6 and removed semantics in 2.x):
        # not supported here — fail with a pointer instead of a JSON error
        if head.lstrip(b"\x00").startswith(b"binf") or head.startswith(
                b"bs64"):
            raise ValueError(
                "this is a legacy binary ('binf') XGBoost model; the "
                "deprecated pre-JSON format is not supported — re-save it "
                "as JSON/UBJSON with reference XGBoost >= 1.6 "
                "(booster.save_model('model.json')) and load that instead")

    def load_model(self, fname: Union[str, bytes, bytearray]) -> None:
        if isinstance(fname, (bytes, bytearray)):
            raw = bytes(fname)
            self._reject_legacy_binary(raw[:16])
            # a UBJSON object also begins with the byte '{' — sniff JSON
            # first, fall back to the binary codec
            try:
                obj = json.loads(raw.decode())
            except (UnicodeDecodeError, ValueError):
                from .utils.ubjson import loads_ubjson
                obj = loads_ubjson(raw)
        elif str(fname).endswith(".ubj"):
            from .utils.ubjson import load_ubjson
            with open(fname, "rb") as fh:
                obj = load_ubjson(fh)
        else:
            with open(fname, "rb") as fh:
                head = fh.read(16)
                self._reject_legacy_binary(head)
                fh.seek(0)
                obj = json.loads(fh.read().decode())
        self._model_from_json(obj)

    def _model_to_json(self) -> dict:
        self._configure(None)
        return {
            "version": list(_VERSION),
            "learner": {
                "attributes": dict(self.attributes_),
                "feature_names": self.feature_names or [],
                "feature_types": self.feature_types or [],
                "learner_model_param": {
                    "base_score": (self._base_np().tolist()
                                   if self.base_margin_ is not None else [0.0]),
                    "num_class": int(self.learner_params.get("num_class", 0)),
                    "num_target": self.n_groups,
                    "num_feature": self.num_features(),
                },
                "objective": self.obj.to_json() if self.obj else {},
                "gradient_booster": self.gbm.to_json() if self.gbm else {},
            },
            "config": {
                "tree_param": self.tree_param.to_json(),
                "learner_params": {k: v for k, v in self.learner_params.items()
                                   if _jsonable(v)},
            },
        }

    def _model_from_json(self, obj: dict) -> None:
        # a freshly loaded model invalidates any pending update queue
        # (reference re-queues trees_to_update on LoadModel, gbtree.cc:364)
        if hasattr(self, "_trees_to_update"):
            del self._trees_to_update
        from .interop import is_reference_model, reference_to_native_json

        if is_reference_model(obj):
            obj = reference_to_native_json(obj)
        learner = obj["learner"]
        cfg = obj.get("config", {})
        self.tree_param = TrainParam.from_dict(cfg.get("tree_param", {}))
        self.learner_params.update(cfg.get("learner_params", {}))
        if self.learner_params.get("data_split_mode", "row") == "col":
            # the split mode describes the TRAINING data layout, not the
            # model (in the reference it lives on the DMatrix) — a model
            # trained under column split must load for prediction in an
            # environment with no mesh or communicator; continuation
            # training re-specifies the mode with the new data
            from .parallel import collective

            if self.ctx.mesh is None and not collective.is_distributed():
                self.learner_params["data_split_mode"] = "row"
        self.attributes_ = dict(learner.get("attributes", {}))
        self.feature_names = learner.get("feature_names") or None
        self.feature_types = learner.get("feature_types") or None
        lmp = learner.get("learner_model_param", {})
        self._num_features = int(lmp.get("num_feature", 0) or 0)
        self.base_margin_ = np.asarray(lmp.get("base_score", [0.0]),
                                       dtype=np.float32).reshape(-1)
        obj_cfg = learner.get("objective", {})
        name = obj_cfg.get("name", self.learner_params.get(
            "objective", "reg:squarederror"))
        self.learner_params["objective"] = name
        self.obj = get_objective(name, {k: v for k, v in obj_cfg.items()
                                        if k != "name"})
        n_groups = max(1, int(lmp.get("num_target", 1)))
        gb = learner.get("gradient_booster", {})
        self.learner_params["booster"] = gb.get("name", "gbtree") if gb \
            else self.learner_params.get("booster", "gbtree")
        self.gbm = self._make_booster(n_groups)
        if gb:
            self.gbm.from_json(gb)
        em = self.learner_params.get("eval_metric")
        if em:
            names = em if isinstance(em, (list, tuple)) else [em]
            self._eval_metrics = [get_metric(n) for n in names]
        else:
            self._eval_metrics = [get_metric(self.obj.default_metric)]
        self._configured = True
        self._caches = {}

    # ------------------------------------------------------------- snapshots
    def make_snapshot(self, dtrain: Optional[DMatrix] = None,
                      fingerprint: Optional[Dict[str, Any]] = None,
                      round_: Optional[int] = None):
        """Full recoverable training state (``utils.checkpoint``): model +
        round counter + the training-cache MARGIN. The margin is the hidden
        accumulator that makes resume bit-exact — recomputing it from the
        trees sums leaf deltas in a different order than training
        accumulated them, which forks the models by an ulp (why the old
        recovery contract was rtol). RNG needs no stream state: every key
        is a stateless function of ``(seed, iteration)``."""
        from .utils.checkpoint import TrainingSnapshot

        margin = None
        state = self._caches.get(id(dtrain)) if dtrain is not None else None
        if state is not None and state.get("is_train"):
            m = state["margin"]
            if not (isinstance(m, jax.Array)
                    and not m.is_fully_addressable):
                # trim mesh/page padding: pad rows carry zero weight, so
                # their margins never reach a gradient — restore re-pads
                # with zeros (multi-controller arrays are not host-visible;
                # those snapshots fall back to model-only = rtol resume)
                margin = np.asarray(m, np.float32)[: state["n_valid"]]
        extra: Dict[str, Any] = {}
        # stateful booster RNG streams (dart's drop selection): the key-based
        # tree PRNG is stateless, but np.random.RandomState streams consume
        # state per round and must resume mid-stream
        brng = getattr(self.gbm, "_rng", None)
        if brng is not None and hasattr(brng, "get_state"):
            alg, keys, pos, has_gauss, cached = brng.get_state()
            extra["booster_rng"] = {
                "alg": str(alg), "keys": np.asarray(keys, np.int64),
                "pos": int(pos), "has_gauss": int(has_gauss),
                "cached": float(cached)}
        # the TrainingLog rides the snapshot so eval histories (and the
        # EarlyStopping patience window built on them) survive resume
        tl = self.training_log
        if tl is not None and (len(tl) or tl.records):
            extra["training_log"] = tl.to_obj()
        return TrainingSnapshot(
            round=int(round_ if round_ is not None
                      else self.num_boosted_rounds()),
            model=bytes(self.save_raw("ubj")),
            margin=margin,
            fingerprint=dict(fingerprint or {}),
            rng={"seed": int(self.ctx.seed),
                 "seed_per_iteration": bool(self.ctx.seed_per_iteration)},
            extra=extra)

    def _prime_resume(self, dtrain: DMatrix, snap) -> None:
        """Install a snapshot's margin into the training cache so the next
        ``update`` continues from the exact interrupted state instead of
        re-deriving the margin through the (order-divergent) continuation
        walk. No-op when the snapshot carried no margin — the standard
        xgb_model continuation fold then applies (rtol-grade resume)."""
        self._configure(dtrain)
        state = self._state_of(dtrain, is_train=True)
        st = snap.extra.get("booster_rng") if snap.extra else None
        brng = getattr(self.gbm, "_rng", None)
        if st is not None and brng is not None \
                and hasattr(brng, "set_state"):
            brng.set_state((st["alg"],
                            np.asarray(st["keys"]).astype(np.uint32),
                            int(st["pos"]), int(st["has_gauss"]),
                            float(st["cached"])))
        tl = snap.extra.get("training_log") if snap.extra else None
        if tl is not None:
            from .obs import insight as obs_insight

            self.training_log = obs_insight.TrainingLog.from_obj(tl)
        if snap.margin is None:
            return
        m = jnp.asarray(np.asarray(snap.margin, np.float32))
        cur = state["margin"]
        if m.ndim == 1:
            m = m[:, None]
        if m.shape[0] < cur.shape[0]:  # re-extend mesh/page pad rows
            m = jnp.concatenate(
                [m, jnp.zeros((cur.shape[0] - m.shape[0], m.shape[1]),
                              jnp.float32)])
        if isinstance(cur, jax.Array) and self.ctx.mesh is not None:
            m = jax.device_put(m, cur.sharding)
        state["margin"] = m
        state["n_trees"] = self.gbm.version()
        hook = getattr(self.gbm, "on_resume", None)
        if hook is not None:
            hook(state)

    def __getstate__(self):
        return {"raw": bytes(self.save_raw("json"))}

    def __setstate__(self, state):
        self.__init__()
        self.load_model(state["raw"])

    def __copy__(self) -> "Booster":
        return self.__deepcopy__(None)

    def __deepcopy__(self, _: Any) -> "Booster":
        out = Booster()
        out.load_model(self.save_raw("json"))
        out.set_param({k: v for k, v in self.learner_params.items()
                       if _jsonable(v)})
        return out

    def copy(self) -> "Booster":
        """Copy the booster (reference ``Booster.copy``, core.py:1869)."""
        return self.__copy__()

    # ------------------------------------------------------------------ config
    def save_config(self) -> str:
        """Internal parameter configuration as a JSON string (reference
        ``XGBoosterSaveJsonConfig``, core.py:1836)."""
        import json as _json

        return _json.dumps({
            "version": [2, 0, 0],
            "learner": {
                "learner_train_param": {
                    k: v for k, v in self.learner_params.items()
                    if _jsonable(v)},
                "gradient_booster": {
                    "name": self.learner_params.get("booster", "gbtree"),
                    "tree_train_param": self.tree_param.to_json(),
                },
            },
        })

    def load_config(self, config: str) -> None:
        """Load configuration returned by :meth:`save_config`."""
        import json as _json

        obj = _json.loads(config)
        learner = obj.get("learner", {})
        self.set_param(learner.get("learner_train_param", {}))
        gbm = learner.get("gradient_booster", {})
        self.set_param(gbm.get("tree_train_param", {}))

    # ------------------------------------------------------------------- dump
    def get_dump(self, fmap: str = "", with_stats: bool = False,
                 dump_format: str = "text") -> List[str]:
        """Per-tree dumps (reference ``XGBoosterDumpModelEx``)."""
        from .dump import dump_dot, dump_json, dump_text

        self._configure(None)
        if not isinstance(self.gbm, GBTree):
            raise NotImplementedError("dump is only supported for tree models")
        out = []
        for tree in self.gbm.trees:
            if dump_format == "json":
                import json as _json

                out.append(_json.dumps(dump_json(tree, self.feature_names,
                                                 with_stats)))
            elif dump_format == "dot":
                out.append(dump_dot(tree, self.feature_names, with_stats))
            else:
                out.append(dump_text(tree, self.feature_names, with_stats))
        return out

    def dump_model(self, fout: str, fmap: str = "", with_stats: bool = False,
                   dump_format: str = "text") -> None:
        dumps = self.get_dump(fmap, with_stats, dump_format)
        with open(fout, "w") as fh:
            if dump_format == "json":
                fh.write("[\n" + ",\n".join(dumps) + "\n]")
            else:
                for i, d in enumerate(dumps):
                    fh.write(f"booster[{i}]:\n{d}")

    def trees_to_dataframe(self, fmap: str = ""):
        from .dump import trees_to_dataframe

        self._configure(None)
        return trees_to_dataframe(self.gbm.trees, self.feature_names)

    # ----------------------------------------------------------- importances
    def get_score(self, fmap: str = "", importance_type: str = "weight"
                  ) -> Dict[str, float]:
        """Feature importances (reference ``CalcFeatureScore``,
        ``src/learner.cc``): weight | gain | total_gain | cover | total_cover."""
        self._configure(None)
        if isinstance(self.gbm, GBLinear):
            coefs = self.gbm.feature_scores()
            return {(self.feature_names[f] if self.feature_names
                     and f < len(self.feature_names) else f"f{f}"): float(v)
                    for f, v in enumerate(coefs) if v != 0.0}
        scores: Dict[int, float] = {}
        counts: Dict[int, int] = {}
        for tree in self.gbm.trees:
            mask = ~tree.is_leaf
            for h in np.nonzero(mask)[0]:
                f = int(tree.split_feature[h])
                counts[f] = counts.get(f, 0) + 1
                if importance_type in ("gain", "total_gain"):
                    scores[f] = scores.get(f, 0.0) + float(tree.gain[h])
                elif importance_type in ("cover", "total_cover"):
                    scores[f] = scores.get(f, 0.0) + float(tree.sum_hess[h])
                else:
                    scores[f] = scores.get(f, 0.0) + 1.0
        if importance_type in ("gain", "cover"):
            scores = {f: s / counts[f] for f, s in scores.items()}

        def fname(f: int) -> str:
            if self.feature_names and f < len(self.feature_names):
                return self.feature_names[f]
            return f"f{f}"

        return {fname(f): v for f, v in scores.items()}

    def get_fscore(self, fmap: str = "") -> Dict[str, float]:
        """Split counts per feature (reference ``get_fscore``, core.py:2720 —
        an alias of weight importance; zero-importance features omitted)."""
        return self.get_score(fmap, importance_type="weight")

    def inspect(self) -> Dict[str, Any]:
        """Structural model report: every importance type, tree-shape
        histograms, totals (obs/insight.py ``model_inspect``). The
        pipeline records one per promoted/rejected epoch; serve renders it
        on ``GET /v1/model/<name>/report``; ``tools/model_report.py`` is
        the CLI."""
        from .obs import insight as obs_insight

        return obs_insight.model_inspect(self)

    def get_split_value_histogram(self, feature: str, fmap: str = "",
                                  bins: Optional[int] = None,
                                  as_pandas: bool = True):
        """Histogram of a feature's used split thresholds (reference
        ``get_split_value_histogram``, core.py:2967)."""
        import re

        xgdump = self.get_dump(fmap=fmap)
        regexp = re.compile(r"\[{0}<([\d.Ee+-]+)\]".format(re.escape(feature)))
        values: List[float] = []
        for val in xgdump:
            values.extend(float(x) for x in re.findall(regexp, val))

        n_unique = len(np.unique(values))
        nbins = max(min(n_unique, bins) if bins is not None else n_unique, 1)
        nph = np.histogram(values, bins=nbins)
        nph_stacked = np.column_stack((nph[1][1:], nph[0]))
        nph_stacked = nph_stacked[nph_stacked[:, 1] > 0]
        if nph_stacked.size == 0:
            fn = self.feature_names or [f"f{i}"
                                        for i in range(self.num_features())]
            try:
                index = fn.index(feature)
                feature_t = (self.feature_types or [])[index]
            except (ValueError, IndexError, TypeError):
                feature_t = None
            if feature_t == "c":
                raise ValueError(
                    "Split value histogram doesn't support categorical split.")
        if as_pandas:
            try:
                from pandas import DataFrame

                return DataFrame(nph_stacked, columns=["SplitValue", "Count"])
            except ImportError:
                pass
        return nph_stacked


def _jsonable(v: Any) -> bool:
    try:
        json.dumps(v)
        return True
    except (TypeError, ValueError):
        return False


def train(params: Dict[str, Any], dtrain: DMatrix,
          num_boost_round: int = 10,
          *, evals: Sequence[Tuple[DMatrix, str]] = (),
          obj: Optional[Callable] = None,
          feval: Optional[Callable] = None,
          maximize: Optional[bool] = None,
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[Dict] = None,
          verbose_eval: Union[bool, int, None] = True,
          xgb_model: Optional[Union[str, Booster]] = None,
          callbacks: Optional[Sequence] = None,
          custom_metric: Optional[Callable] = None,
          checkpoint: Optional[Any] = None) -> Booster:
    """Train loop (reference ``python-package/xgboost/training.py:178``).

    ``checkpoint``: a ``CheckpointConfig`` enabling full-state snapshots
    every N rounds plus auto-resume (docs/reliability.md). On auto-resume
    ``num_boost_round`` is the TOTAL round target, so re-running the
    identical command after a crash converges to the straight-run model —
    bit-exactly (``tools/validate_resume.py`` gates this)."""
    from .callback import (CallbackContainer, EarlyStopping,
                           EvaluationMonitor)

    from .obs import insight as obs_insight
    from .obs.metrics import freeze_startup

    callbacks = list(callbacks) if callbacks else []
    # Round batching: valid when NOTHING consumes per-round output. Decided
    # on the USER-supplied callbacks — the EvaluationMonitor appended below
    # is a no-op without evals, so it must not disable batching. Insight
    # consumes per-round output by definition, so it disables batching too.
    batchable = (not callbacks and not evals and obj is None
                 and custom_metric is None and feval is None
                 and not obs_insight.enabled())
    if verbose_eval:
        period = 1 if verbose_eval is True else int(verbose_eval)
        callbacks.append(EvaluationMonitor(period=period))
    if early_stopping_rounds is not None:
        callbacks.append(EarlyStopping(rounds=early_stopping_rounds,
                                       maximize=maximize, save_best=False))
    metric_fn = custom_metric if custom_metric is not None else feval
    container = CallbackContainer(callbacks, metric=metric_fn)

    ck = None
    resumed = None
    if checkpoint is not None:
        from .utils.checkpoint import CheckpointManager

        ck = CheckpointManager(checkpoint)
        if xgb_model is None:
            resumed = ck.find_resume(dtrain)

    if resumed is not None:
        bst = Booster(params)
        bst.load_model(resumed.model)
        bst.set_param(params)
    elif isinstance(xgb_model, Booster):
        bst = xgb_model
        bst.set_param(params)
    elif xgb_model is not None:
        bst = Booster(params, model_file=xgb_model)
    else:
        bst = Booster(params)

    if ck is not None:
        ck.ensure_fingerprint(dtrain)
    if resumed is not None:
        bst._prime_resume(dtrain, resumed)
        if bst.training_log is not None:
            # the snapshot's log becomes the container history, so
            # evals_result and the EarlyStopping patience window continue
            # from the interrupted round instead of restarting empty
            container.history = bst.training_log
    # the container's history IS the booster's TrainingLog: one object,
    # written by callbacks (eval parsing) and insight (round telemetry)
    bst.training_log = container.history
    if (obs_insight.eval_enabled() and evals and metric_fn is None
            and obj is None):
        # arm the in-carry eval: _insight_binding folds these eval sets'
        # margin update + metric partials into the fused round program
        bst._insight_evals = list(evals)

    with obs_trace.phase("train/call", "train",
                         {"iteration": bst.num_boosted_rounds(),
                          "rounds": num_boost_round}):
        bst = _train_rounds(bst, dtrain, num_boost_round, container, evals,
                            obj, batchable, ck, resumed)
    bst._monitor.maybe_print()  # one cumulative table (reference: destructor)
    startup = freeze_startup()  # the process's first call alone gets one
    if startup is not None:
        from .config import get_config

        if get_config().get("verbosity", 1) >= 2:
            console("start-up: " + ", ".join(
                f"{name} {secs:.2f}s" for name, secs in sorted(
                    startup.items(), key=lambda kv: -kv[1])))

    if evals_result is not None:
        evals_result.update(container.history)
    return bst


def _train_rounds(bst: Booster, dtrain: DMatrix, num_boost_round: int,
                  container, evals, obj, batchable: bool, ck,
                  resumed) -> Booster:
    """``train``'s round loop, under its ``train/call`` span. Each iteration
    is one ``round`` step span (``step_num`` = its first round, ``rounds`` =
    how many it boosts), so a profiler trace reads in rounds."""
    from .parallel import collective

    bst = container.before_training(bst)
    start = bst.num_boosted_rounds()
    # Largest power-of-two chunks <= XTPU_BATCH_ROUNDS: each chunk is one
    # device dispatch (lax.scan), and pow2 sizing bounds the set of distinct
    # scan lengths — i.e. compiled programs — to log2(max) + 1. Checkpoint
    # boundaries additionally cap a chunk so snapshots land exactly every
    # N rounds (scan-batched rounds are bit-identical to sequential ones,
    # so chunk geometry never changes the model).
    batch_max = int(os.environ.get("XTPU_BATCH_ROUNDS", "16"))
    i = start
    # auto-resume treats num_boost_round as the TOTAL target (see docstring)
    end = (max(start, num_boost_round) if resumed is not None
           else start + num_boost_round)
    try:
        while i < end:
            collective.notify_round(i)
            lim = min(batch_max, end - i)
            if ck is not None:
                lim = min(lim, ck.rounds_to_boundary(i))
            if batchable and lim >= 2:
                k = 1 << (lim.bit_length() - 1)
                with obs_trace.phase("round", "train",
                                     {"iteration": i, "rounds": k}, step=i):
                    batched = bst.update_batch(dtrain, list(range(i, i + k)))
                if batched:
                    i += k
                    if ck is not None:
                        ck.maybe_save(bst, dtrain, i, force=(i == end))
                    continue
                # config needs the per-round path (or a continuation
                # bootstrap round) — fall through; retried next iteration
            with obs_trace.phase("round", "train",
                                 {"iteration": i, "rounds": 1}, step=i):
                if container.before_iteration(bst, i):
                    break
                bst.update(dtrain, i, fobj=obj)
                stop = container.after_iteration(bst, i, list(evals))
            i += 1
            if ck is not None:
                ck.maybe_save(bst, dtrain, i, force=(stop or i == end))
            if stop:
                break
    except BaseException:
        # flush + join the background writer even when the round loop dies
        # (the snapshot being flushed is exactly what the relaunched run
        # will resume from) — but never let a secondary write failure mask
        # the original error
        if ck is not None:
            ck.close()
        raise
    else:
        # normal exit: a silently-failed background write would leave the
        # newest snapshot stale, so here write failures DO surface
        if ck is not None:
            ck.close(raise_errors=True)
    return container.after_training(bst)
