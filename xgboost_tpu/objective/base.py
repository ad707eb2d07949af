"""Objective base class + task descriptor."""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..registry import OBJECTIVES


class NumericalDivergence(RuntimeError):
    """Non-finite gradients detected (reference: silent — a NaN gradient
    poisons histogram sums, every split gain, and finally the committed
    leaf values, and the run "succeeds" with an all-NaN model). Raised
    BEFORE the offending round's tree is committed, so the model on the
    booster stays clean. ``XTPU_NAN_POLICY=zero`` degrades gracefully
    instead (offending gpairs are zeroed with a warning — the bad rows
    simply stop contributing, like zero-weight rows); ``off`` disables
    the check entirely for maximum throughput."""

    def __init__(self, message: str, *, iteration: Optional[int] = None,
                 objective: Optional[str] = None,
                 bad_rows: Optional[int] = None) -> None:
        super().__init__(message)
        self.iteration = iteration
        self.objective = objective
        self.bad_rows = bad_rows


def _nan_policy() -> str:
    p = os.environ.get("XTPU_NAN_POLICY", "raise").strip().lower()
    if p not in ("raise", "zero", "off"):
        raise ValueError(
            f"XTPU_NAN_POLICY must be raise|zero|off, got {p!r}")
    return p


def guard_gradient(gpair: jnp.ndarray, objective: str,
                   iteration: int) -> jnp.ndarray:
    """Finite-check one [n, k, 2] gradient matrix under XTPU_NAN_POLICY.

    Eager gradients (the general per-round path, custom ``fobj``) raise a
    typed :class:`NumericalDivergence` or zero-and-warn host-side. Inside
    a trace (the fused round programs) the ``zero`` policy applies as an
    in-trace ``where`` — bit-free for finite inputs — while the ``raise``
    policy defers to the round-loop margin check (``core._assert_finite``)
    which fires before the tree is committed."""
    policy = _nan_policy()
    if policy == "off":
        return gpair
    # a (grad, hess) pair is "offending" when either half is non-finite
    pair_ok = jnp.isfinite(gpair).all(axis=-1, keepdims=True)  # [n, k, 1]
    if isinstance(gpair, jax.core.Tracer):
        if policy == "zero":
            return jnp.where(pair_ok, gpair, jnp.zeros_like(gpair))
        return gpair  # raise policy: caught post-round, pre-commit
    bad_rows = int(jnp.sum(~pair_ok.all(axis=1)[:, 0]))
    if bad_rows == 0:
        return gpair
    if policy == "zero":
        from ..logging_utils import logger

        logger.warning(
            "objective %r produced non-finite gradients for %d rows at "
            "round %d; XTPU_NAN_POLICY=zero drops their contribution",
            objective, bad_rows, iteration)
        return jnp.where(pair_ok, gpair, jnp.zeros_like(gpair))
    raise NumericalDivergence(
        f"objective {objective!r} produced non-finite gradients for "
        f"{bad_rows} row(s) at round {iteration} — check labels/weights "
        "for NaN/Inf (or a diverging custom objective). Set "
        "XTPU_NAN_POLICY=zero to drop the offending rows and continue.",
        iteration=iteration, objective=objective, bad_rows=bad_rows)


@dataclass
class ObjInfo:
    """Task descriptor (reference ``include/xgboost/task.h:24-36``)."""

    task: str = "regression"        # regression | binary | classification | ranking | survival
    const_hess: bool = False
    zero_hess: bool = False         # adaptive-leaf objectives (mae, quantile)


class Objective:
    """Base objective. Subclasses override gradient/transform hooks.

    Shapes: margins are [n, k] (k = n_targets, 1 for most objectives); the
    gradient result is [n, k, 2] packing (grad, hess) — the analogue of the
    reference's ``GradientPair`` matrix (``linalg::Matrix<GradientPair>``).
    """

    name: str = ""
    default_metric: str = "rmse"
    info = ObjInfo()

    def __init__(self, params: Optional[Dict[str, Any]] = None) -> None:
        self.params: Dict[str, Any] = {}
        if params:
            self.configure(params)

    def configure(self, params: Dict[str, Any]) -> None:
        self.params.update(params)

    # -- shape ---------------------------------------------------------------
    def n_targets(self, info) -> int:
        if info is not None and info.labels is not None and info.labels.ndim == 2:
            return info.labels.shape[1]
        return 1

    # -- core hooks ----------------------------------------------------------
    def gradient(self, preds: jnp.ndarray, labels: jnp.ndarray,
                 iteration: int = 0) -> jnp.ndarray:
        """preds/labels [n, k] -> [n, k, 2]."""
        raise NotImplementedError

    def get_gradient(self, preds: jnp.ndarray, info,
                     iteration: int = 0) -> jnp.ndarray:
        # MetaInfo caches the device label/weight copies — a bare
        # jnp.asarray here would re-upload O(n) bytes EVERY round (44 MB
        # at HIGGS-11M). Duck-typed infos (tests, adapters) without the
        # cache fall back to a plain upload.
        dev = getattr(info, "labels_device", None)
        labels = (dev() if dev is not None
                  else jnp.asarray(info.labels, dtype=jnp.float32))
        if labels.ndim == 1:
            labels = labels[:, None]
        gpair = self.gradient(preds, labels, iteration)
        if info.weights is not None:
            wdev = getattr(info, "weights_device", None)
            w = (wdev() if wdev is not None
                 else jnp.asarray(info.weights, dtype=jnp.float32))
            gpair = gpair * w[:, None, None]
        return guard_gradient(gpair, self.name, iteration)

    def pred_transform(self, margin: jnp.ndarray) -> jnp.ndarray:
        return margin

    def prob_to_margin(self, prob: np.ndarray) -> np.ndarray:
        return prob

    def _stump_sums(self, info):
        """Zero-margin gradient sums on device -> ([k] g, [k] h). The
        [n, k, 2] gradient never leaves the device (materialising it
        host-side costs an n-proportional transfer)."""
        k = self.n_targets(info)
        zero = jnp.zeros((len(info.labels), k), dtype=jnp.float32)
        gpair = jnp.asarray(self.get_gradient(zero, info))
        return gpair[..., 0].sum(axis=0), gpair[..., 1].sum(axis=0)

    def init_estimation(self, info) -> np.ndarray:
        """One Newton step from margin 0 (reference fit_stump,
        ``src/tree/fit_stump.cc:25-58`` — gradient sums cross workers via
        ``collective::GlobalSum`` so every rank derives the same base score
        from its row shard)."""
        from ..parallel.collective import global_sum

        g_d, h_d = self._stump_sums(info)
        sums = np.stack([np.asarray(g_d), np.asarray(h_d)])  # [2, k] pull
        row_split = getattr(info, "data_split_mode", "row") == "row"
        gh = global_sum(sums, row_split=row_split)
        g, h = gh[0], gh[1]
        return np.where(h <= 0, 0.0, -g / np.maximum(h, 1e-10)).astype(np.float32)

    def init_estimation_device(self, info) -> jnp.ndarray:
        """Single-process stump fit that STAYS on device: same sums as
        ``init_estimation`` (shared ``_stump_sums``) without the host pull
        — that device_get serializes every ``train()`` start on a blocking
        device->host transfer. Only valid when no communicator is active (the
        distributed path must cross hosts via ``global_sum``)."""
        g, h = self._stump_sums(info)
        return jnp.where(h <= 0, 0.0,
                         -g / jnp.maximum(h, 1e-10)).astype(jnp.float32)

    def to_json(self) -> Dict[str, Any]:
        return {"name": self.name, **{k: str(v) for k, v in self.params.items()}}


def get_objective(name: str, params: Optional[Dict[str, Any]] = None) -> Objective:
    return OBJECTIVES.create(name, params)
