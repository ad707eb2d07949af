"""Traceable program handles — the library's declared hot-path schedule.

Each *handle* names one execution tier (resident fused, paged
level_full, mesh row/col, serve walk) and builds a
:class:`RoundPlan`: the ordered list of jitted programs that tier
dispatches per steady scheduling unit (round / tree / level / batch),
each paired with abstract avals so the program can be traced with
``jax.ShapeDtypeStruct`` inputs — no device execution, no real data.

This is the supported surface for ``tools/xtpuverify``: the verifier
traces these handles and checks the jaxprs against the contract table
instead of reaching into private jit wrappers, and the builders live
next to the drivers they describe (``core.steady_round_dispatches``,
``TreeGrower.sharded_program``, ``_PageKernels.level_full_fn``, ...) so
a schedule change and its declared plan move in the same review. The
ROADMAP item-4 schedule IR is expected to *generate* plans in this
format per emitted driver.

Builders are lazy: nothing here traces or compiles at import time, and
tier modules register their handles only when :func:`load_all` runs.
"""

from __future__ import annotations

import inspect
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class ProgramUnavailable(RuntimeError):
    """Raised by a builder whose tier cannot be traced in this process
    (e.g. the mesh twins need >= 2 devices). The verifier CLI reports
    these as skips; the tier-1 gate requires zero of them."""


def _source_of(fn) -> Tuple[str, int]:
    """(repo-relative path, def line) of the python function behind a
    jit/shard_map/partial wrapper stack."""
    seen = 0
    while seen < 8:
        seen += 1
        if hasattr(fn, "__wrapped__"):
            fn = fn.__wrapped__
        elif hasattr(fn, "func"):        # functools.partial
            fn = fn.func
        else:
            break
    try:
        path = inspect.getsourcefile(fn)
        line = fn.__code__.co_firstlineno
    except (TypeError, AttributeError):
        return "<unknown>", 0
    rel = os.path.relpath(os.path.abspath(path), _REPO_ROOT)
    return rel.replace(os.sep, "/"), line


@dataclass(frozen=True)
class ProgramSpec:
    """One jitted dispatch of a plan, with abstract call arguments.

    ``fn`` must be the SAME jitted callable object the driver invokes
    (not a re-wrap), so the traced jaxpr is the program that actually
    runs. ``src`` optionally names the underlying python function when
    wrapping (shard_map, closures) hides it from introspection — it
    anchors findings and ``# xtpuverify: disable=`` pragmas."""
    name: str
    fn: Any
    args: Tuple[Any, ...]
    kwargs: Any = None                   # dict | None (static kwargs)
    donate_argnums: Tuple[int, ...] = ()
    src: Any = None

    @property
    def source(self) -> Tuple[str, int]:
        return _source_of(self.src if self.src is not None else self.fn)


@dataclass
class RoundPlan:
    """The steady-state dispatch schedule of one tier.

    ``unit`` is the scheduling unit the dispatch count is measured per:
    ``"round"`` (resident boosting round), ``"tree"`` (lossguide / mesh
    grow), ``"level"`` (paged level boundary), ``"batch"`` (serve).
    ``meta`` carries declared schedule facts the contracts cross-check
    (``uploads_per_level``, ``mesh_axes``)."""
    handle: str
    unit: str
    dispatches: List[ProgramSpec]
    meta: Dict[str, Any] = field(default_factory=dict)


PROGRAM_BUILDERS: Dict[str, Callable[[], RoundPlan]] = {}
_LOADED = False


def register_program(name: str):
    def deco(builder: Callable[[], RoundPlan]):
        PROGRAM_BUILDERS[name] = builder
        return builder
    return deco


def load_all() -> None:
    """Import every tier's program module (idempotent)."""
    global _LOADED
    if _LOADED:
        return
    from .serve import programs as _serve_programs    # noqa: F401
    from .tree import programs as _tree_programs      # noqa: F401
    _LOADED = True


def program_names() -> List[str]:
    load_all()
    return sorted(PROGRAM_BUILDERS)


def build_plan(name: str) -> RoundPlan:
    load_all()
    return PROGRAM_BUILDERS[name]()


# --------------------------------------------------------- resident tiers
#
# Shapes are abstract-trace stand-ins, not benchmarks: small enough to
# trace in milliseconds, large enough that every structural feature of
# the real program (level loop, histogram width, NaN guard) is present.

_R, _F, _B = 512, 8, 64


def _abstract(shape, dtype):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, getattr(jnp, dtype))


@register_program("resident.fused")
def _resident_fused() -> RoundPlan:
    from . import core
    from .registry import OBJECTIVES
    from .tree.param import TrainParam

    obj_cls = OBJECTIVES.get("binary:logistic")
    round_fn, guard_fn = core.steady_round_dispatches()
    round_spec = ProgramSpec(
        name="fused_round",
        fn=round_fn,
        args=(_abstract((_R, _F), "uint8"),       # bins
              _abstract((_R, 1), "float32"),      # margin (donated)
              _abstract((_R,), "float32"),        # labels
              None,                               # weights
              _abstract((_F,), "int32"),          # n_real
              _abstract((), "uint32"),            # seed
              _abstract((), "int32"),             # iteration
              None, None, None),                  # monotone/constraints/cat
        kwargs=dict(obj_cls=obj_cls, obj_params=(),
                    param=TrainParam(max_depth=3), max_nbins=_B,
                    hist_method="fused", has_missing=True,
                    nan_policy="raise"),
        donate_argnums=(1,))
    guard_spec = ProgramSpec(
        name="margin_bad_rows",
        fn=guard_fn,
        args=(_abstract((_R, 1), "float32"),),
        kwargs=dict(n_valid=_R))
    return RoundPlan(handle="resident.fused", unit="round",
                     dispatches=[round_spec, guard_spec])


_RE = 64  # eval rows in the insight-armed abstract trace


@register_program("resident.fused.insight")
def _resident_fused_insight() -> RoundPlan:
    """The xtpuinsight-armed resident round (obs/insight.py): telemetry
    scalars and ONE armed eval set (margin walk + metric partials) ride
    the round program as extra outputs. Same dispatch list length as the
    unarmed plan — the contract table pins the budget, so smuggling the
    telemetry into its own dispatch is a gate failure."""
    from . import core
    from .registry import OBJECTIVES
    from .tree.param import TrainParam

    obj_cls = OBJECTIVES.get("binary:logistic")
    round_fn, guard_fn = core.steady_round_dispatches_insight()
    round_spec = ProgramSpec(
        name="fused_round_insight",
        fn=round_fn,
        args=(_abstract((_R, _F), "uint8"),       # bins
              _abstract((_R, 1), "float32"),      # margin (donated)
              _abstract((_R,), "float32"),        # labels
              None,                               # weights
              _abstract((_F,), "int32"),          # n_real
              _abstract((), "uint32"),            # seed
              _abstract((), "int32"),             # iteration
              None, None, None,                   # monotone/constraints/cat
              (_abstract((_RE, _F), "uint8"),),   # eval bins
              (_abstract((_RE, 1), "float32"),),  # eval margins (donated)
              (_abstract((_RE,), "float32"),),    # eval labels
              (None,)),                           # eval weights
        kwargs=dict(obj_cls=obj_cls, obj_params=(),
                    param=TrainParam(max_depth=3), max_nbins=_B,
                    hist_method="fused", has_missing=True,
                    nan_policy="raise",
                    eval_specs=(("logloss", 0.0),),
                    eval_missing=(_B - 1,)),
        donate_argnums=(1, 11))
    guard_spec = ProgramSpec(
        name="margin_bad_rows",
        fn=guard_fn,
        args=(_abstract((_R, 1), "float32"),),
        kwargs=dict(n_valid=_R))
    return RoundPlan(handle="resident.fused.insight", unit="round",
                     dispatches=[round_spec, guard_spec])
