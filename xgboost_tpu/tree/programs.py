"""Tree-tier program handles: paged level_full, mesh twins.

Registered into :mod:`xgboost_tpu.programs` (see that module's docstring
for the plan format). Every builder returns the SAME jitted callables the
drivers dispatch — pulled from the grower/kernel caches via the
non-dispatching accessors (``TreeGrower.sharded_program``,
``_PageKernels.level_full_fn``) — paired with abstract avals, so tracing
a handle traces the real program.
"""

from __future__ import annotations

import types

import numpy as np

from ..programs import (ProgramSpec, ProgramUnavailable, RoundPlan,
                        _abstract, register_program)

_R, _F, _B = 512, 8, 64


class _NumericCuts:
    """Minimal cuts stand-in for building growers abstractly: all-numeric
    features (``is_cat`` drives construction; ``split_values`` is only
    touched when materializing a grown tree, which tracing never does)."""

    def __init__(self, n_features: int) -> None:
        self._F = n_features

    def is_cat(self) -> np.ndarray:
        return np.zeros(self._F, bool)

    def n_real_bins(self) -> np.ndarray:
        return np.full(self._F, _B - 1, np.int32)


def _grow_args():
    return (_abstract((_R, _F), "uint8"),      # bins
            _abstract((_R, 2), "float32"),     # gpair
            _abstract((_F,), "int32"),         # n_real_bins
            _abstract((_F,), "bool_"),         # tree_mask
            _abstract((2,), "uint32"))         # key


@register_program("paged.level_full")
def _paged_level_full() -> RoundPlan:
    from .paged import _LevelEvaluator, _PageKernels

    from .param import TrainParam

    n_static, n_pages, page_rows = 8, 2, 256
    cfg = types.SimpleNamespace(param=TrainParam(max_depth=3), cat=None,
                                has_missing=True,
                                max_nbins=_B)
    ev = _LevelEvaluator(cfg, n_static=n_static, max_nodes=15, deep=False,
                         n_real_bins=np.full(_F, _B - 1, np.int64),
                         coarse=True)
    paged = types.SimpleNamespace(packed=False, n_features=_F)
    kern = _PageKernels(max_nbins=_B, missing_bin=_B - 1,
                        hist_kernel="auto")
    fn = kern.level_full_fn(paged, ev, n_static, kind="dense", W=None,
                            n_arr=4, n_cached=n_pages)
    state = (_abstract((n_static,), "bool_"),        # active
             _abstract((n_static, 2), "float32"),    # parent sums
             _abstract((n_static,), "float32"),      # monotone lo
             _abstract((n_static,), "float32"),      # monotone hi
             _abstract((1,), "bool_"),               # constraint path
             _abstract((1,), "bool_"))               # deep-walk arrays
    scalar = _abstract((), "int32")
    consts = ((_abstract((_R, 2), "float32"),        # gpair
               scalar, scalar, scalar, scalar, scalar)
              + (_abstract((n_static,), "int32"),    # prev split feature
                 _abstract((n_static,), "int32"),    # prev split bin
                 _abstract((n_static,), "bool_"),    # prev default-left
                 _abstract((n_static,), "bool_")))   # prev can-split
    spec = ProgramSpec(
        name="level_full",
        fn=fn,
        args=(_abstract((_R,), "int32"),             # positions (donated)
              state,                                 # carried state (donated)
              _abstract((_F,), "bool_"),             # tree_mask
              _abstract((2,), "uint32"),             # key
              consts,
              tuple(scalar for _ in range(n_pages)),            # page starts
              tuple(_abstract((page_rows, _F), "uint8")
                    for _ in range(n_pages))),       # HBM-cached pages
        donate_argnums=(0, 1),
        src=_PageKernels.level_full_fn)
    return RoundPlan(handle="paged.level_full", unit="level",
                     dispatches=[spec],
                     meta={"uploads_per_level": 0})


def _mesh_plan(split_mode: str) -> RoundPlan:
    import jax

    from ..context import DATA_AXIS, make_data_mesh
    from .grow import TreeGrower, _grow
    from .param import TrainParam

    if len(jax.devices()) < 2:
        raise ProgramUnavailable(
            f"mesh.{split_mode} needs >= 2 devices (have "
            f"{len(jax.devices())}; run under "
            "--xla_force_host_platform_device_count=8)")
    mesh = make_data_mesh()
    grower = TreeGrower(TrainParam(max_depth=3), _B, _NumericCuts(_F),
                        hist_method="fused", mesh=mesh,
                        split_mode=split_mode)
    spec = ProgramSpec(
        name=f"sharded_grow_{split_mode}",
        fn=grower.sharded_program(),
        # monotone, constraint_sets, cat: none
        args=_grow_args() + (None, None, None),
        src=_grow)
    return RoundPlan(handle=f"mesh.{split_mode}", unit="tree",
                     dispatches=[spec],
                     meta={"mesh_axes": (DATA_AXIS,)})


@register_program("mesh.row")
def _mesh_row() -> RoundPlan:
    # what "auto" runs on a mesh: in-body histogram psum inside the
    # shard_map
    return _mesh_plan("row")


@register_program("mesh.col")
def _mesh_col() -> RoundPlan:
    # col split: local split finding + best-split allgather + decision psum
    return _mesh_plan("col")
