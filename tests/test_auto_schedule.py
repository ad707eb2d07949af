"""What ``hist_method`` resolves to, and the counter that says so.

One function decides (``tree/grow.py resolve_schedule``) and every grower
asks it. On a TPU backend ``auto`` promotes to the two-level search as the
FUSED sweep; CPU runs never promote, so the backend is patched here; what
the chip does with the schedule is ``chip_smoke.py``'s and the benchmark's
to show. The names PR 31 removed (``scan``, ``mega``, ``prehot``, the
``+sub``/``+nosub`` suffixes) are refused where the booster is configured,
before anything is traced."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.obs.metrics import (get_registry, grow_schedule_counts,
                                     program_compile_counts)
from xgboost_tpu.tree import grow as grow_mod
from xgboost_tpu.tree.grow import resolve_schedule
from xgboost_tpu.tree.param import TrainParam

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, BINS = 10_500_000, 256          # the benchmark cells' shape


@pytest.fixture
def tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _resolve(method, n=ROWS, max_nbins=BINS, *, numeric=True,
             col_split=False):
    return resolve_schedule(method, n, max_nbins, False, numeric=numeric,
                            col_split=col_split)


# a shape a case: jit's trace cache does not know the backend is patched
@pytest.mark.parametrize("sharded", [False, True], ids=["one-chip", "mesh"])
@pytest.mark.parametrize("depth", [6, 8, 10])
def test_auto_is_fused_on_tpu(tpu_backend, depth, sharded):
    """The grow program itself, traced at auto's row threshold for every
    depth the cells and the walk boundaries cover, alone and under a mesh
    axis (local rows): one ``fused`` count, none of any other schedule."""
    n, F = grow_mod.AUTO_COARSE_MIN_ROWS + depth + 16 * sharded, 4
    args = (jax.ShapeDtypeStruct((n, F), jnp.uint8),
            jax.ShapeDtypeStruct((n, 2), jnp.float32),
            jax.ShapeDtypeStruct((F,), jnp.int32),
            jax.ShapeDtypeStruct((F,), jnp.bool_), jax.random.key(0))
    kwargs = dict(param=TrainParam(max_depth=depth), max_nbins=BINS,
                  hist_method="auto", has_missing=False,
                  axis_name="data" if sharded else None)
    before = grow_schedule_counts()
    # traced, never lowered: the CPU cannot compile a Mosaic kernel
    jax.make_jaxpr(lambda *a: grow_mod._grow(*a, **kwargs).delta,
                   axis_env=[("data", 4)])(*args)
    grew = {k: v - before.get(k, 0) for k, v in grow_schedule_counts().items()
            if v != before.get(k, 0)}
    assert grew == {"fused": 1}


@pytest.mark.parametrize("kw", [
    {"n": grow_mod.AUTO_COARSE_MIN_ROWS - 1},
    {"max_nbins": grow_mod.AUTO_COARSE_MIN_BINS - 1},
    {"max_nbins": 257},
    {"numeric": False},
    {"col_split": True},
], ids=["few-rows", "narrow-bins", "over-256-bins", "categorical",
        "col-split"])
def test_auto_keeps_the_one_pass_kernel_under_each_threshold(tpu_backend,
                                                            kw):
    sched = _resolve("auto", **kw)
    assert sched.name == "auto"
    assert not (sched.coarse or sched.fused)


def test_auto_never_promotes_on_cpu():
    assert jax.default_backend() == "cpu"
    assert _resolve("auto").name == "auto"


@pytest.mark.parametrize("method,flags", [
    ("fused", (True, True)), ("coarse", (True, False)),
    ("pallas", (False, False)),
])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_explicit_schedules_resolve_as_before(monkeypatch, backend, method,
                                              flags):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    sched = _resolve(method)
    assert sched.name == sched.kernel == method
    assert (sched.coarse, sched.fused) == flags


def test_the_names_are_written_once():
    """``HIST_METHODS`` is every name ``resolve_schedule`` and
    ``build_hist`` between them accept, and the two-level pair is its
    subset."""
    assert set(grow_mod.TWO_LEVEL_METHODS) < set(grow_mod.HIST_METHODS)
    for method in grow_mod.HIST_METHODS:
        sched = _resolve(method)
        assert sched.coarse == (method in grow_mod.TWO_LEVEL_METHODS)


class _Cuts:
    """Cuts stand-in for building growers without data; ``cat`` makes
    feature 0 categorical."""

    def __init__(self, n_features: int, cat: bool) -> None:
        self._is_cat = np.zeros(n_features, bool)
        self._is_cat[0] = cat

    def is_cat(self):
        return self._is_cat

    def n_real_bins(self):
        return np.full(len(self._is_cat), BINS - 1, np.int32)


def _depthwise_coarse(method, n, max_nbins, cuts, monkeypatch):
    return _resolve(method, n, max_nbins,
                    numeric=not cuts.is_cat().any()).coarse


def _lossguide_coarse(method, n, max_nbins, cuts, monkeypatch):
    from xgboost_tpu.tree.lossguide import LossguideGrower

    grower = LossguideGrower(TrainParam(max_leaves=8), max_nbins, cuts,
                             hist_method=method, has_missing=False)
    grower._resolve_schedule(n)
    assert grower._fused == grower._coarse     # auto: one dispatch a pop
    return grower._coarse


def _paged_coarse(method, n, max_nbins, cuts, monkeypatch):
    """``PagedGrower.grow`` decides before it builds its page kernels:
    stop it there."""
    from xgboost_tpu.tree import paged

    class Decided(Exception):
        pass

    def stop(grower):
        raise Decided

    monkeypatch.setattr(paged, "_make_kernels", stop)
    grower = paged.PagedGrower(TrainParam(max_depth=4), max_nbins, cuts,
                               hist_method=method, has_missing=False)
    with pytest.raises(Decided):
        grower.grow(None, jax.ShapeDtypeStruct((n, 2), jnp.float32), None,
                    None)
    return grower._coarse


SHAPES = {
    "over-every-threshold": dict(n=1 << 20),
    "few-rows": dict(n=grow_mod.AUTO_COARSE_MIN_ROWS - 1),
    "narrow-bins": dict(n=1 << 20, max_nbins=grow_mod.AUTO_COARSE_MIN_BINS - 1),
    "categorical": dict(n=1 << 20, cat=True),
}


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("grower", [_depthwise_coarse, _lossguide_coarse,
                                    _paged_coarse],
                         ids=["depthwise", "lossguide", "paged"])
def test_growers_agree_on_auto(monkeypatch, grower, backend, shape):
    """The three growers apply one rule to ``auto`` (each on its local
    rows): the two-level search on a TPU over every threshold, the
    one-pass kernel everywhere else; and explicit ``fused`` is the
    two-level search on either backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    kw = dict(SHAPES[shape])
    cuts = _Cuts(28, kw.pop("cat", False))
    n, max_nbins = kw["n"], kw.get("max_nbins", BINS)
    want = backend == "tpu" and shape == "over-every-threshold"
    assert bool(grower("auto", n, max_nbins, cuts, monkeypatch)) == want
    if shape != "categorical":      # explicit fused refuses categoricals
        assert grower("fused", n, max_nbins, cuts, monkeypatch)


REMOVED = ["scan", "mega", "prehot", "fused+sub", "auto+nosub", "scan+sub"]


def _refused(params, dm):
    """``xgb.train`` raises the refusal, and nothing was traced or
    compiled on the way to it."""
    before = (program_compile_counts(), grow_schedule_counts())
    with pytest.raises(ValueError, match="removed in PR 31") as err:
        xgb.train(params, dm, 1, verbose_eval=False)
    assert "auto, coarse, fused" in str(err.value)
    assert (program_compile_counts(), grow_schedule_counts()) == before


@pytest.fixture(scope="module")
def small():
    rng = np.random.RandomState(3)
    X = rng.randn(600, 5).astype(np.float32)
    return X, (X[:, 0] > 0).astype(np.float32)


@pytest.mark.parametrize("method", REMOVED)
def test_removed_hist_methods_are_refused(small, method):
    X, y = small
    _refused({"objective": "binary:logistic", "hist_method": method},
             xgb.DMatrix(X, label=y))


@pytest.mark.parametrize("where", ["XTPU_HIST_METHOD=scan", "lossguide",
                                   "external-memory"])
def test_removed_hist_methods_are_refused_on_every_path(small, where,
                                                        monkeypatch,
                                                        tmp_path):
    X, y = small
    params = {"objective": "binary:logistic", "hist_method": "scan"}
    dm = xgb.DMatrix(X, label=y)
    if where == "XTPU_HIST_METHOD=scan":
        monkeypatch.setenv("XTPU_HIST_METHOD", params.pop("hist_method"))
    elif where == "lossguide":
        params.update(grow_policy="lossguide", max_leaves=6)
    else:
        from xgboost_tpu.data.dmatrix import DataIter

        class It(DataIter):
            def __init__(self):
                super().__init__()
                self.i = 0

            def next(self, input_data):
                if self.i >= 2:
                    return 0
                half = slice(self.i * 300, (self.i + 1) * 300)
                input_data(data=X[half], label=y[half])
                self.i += 1
                return 1

            def reset(self):
                self.i = 0

        it = It()
        it.cache_prefix = str(tmp_path / "pc")
        dm = xgb.QuantileDMatrix(it, max_bin=32)
    _refused(params, dm)


@pytest.mark.parametrize("var", ["XTPU_SCAN_PROMOTE", "XTPU_MEGA",
                                 "XTPU_SCAN_ACC", "XTPU_SCAN_ACC_RMS"])
def test_the_promotion_knobs_are_gone(var):
    """The env-knob inventory (``docs/env_knobs.md``'s generator) finds no
    read of any of these variables, and no source of the package names
    one."""
    from tools.xtpulint.engine import LintConfig, RepoIndex
    from tools.xtpulint.envdoc import classify_sites

    sites = classify_sites(RepoIndex(LintConfig(root=REPO)))
    assert len(sites) > 20               # the walk found the package
    assert var not in {s.var for s in sites}
    for attr in ("AUTO_SCAN_PROMOTE", "AUTO_MEGA"):
        assert not hasattr(grow_mod, attr)
    for dirpath, _dirs, files in os.walk(os.path.join(REPO, "xgboost_tpu")):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(dirpath, fn)) as fh:
                    assert var not in fh.read(), os.path.join(dirpath, fn)


@pytest.mark.parametrize("i,method,name", [
    (0, "auto", "auto"), (1, "segment", "segment"), (2, "coarse", "coarse"),
    (3, "fused", "fused"),
])
def test_counter_counts_one_per_traced_grow_program(i, method, name):
    # a shape no other test traces, so that jit's cache cannot serve it
    rng = np.random.RandomState(i)
    X = rng.randn(1231 + i, 7).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 3, "max_bin": 32,
              "hist_method": method}
    before = grow_schedule_counts()
    bst = xgb.train(params, xgb.DMatrix(X, label=y), 2, verbose_eval=False)
    once = grow_schedule_counts()
    assert bst.num_boosted_rounds() == 2
    grew = {k: v - before.get(k, 0) for k, v in once.items()
            if v != before.get(k, 0)}
    assert grew == {name: 1}
    # the same program again: served by jit's cache, nothing traced
    xgb.train(params, xgb.DMatrix(X, label=y), 2, verbose_eval=False)
    assert grow_schedule_counts() == once
    assert f'xtpu_grow_schedule_total{{schedule="{name}"}} ' \
        in get_registry().render_prometheus()
