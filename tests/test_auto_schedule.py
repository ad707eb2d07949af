"""What ``hist_method="auto"`` resolves to, and the counter that says so.

On a TPU backend ``auto`` promotes to the two-level search as the FUSED
sweep and no further (PERF.md section 6, PR 28: the row sort and the
permute of ``scan`` / ``mega`` were 92% of a round on the chip). CPU runs
never promote, so the backend is patched here; what the chip does with the
schedule is ``chip_smoke.py``'s and the benchmark's to show."""

import os

import jax
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.obs.metrics import get_registry, grow_schedule_counts
from xgboost_tpu.tree import grow as grow_mod
from xgboost_tpu.tree.grow import resolve_schedule
from xgboost_tpu.tree.param import TrainParam

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, BINS = 10_500_000, 256          # the benchmark cells' shape


@pytest.fixture
def tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _resolve(method, n=ROWS, max_nbins=BINS, *, depth=8, numeric=True,
             col_split=False, sharded=False, **param):
    return resolve_schedule(
        method, n, max_nbins, False, TrainParam(max_depth=depth, **param),
        numeric=numeric, col_split=col_split, sharded=sharded)


@pytest.mark.parametrize("sharded", [False, True], ids=["one-chip", "mesh"])
@pytest.mark.parametrize("depth", [6, 8, 10])
def test_auto_is_fused_on_tpu(tpu_backend, depth, sharded):
    sched = _resolve("auto", depth=depth, sharded=sharded)
    assert sched.name == "fused"
    assert sched.coarse and not (sched.scan or sched.mega)


@pytest.mark.parametrize("kw", [
    {"n": grow_mod.AUTO_COARSE_MIN_ROWS - 1},
    {"max_nbins": grow_mod.AUTO_COARSE_MIN_BINS - 1},
    {"max_nbins": 257},
    {"numeric": False},
    {"col_split": True, "sharded": True},
], ids=["few-rows", "narrow-bins", "over-256-bins", "categorical",
        "col-split"])
def test_auto_keeps_the_one_pass_kernel_under_each_threshold(tpu_backend,
                                                            kw):
    sched = _resolve("auto", **kw)
    assert sched.name == "auto"
    assert not (sched.coarse or sched.fused or sched.scan or sched.mega)


def test_auto_never_promotes_on_cpu():
    assert jax.default_backend() == "cpu"
    assert _resolve("auto").name == "auto"


@pytest.mark.parametrize("method,kw,name", [
    ("scan", {"depth": 6}, "scan"),
    ("scan", {"depth": 8}, "scan"),
    ("mega", {"depth": 6}, "mega"),
    ("mega", {"depth": 8}, "scan"),      # 2^8 > DENSE_LEVEL_MAX: unrolled
    ("mega", {"depth": 6, "colsample_bynode": 0.5}, "scan"),
    ("fused", {"depth": 8}, "fused"),
    ("coarse", {"depth": 8}, "coarse"),
    ("pallas", {"depth": 8}, "pallas"),
])
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_explicit_schedules_resolve_as_before(monkeypatch, backend, method,
                                              kw, name):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    sched = _resolve(method, **kw)
    assert sched.name == name
    assert sched.scan == (name in ("scan", "mega"))


@pytest.mark.parametrize("method,n,flags", [
    ("auto", 1 << 20, (True, True, False)),      # fused, as depthwise
    ("auto", grow_mod.AUTO_COARSE_MIN_ROWS - 1, (False, False, False)),
    ("fused", 1 << 20, (True, True, False)),
    ("scan", 1 << 20, (True, True, True)),
    ("mega", 1 << 20, (True, True, True)),
])
def test_lossguide_auto_follows_the_depthwise_one(tpu_backend, method, n,
                                                  flags):
    from xgboost_tpu.tree.lossguide import LossguideGrower
    from xgboost_tpu.tree.programs import _NumericCuts

    grower = LossguideGrower(TrainParam(max_leaves=8), BINS,
                             _NumericCuts(28), hist_method=method,
                             has_missing=False)
    grower._resolve_schedule(n)
    assert (bool(grower._coarse), bool(grower._fused),
            bool(grower._scan)) == flags


@pytest.mark.parametrize("var", ["XTPU_SCAN_PROMOTE", "XTPU_MEGA"])
def test_the_promotion_knobs_are_gone(var):
    """The env-knob inventory (``docs/env_knobs.md``'s generator) finds no
    read of either variable, and no source of the package names one."""
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
    (3, "fused", "fused"), (4, "scan", "scan"), (5, "mega", "mega"),
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
