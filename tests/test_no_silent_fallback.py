"""Nothing on the train path swallows a failure of its device program, and
a native library built elsewhere is rebuilt, never loaded."""

import os
import shutil

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu import core, native


class _Refused(RuntimeError):
    pass


def _refuse(*_a, **_kw):
    raise _Refused("the device refused this program")


def _data():
    rng = np.random.RandomState(3)
    X = rng.randn(300, 5).astype(np.float32)
    y = (X[:, 0] + X[:, 1] > 0).astype(np.float32)
    return xgb.DMatrix(X, label=y)


PARAMS = {"objective": "binary:logistic", "max_depth": 2,
          "eval_metric": "logloss"}


def test_batched_round_program_failure_raises(monkeypatch):
    monkeypatch.setattr(core, "_fused_multi_round_fn", _refuse)
    with pytest.raises(_Refused):
        xgb.train(PARAMS, _data(), 4, verbose_eval=False)


def test_fused_round_program_failure_raises(monkeypatch):
    monkeypatch.setattr(core, "_fused_round_fn", _refuse)
    dm = _data()
    with pytest.raises(_Refused):
        xgb.train(PARAMS, dm, 2, evals=[(dm, "train")], verbose_eval=False)


def test_eval_program_failure_raises(monkeypatch):
    monkeypatch.setattr(core, "_eval_partials_fn", _refuse)
    dm = _data()
    with pytest.raises(_Refused):
        xgb.train(PARAMS, dm, 2, evals=[(dm, "train")], verbose_eval=False)


@pytest.mark.skipif(shutil.which("g++") is None, reason="needs g++")
def test_native_library_with_foreign_digest_is_rebuilt(tmp_path, monkeypatch):
    built = native.load()
    assert built is not None
    # a checkout copied from another machine: sources, the library that
    # machine built, and the digest that machine recorded for it
    there = tmp_path / "native"
    shutil.copytree(native._NATIVE_DIR, there)
    lib_path = there / native._LIB_NAME
    assert lib_path.exists()
    (there / (native._LIB_NAME + ".digest")).write_text("0" * 64)
    monkeypatch.setattr(native, "_NATIVE_DIR", str(there))
    monkeypatch.setattr(native, "_lib", None)

    events = []
    real_build, real_cdll = native._build, native.ctypes.CDLL
    monkeypatch.setattr(native, "_build", lambda *a: (
        events.append("build"), real_build(*a))[1])
    monkeypatch.setattr(native.ctypes, "CDLL", lambda p: (
        events.append("dlopen"), real_cdll(p))[1])
    assert native.load() is not None
    assert events == ["build", "dlopen"]
    assert native._cached_digest(str(lib_path)) == native._digest()

    # the digest now matches: the next process loads without building
    monkeypatch.setattr(native, "_lib", None)
    del events[:]
    assert native.load() is not None
    assert events == ["dlopen"]
    assert not [f for f in os.listdir(there) if f.endswith(".tmp")]
