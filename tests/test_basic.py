"""End-to-end smoke tests: train/predict/save/load on small data."""

import json
import os

import numpy as np
import pytest

import xgboost_tpu as xgb

from conftest import make_classification, make_regression


def test_dmatrix_basic():
    X, y = make_regression(100, 5)
    dm = xgb.DMatrix(X, label=y)
    assert dm.num_row() == 100
    assert dm.num_col() == 5
    assert dm.get_label() is not None


def test_train_squarederror_reduces_rmse():
    X, y = make_regression(800, 10)
    dm = xgb.DMatrix(X, label=y)
    res = {}
    bst = xgb.train({"objective": "reg:squarederror", "max_depth": 4,
                     "eta": 0.3}, dm, num_boost_round=20,
                    evals=[(dm, "train")], evals_result=res,
                    verbose_eval=False)
    hist = res["train"]["rmse"]
    assert hist[-1] < hist[0] * 0.3, hist
    preds = bst.predict(dm)
    assert preds.shape == (800,)
    rmse = np.sqrt(np.mean((preds - y) ** 2))
    assert abs(rmse - hist[-1]) < 1e-3


def test_train_binary_logistic():
    X, y = make_classification(600, 8)
    dm = xgb.DMatrix(X, label=y)
    res = {}
    xgb.train({"objective": "binary:logistic", "max_depth": 3,
               "eval_metric": ["logloss", "auc", "error"]},
              dm, num_boost_round=20, evals=[(dm, "train")],
              evals_result=res, verbose_eval=False)
    assert res["train"]["logloss"][-1] < 0.3
    assert res["train"]["auc"][-1] > 0.9
    assert res["train"]["error"][-1] < 0.15


def test_multiclass_softprob():
    X, y = make_classification(600, 8, n_classes=4)
    dm = xgb.DMatrix(X, label=y)
    res = {}
    bst = xgb.train({"objective": "multi:softprob", "num_class": 4,
                     "max_depth": 3}, dm, num_boost_round=15,
                    evals=[(dm, "train")], evals_result=res,
                    verbose_eval=False)
    assert res["train"]["mlogloss"][-1] < 0.6
    preds = bst.predict(dm)
    assert preds.shape == (600, 4)
    np.testing.assert_allclose(preds.sum(axis=1), 1.0, rtol=1e-4)


def test_missing_values_handled():
    X, y = make_regression(500, 6, missing_frac=0.2)
    dm = xgb.DMatrix(X, label=y)
    res = {}
    xgb.train({"objective": "reg:squarederror", "max_depth": 4}, dm,
              num_boost_round=15, evals=[(dm, "train")], evals_result=res,
              verbose_eval=False)
    assert res["train"]["rmse"][-1] < res["train"]["rmse"][0]


def test_legacy_binf_model_rejected(tmp_path):
    # reference pre-JSON binary models (src/learner.cc 'binf' magic,
    # deprecated upstream) must fail with an actionable message, not a
    # JSON decode error
    p = tmp_path / "old.model"
    p.write_bytes(b"binf\x00\x00\x00\x04garbage")
    with pytest.raises(ValueError, match="legacy binary"):
        xgb.Booster(model_file=str(p))
    with pytest.raises(ValueError, match="legacy binary"):
        xgb.Booster().load_model(p.read_bytes())


def test_save_load_roundtrip(tmp_path):
    X, y = make_regression(300, 6)
    dm = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "reg:squarederror", "max_depth": 3}, dm,
                    num_boost_round=5, verbose_eval=False)
    preds = bst.predict(dm)
    for name in ("model.json", "model.ubj"):
        path = os.path.join(tmp_path, name)
        bst.save_model(path)
        bst2 = xgb.Booster(model_file=path)
        preds2 = bst2.predict(dm)
        np.testing.assert_allclose(preds, preds2, rtol=1e-5)


def test_pickle_roundtrip():
    import pickle
    X, y = make_regression(200, 5)
    dm = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "reg:squarederror"}, dm, 3,
                    verbose_eval=False)
    bst2 = pickle.loads(pickle.dumps(bst))
    np.testing.assert_allclose(bst.predict(dm), bst2.predict(dm), rtol=1e-5)


def test_eval_on_holdout():
    X, y = make_regression(1000, 8)
    dtr = xgb.DMatrix(X[:800], label=y[:800])
    dte = xgb.DMatrix(X[800:], label=y[800:])
    res = {}
    xgb.train({"objective": "reg:squarederror", "max_depth": 4}, dtr, 20,
              evals=[(dtr, "train"), (dte, "test")], evals_result=res,
              verbose_eval=False)
    assert res["test"]["rmse"][-1] < res["test"]["rmse"][0]


def test_early_stopping():
    X, y = make_regression(1000, 8)
    # noise-only holdout: should stop early
    rng = np.random.RandomState(3)
    dtr = xgb.DMatrix(X[:800], label=y[:800])
    dte = xgb.DMatrix(X[800:], label=rng.randn(200))
    bst = xgb.train({"objective": "reg:squarederror", "max_depth": 4}, dtr,
                    500, evals=[(dte, "val")], early_stopping_rounds=5,
                    verbose_eval=False)
    assert bst.num_boosted_rounds() < 500
    assert bst.attr("best_iteration") is not None


def test_base_margin():
    X, y = make_regression(300, 5)
    margin = np.full(300, 2.0, dtype=np.float32)
    dm = xgb.DMatrix(X, label=y, base_margin=margin)
    bst = xgb.train({"objective": "reg:squarederror"}, dm, 3,
                    verbose_eval=False)
    p_with = bst.predict(dm)
    dm2 = xgb.DMatrix(X, label=y)
    p_without = bst.predict(dm2)
    # margins shift predictions (trees differ too, but offset should show)
    assert not np.allclose(p_with, p_without)


def test_model_slicing():
    X, y = make_regression(300, 5)
    dm = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "reg:squarederror", "eta": 0.5}, dm, 10,
                    verbose_eval=False)
    sliced = bst[:5]
    assert sliced.num_boosted_rounds() == 5
    full = bst.predict(dm, iteration_range=(0, 5))
    np.testing.assert_allclose(sliced.predict(dm), full, rtol=1e-5)


def test_feature_importance():
    X, y = make_regression(400, 6)
    dm = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "reg:squarederror", "max_depth": 4}, dm, 5,
                    verbose_eval=False)
    for t in ("weight", "gain", "cover", "total_gain", "total_cover"):
        scores = bst.get_score(importance_type=t)
        assert scores, t
        assert all(v >= 0 for v in scores.values())


def test_fused_round_matches_general_path():
    """The single-dispatch fused round must produce bit-identical models to
    the general do_boost path (same PRNG folding, same numerics)."""
    rng = np.random.RandomState(12)
    X = rng.randn(3000, 9).astype(np.float32)
    y = (X @ rng.randn(9) > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 4,
              "subsample": 0.8, "colsample_bytree": 0.9, "seed": 5}
    b1 = xgb.train(params, xgb.DMatrix(X, label=y), 5, verbose_eval=False)
    assert b1._fused_round is not None  # fast path was taken
    b2 = xgb.Booster(params=params)
    b2._fused_blocked = True            # force the general path
    for i in range(5):
        b2.update(xgb.DMatrix(X, label=y) if i == 0 else dm2, i)
        dm2 = list(b2._caches.values())[0]["dm"]
    assert bytes(b1.save_raw("json")) == bytes(b2.save_raw("json"))


def test_round_batching_matches_sequential():
    """train() batches fused rounds K-per-dispatch when nothing consumes
    per-round output; the model must be identical to per-round updates
    (same PRNG stream, same numerics — lax.scan over the same body)."""
    import xgboost_tpu.callback as cb

    rng = np.random.RandomState(11)
    X = rng.randn(3000, 7).astype(np.float32)
    y = (X @ rng.randn(7) > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.3,
              "subsample": 0.8, "colsample_bytree": 0.8}

    b_batched = xgb.train(params, xgb.DMatrix(X, label=y), 11,
                          verbose_eval=False)
    # a no-op callback forces the per-round path
    b_seq = xgb.train(params, xgb.DMatrix(X, label=y), 11,
                      verbose_eval=False,
                      callbacks=[cb.TrainingCallback()])

    assert len(b_batched.gbm.trees) == len(b_seq.gbm.trees) == 11
    for ta, tb in zip(b_batched.gbm.trees, b_seq.gbm.trees):
        np.testing.assert_array_equal(ta.split_feature, tb.split_feature)
        np.testing.assert_array_equal(ta.split_bin, tb.split_bin)
        np.testing.assert_array_equal(ta.leaf_value, tb.leaf_value)
    dm = xgb.DMatrix(X)
    np.testing.assert_array_equal(b_batched.predict(dm), b_seq.predict(dm))


def test_fused_multiclass_matches_general_path():
    """Multiclass rounds fuse the per-class grow loop into one dispatch
    (lax.scan over the class axis); the model must be bit-identical to the
    general path's sequential per-class boosting."""
    rng = np.random.RandomState(13)
    X = rng.randn(2500, 8).astype(np.float32)
    y = (X @ rng.randn(8, 3)).argmax(axis=1).astype(np.float32)
    params = {"objective": "multi:softprob", "num_class": 3,
              "max_depth": 4, "subsample": 0.8, "colsample_bytree": 0.9,
              "seed": 7}
    b1 = xgb.train(params, xgb.DMatrix(X, label=y), 4, verbose_eval=False)
    assert b1._fused_round is not None  # multiclass takes the fast path now
    assert len(b1.gbm.trees) == 12      # 4 rounds x 3 class trees
    assert b1.gbm.tree_info == [0, 1, 2] * 4
    b2 = xgb.Booster(params=params)
    b2._fused_blocked = True            # force the general path
    dm2 = xgb.DMatrix(X, label=y)
    for i in range(4):
        b2.update(dm2, i)
    assert bytes(b1.save_raw("json")) == bytes(b2.save_raw("json"))
    # round-batched multiclass (no callbacks) == per-round fused
    from xgboost_tpu.callback import TrainingCallback

    b3 = xgb.train(params, xgb.DMatrix(X, label=y), 4, verbose_eval=False,
                   callbacks=[TrainingCallback()])
    assert bytes(b1.save_raw("json")) == bytes(b3.save_raw("json"))


def test_scanned_class_grow_matches_sequential(monkeypatch):
    """The general path's scanned per-class grow (which dart also uses)
    must be bit-identical to the truly sequential per-class loop
    (XTPU_SCAN_CLASSES=0)."""
    rng = np.random.RandomState(21)
    X = rng.randn(2000, 7).astype(np.float32)
    y = (X @ rng.randn(7, 3)).argmax(axis=1).astype(np.float32)
    params = {"objective": "multi:softprob", "num_class": 3,
              "booster": "dart", "rate_drop": 0.3, "max_depth": 3,
              "seed": 9}
    b1 = xgb.train(params, xgb.DMatrix(X, label=y), 4, verbose_eval=False)
    monkeypatch.setenv("XTPU_SCAN_CLASSES", "0")
    b2 = xgb.train(params, xgb.DMatrix(X, label=y), 4, verbose_eval=False)
    assert bytes(b1.save_raw("json")) == bytes(b2.save_raw("json"))
    assert b1.gbm.tree_info == [0, 1, 2] * 4


def test_scanned_class_grow_respects_max_leaves(monkeypatch):
    """max_leaves truncation is host-side (TreeGrower._truncate_max_leaves)
    so the scanned class grow must stand down; the model must equal the
    sequential path and honour the cap."""
    rng = np.random.RandomState(22)
    X = rng.randn(1500, 6).astype(np.float32)
    y = (X @ rng.randn(6, 3)).argmax(axis=1).astype(np.float32)
    params = {"objective": "multi:softprob", "num_class": 3,
              "max_depth": 5, "max_leaves": 4, "seed": 3}
    b1 = xgb.train(params, xgb.DMatrix(X, label=y), 3, verbose_eval=False)
    for t in b1.gbm.trees:
        assert int(t.is_leaf.sum()) <= 4
    monkeypatch.setenv("XTPU_SCAN_CLASSES", "0")
    b2 = xgb.train(params, xgb.DMatrix(X, label=y), 3, verbose_eval=False)
    assert bytes(b1.save_raw("json")) == bytes(b2.save_raw("json"))


def test_predict_returns_mutable_numpy_after_device_stump():
    """The device-resident base score must materialize to host numpy at
    predict/serialize time: predictions stay mutable np.ndarray, and the
    materialized value is cached (no repeated device pulls)."""
    X, y = make_classification(500, 6)
    dm = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 3}, dm, 3,
                    verbose_eval=False)
    p = bst.predict(dm, output_margin=True)
    assert isinstance(p, np.ndarray)
    p[0] = 0.0  # mutable
    assert isinstance(bst.base_margin_, np.ndarray)  # cached host-side
    import json
    bs = json.loads(bytes(bst.save_raw("json")))
    assert np.isfinite(bs["learner"]["learner_model_param"]
                       ["base_score"]).all()


def test_coarse_hist_matches_exact_at_small_max_bin():
    """hist_method='coarse' (two-level coarse->refine histogram): with
    max_bin <= 32 every fine bin lives inside the 32-bin refine window,
    so the search space equals the exact evaluator's and the forests must
    be BIT-identical."""
    rng = np.random.RandomState(7)
    X = rng.randn(8000, 8).astype(np.float32)
    X[rng.rand(*X.shape) < 0.05] = np.nan
    y = ((np.nan_to_num(X) @ rng.randn(8)) > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 5, "max_bin": 32}
    b_e = xgb.train(params, xgb.DMatrix(X, label=y), 4, verbose_eval=False)
    b_c = xgb.train({**params, "hist_method": "coarse"},
                    xgb.DMatrix(X, label=y), 4, verbose_eval=False)
    for te, tc in zip(b_e.gbm.trees, b_c.gbm.trees):
        np.testing.assert_array_equal(te.split_feature, tc.split_feature)
        np.testing.assert_array_equal(te.split_bin, tc.split_bin)
        np.testing.assert_allclose(te.leaf_value, tc.leaf_value,
                                   rtol=1e-5, atol=1e-6)


def test_coarse_hist_quality_at_full_max_bin():
    """At max_bin=256 the coarse path searches every coarse boundary
    exactly plus the best span's fine bins — training quality must match
    the exact path to a hair (the monotone/constraint machinery rides the
    same synthetic evaluator)."""
    rng = np.random.RandomState(11)
    X = rng.randn(12000, 8).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.1 * rng.randn(12000)).astype(
        np.float32)
    dm = xgb.DMatrix(X, label=y)
    params = {"objective": "reg:squarederror", "max_depth": 6,
              "max_bin": 256, "eval_metric": "rmse",
              "monotone_constraints": "(1,0,0,0,0,0,0,0)"}
    r_e, r_c = {}, {}
    xgb.train(params, xgb.DMatrix(X, label=y), 8, evals=[(dm, "t")],
              evals_result=r_e, verbose_eval=False)
    b_c = xgb.train({**params, "hist_method": "coarse"},
                    xgb.DMatrix(X, label=y), 8, evals=[(dm, "t")],
                    evals_result=r_c, verbose_eval=False)
    assert abs(r_e["t"]["rmse"][-1] - r_c["t"]["rmse"][-1]) \
        < 0.02 * r_e["t"]["rmse"][-1] + 1e-6
    # monotonicity holds on the coarse-trained model
    grid = np.zeros((50, 8), np.float32)
    grid[:, 0] = np.linspace(-2, 2, 50)
    p = b_c.predict(xgb.DMatrix(grid))
    assert (np.diff(p) >= -1e-5).all()


def test_coarse_hist_multiclass_and_sampling():
    """hist_method='coarse' through the class-scanned multiclass grow and
    under row/column sampling + weights — trains to comparable quality as
    the exact path."""
    rng = np.random.RandomState(3)
    n, K = 6000, 4
    X, y = make_classification(n, 10, rng=rng, n_classes=K)
    w = rng.rand(n).astype(np.float32) + 0.5
    params = {"objective": "multi:softprob", "num_class": K, "max_depth": 5,
              "subsample": 0.8, "colsample_bytree": 0.8,
              "eval_metric": "mlogloss"}
    r_e, r_c = {}, {}
    dm = xgb.DMatrix(X, label=y, weight=w)
    xgb.train(params, dm, 8,
              evals=[(dm, "t")], evals_result=r_e, verbose_eval=False)
    xgb.train({**params, "hist_method": "coarse"}, dm, 8,
              evals=[(dm, "t")], evals_result=r_c, verbose_eval=False)
    assert r_c["t"]["mlogloss"][-1] < r_c["t"]["mlogloss"][0]
    assert abs(r_e["t"]["mlogloss"][-1] - r_c["t"]["mlogloss"][-1]) < 0.05


def test_coarse_hist_unsupported_configs_raise():
    rng = np.random.RandomState(0)
    X = rng.randn(500, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    for bad in ({"tree_method": "approx"},
                {"multi_strategy": "multi_output_tree",
                 "objective": "reg:squarederror"}):
        with pytest.raises(NotImplementedError):
            xgb.train({"objective": "binary:logistic",
                       "hist_method": "coarse", **bad},
                      xgb.DMatrix(X, label=y), 1, verbose_eval=False)
    # categorical features ride beside the two-level search up to
    # CAT_SLOTS_MAX categories; a wider column rejects at trace time inside
    # _grow
    from xgboost_tpu.tree.grow import CAT_SLOTS_MAX

    for n_cat, ok in ((5, True), (CAT_SLOTS_MAX + 88, False)):
        codes = np.linspace(0, n_cat - 1, 500).round()
        Xc = np.concatenate([X, codes[:, None].astype(np.float32)], axis=1)
        dmc = xgb.DMatrix(Xc, label=y, feature_types=["q"] * 4 + ["c"],
                          enable_categorical=True)
        params = {"objective": "binary:logistic", "hist_method": "coarse"}
        if ok:
            xgb.train(params, dmc, 1, verbose_eval=False)
            continue
        with pytest.raises(NotImplementedError):
            xgb.train(params, dmc, 1, verbose_eval=False)


def test_auto_coarse_promotion_rule():
    """hist_method='auto' promotes to the two-level coarse histogram only
    on TPU, numeric row-split, wide bins, and at scale (round-5 promotion
    — quality table in docs/performance.md)."""
    from xgboost_tpu.tree.grow import (AUTO_COARSE_MIN_BINS,
                                       AUTO_COARSE_MIN_ROWS,
                                       auto_selects_coarse)

    ok = dict(numeric=True, col_split=False, backend="tpu")
    assert auto_selects_coarse(AUTO_COARSE_MIN_ROWS, 257, True, **ok)
    assert auto_selects_coarse(1 << 20, 256, False, **ok)
    # every precondition individually gates the promotion
    assert not auto_selects_coarse(AUTO_COARSE_MIN_ROWS - 1, 257, True,
                                   **ok)
    assert not auto_selects_coarse(1 << 20, AUTO_COARSE_MIN_BINS,
                                   True, **ok)  # 127 real bins < 128
    assert not auto_selects_coarse(1 << 20, 258, True, **ok)  # > 256 real
    assert not auto_selects_coarse(1 << 20, 257, True,
                                   numeric=False, col_split=False,
                                   backend="tpu")
    assert not auto_selects_coarse(1 << 20, 257, True,
                                   numeric=True, col_split=True,
                                   backend="tpu")
    # CPU keeps the exact kernel: the segment-sum build's cost is
    # bin-width-independent, so two passes would be a strict loss
    assert not auto_selects_coarse(1 << 20, 257, True,
                                   numeric=True, col_split=False,
                                   backend="cpu")
