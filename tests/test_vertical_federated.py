"""Vertical federated training E2E: column-partitioned parties, labels only
on rank 0, model must equal single-process training on the pooled columns.

Reference behaviours being mirrored: gradient/base-score/adaptive-leaf
broadcast via collective::ApplyWithLabels (src/collective/aggregator.h:36-113),
column-split best-split exchange (src/tree/hist/evaluate_splits.h:294-409),
decision-bit sync (src/tree/common_row_partitioner.h)."""

import threading

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.parallel import collective
from xgboost_tpu.parallel.collective import InMemoryCommunicator


def _column_blocks(F, world):
    """Contiguous rank-ordered feature blocks, deliberately unequal."""
    cuts = np.linspace(0, F, world + 1).astype(int)
    return [(cuts[r], cuts[r + 1]) for r in range(world)]


def _run_threads(world, fn):
    comms = InMemoryCommunicator.make_world(world)
    results = [None] * world
    errors = []

    def worker(rank):
        collective.set_thread_local_communicator(comms[rank])
        try:
            results[rank] = fn(comms[rank], rank)
        except Exception as e:  # pragma: no cover
            import traceback

            traceback.print_exc()
            errors.append(e)
        finally:
            collective.set_thread_local_communicator(None)

    threads = [threading.Thread(target=worker, args=(r,))
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    if errors:
        raise errors[0]
    return results


def _make_data(n=2000, F=9, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    w = rng.randn(F).astype(np.float32)
    y = (X @ w + 0.3 * rng.randn(n).astype(np.float32) > 0).astype(
        np.float32)
    return X, y


def _train_vertical(params, X, y, comm, rank, rounds=5):
    lo, hi = _column_blocks(X.shape[1], comm.get_world_size())[rank]
    dm = xgb.DMatrix(X[:, lo:hi], label=y if rank == 0 else None,
                     data_split_mode="col")
    p = dict(params)
    p["data_split_mode"] = "col"
    return xgb.train(p, dm, rounds, verbose_eval=False)


PARAMS = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
          "max_bin": 64}


def test_vertical_matches_pooled_inmemory():
    X, y = _make_data()
    pooled = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 5,
                       verbose_eval=False)
    pooled_dump = pooled.get_dump(with_stats=True)

    def fn(comm, rank):
        bst = _train_vertical(PARAMS, X, y, comm, rank)
        return bst.get_dump(with_stats=True)

    for dump in _run_threads(3, fn):
        assert dump == pooled_dump


@pytest.mark.slow
def test_vertical_colsample_subsample_matches_pooled():
    params = dict(PARAMS, colsample_bytree=0.7, colsample_bylevel=0.8,
                  subsample=0.8, seed=11)
    X, y = _make_data(n=1500, F=10, seed=7)
    pooled = xgb.train(params, xgb.DMatrix(X, label=y), 4,
                       verbose_eval=False)
    pooled_dump = pooled.get_dump(with_stats=True)

    def fn(comm, rank):
        return _train_vertical(params, X, y, comm, rank,
                               rounds=4).get_dump(with_stats=True)

    for dump in _run_threads(2, fn):
        assert dump == pooled_dump


def test_vertical_adaptive_leaf_matches_pooled():
    """reg:absoluteerror rewrites leaves with label quantiles — must route
    through apply_with_labels (labels only on rank 0)."""
    params = {"objective": "reg:absoluteerror", "max_depth": 3, "eta": 0.5,
              "max_bin": 64}
    rng = np.random.RandomState(5)
    X = rng.randn(1200, 6).astype(np.float32)
    y = (X @ rng.randn(6) + 0.1 * rng.randn(1200)).astype(np.float32)
    pooled = xgb.train(params, xgb.DMatrix(X, label=y), 4,
                       verbose_eval=False)
    pooled_dump = pooled.get_dump(with_stats=True)

    def fn(comm, rank):
        return _train_vertical(params, X, y, comm, rank,
                               rounds=4).get_dump(with_stats=True)

    for dump in _run_threads(3, fn):
        assert dump == pooled_dump


def test_vertical_base_score_broadcast():
    """Non-label ranks must receive the label rank's fitted base score, not
    default to zero."""
    X, y = _make_data(n=800, F=4)

    def fn(comm, rank):
        bst = _train_vertical(PARAMS, X, y, comm, rank, rounds=1)
        return float(bst.base_margin_[0])

    vals = _run_threads(2, fn)
    pooled = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 1,
                       verbose_eval=False)
    assert vals[0] == vals[1] == pytest.approx(float(pooled.base_margin_[0]))


def test_vertical_predict_and_eval_match_pooled():
    """Decision-bit prediction + apply_with_labels metric eval: every party
    gets the pooled model's predictions and eval lines."""
    X, y = _make_data(n=1600, F=8)
    Xv, yv = _make_data(n=400, F=8, seed=21)
    dtr = xgb.DMatrix(X, label=y)
    dva = xgb.DMatrix(Xv, label=yv)
    pooled_hist = {}
    pooled = xgb.train(dict(PARAMS, eval_metric=["logloss", "auc"]), dtr, 4,
                       evals=[(dva, "val")], evals_result=pooled_hist,
                       verbose_eval=False)
    pooled_pred = pooled.predict(xgb.DMatrix(Xv))

    def fn(comm, rank):
        lo, hi = _column_blocks(8, comm.get_world_size())[rank]
        dm = xgb.DMatrix(X[:, lo:hi], label=y if rank == 0 else None,
                         data_split_mode="col")
        dmv = xgb.DMatrix(Xv[:, lo:hi], label=yv if rank == 0 else None,
                          data_split_mode="col")
        hist = {}
        p = dict(PARAMS, data_split_mode="col",
                 eval_metric=["logloss", "auc"])
        bst = xgb.train(p, dm, 4, evals=[(dmv, "val")], evals_result=hist,
                        verbose_eval=False)
        return hist, bst.predict(xgb.DMatrix(Xv[:, lo:hi]))

    for hist, pred in _run_threads(3, fn):
        np.testing.assert_allclose(pred, pooled_pred, rtol=1e-5, atol=1e-6)
        for metric in ("logloss", "auc"):
            np.testing.assert_allclose(hist["val"][metric],
                                       pooled_hist["val"][metric],
                                       rtol=1e-5)


def test_vertical_requires_comm_or_mesh():
    X, y = _make_data(n=100, F=4)
    dm = xgb.DMatrix(X, label=y, data_split_mode="col")
    with pytest.raises(ValueError, match="mesh|communicator"):
        xgb.train({**PARAMS, "data_split_mode": "col"}, dm, 1,
                  verbose_eval=False)


@pytest.mark.slow
def test_vertical_matches_pooled_federated_grpc():
    """Same parity over the real gRPC federated communicator."""
    pytest.importorskip("grpc")
    from xgboost_tpu.parallel.federated import (FederatedCommunicator,
                                                run_federated_server)

    X, y = _make_data(n=1000, F=6)
    pooled = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 3,
                       verbose_eval=False)
    pooled_dump = pooled.get_dump(with_stats=True)

    world = 3
    server = run_federated_server(world, port=0)
    results = [None] * world
    errors = []

    def worker(rank):
        comm = FederatedCommunicator(f"localhost:{server.port}", world,
                                     rank, timeout=60.0)
        collective.set_thread_local_communicator(comm)
        try:
            results[rank] = _train_vertical(PARAMS, X, y, comm, rank,
                                            rounds=3).get_dump(
                                                with_stats=True)
        except Exception as e:  # pragma: no cover
            errors.append(e)
        finally:
            collective.set_thread_local_communicator(None)
            comm.close()

    threads = [threading.Thread(target=worker, args=(r,))
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(180)
    server.stop(0)
    if errors:
        raise errors[0]
    for dump in results:
        assert dump == pooled_dump


# ---------------------------------------------------------------------------
# Round-3 scope lift: categorical + monotone/interaction under vertical
# federation (reference: the column-split evaluator has no such caps,
# src/tree/hist/evaluate_splits.h:294-409; categorical decision bits ride
# the same partition-bitvector sync).


@pytest.mark.slow
def test_vertical_monotone_matches_pooled():
    rng = np.random.RandomState(31)
    n, F = 1500, 6
    X = rng.randn(n, F).astype(np.float32)
    y = (np.sin(2 * X[:, 0]) + X[:, 1]
         + 0.1 * rng.randn(n)).astype(np.float32)
    params = {"objective": "reg:squarederror", "max_depth": 4, "eta": 0.3,
              "max_bin": 64, "monotone_constraints": "(1,-1,0,0,0,0)"}
    pooled = xgb.train(params, xgb.DMatrix(X, label=y), 4,
                       verbose_eval=False)
    # structure/thresholds exact; stats excluded — the monotone clipped-gain
    # arithmetic FMA-fuses differently inside the pooled jit vs the
    # federated eager evaluator (low-order f32 bits only)
    pooled_dump = pooled.get_dump(with_stats=False)
    pooled_pred = pooled.predict(xgb.DMatrix(X))

    def fn(comm, rank):
        # every party passes the SAME global constraint config
        world = comm.get_world_size()
        lo, hi = _column_blocks(X.shape[1], world)[rank]
        bst = _train_vertical(params, X, y, comm, rank, rounds=4)
        pred = bst.predict(xgb.DMatrix(X[:, lo:hi]))
        return bst.get_dump(with_stats=False), np.asarray(pred)

    for dump, pred in _run_threads(3, fn):
        assert dump == pooled_dump
        np.testing.assert_allclose(pred, pooled_pred, rtol=1e-5, atol=1e-6)


def test_vertical_interaction_matches_pooled():
    rng = np.random.RandomState(32)
    n, F = 1500, 9
    X = rng.randn(n, F).astype(np.float32)
    # interacting pairs deliberately SPAN parties (blocks are 0-2/3-5/6-8)
    y = (X[:, 0] * X[:, 4] + X[:, 5] * X[:, 8]
         + 0.1 * rng.randn(n)).astype(np.float32)
    params = {"objective": "reg:squarederror", "max_depth": 4, "eta": 0.3,
              "max_bin": 64, "interaction_constraints": "[[0,4],[5,8]]"}
    pooled = xgb.train(params, xgb.DMatrix(X, label=y), 4,
                       verbose_eval=False)
    pooled_dump = pooled.get_dump(with_stats=True)

    def fn(comm, rank):
        return _train_vertical(params, X, y, comm, rank,
                               rounds=4).get_dump(with_stats=True)

    for dump in _run_threads(3, fn):
        assert dump == pooled_dump
    # the constraint really binds: every path stays inside one group
    groups = [{0, 4}, {5, 8}]
    for tree in pooled.gbm.trees:
        def walk(h, path):
            if tree.is_leaf[h]:
                if path:
                    assert any(path <= g for g in groups), path
                return
            path = path | {int(tree.split_feature[h])}
            walk(tree.left_child[h], path)
            walk(tree.right_child[h], path)
        walk(0, set())


@pytest.mark.slow
def test_vertical_categorical_matches_pooled():
    rng = np.random.RandomState(33)
    n, k = 1500, 8
    cat0 = rng.randint(0, k, n).astype(np.float32)   # party 0's block
    num = rng.randn(n, 3).astype(np.float32)
    cat4 = rng.randint(0, 5, n).astype(np.float32)   # party 1's block
    X = np.column_stack([cat0, num[:, :2], cat4, num[:, 2]]).astype(
        np.float32)
    ft = ["c", "float", "float", "c", "float"]
    eff = rng.randn(k)
    y = (eff[cat0.astype(int)] + num[:, 0] + 0.3 * (cat4 == 2)
         + 0.1 * rng.randn(n) > 0).astype(np.float32)
    params = {"objective": "binary:logistic", "max_depth": 4, "eta": 0.3,
              "max_bin": 64, "max_cat_to_onehot": 4}
    pooled = xgb.train(params, xgb.DMatrix(
        X, label=y, feature_types=ft, enable_categorical=True), 4,
        verbose_eval=False)
    pooled_dump = pooled.get_dump(with_stats=True)
    assert any(t.is_cat_split.any() for t in pooled.gbm.trees)
    pooled_pred = pooled.predict(xgb.DMatrix(
        X, feature_types=ft, enable_categorical=True))

    def fn(comm, rank):
        world = comm.get_world_size()
        lo, hi = _column_blocks(X.shape[1], world)[rank]
        dm = xgb.DMatrix(X[:, lo:hi], label=y if rank == 0 else None,
                         feature_types=ft[lo:hi], enable_categorical=True,
                         data_split_mode="col")
        p = dict(params, data_split_mode="col")
        bst = xgb.train(p, dm, 4, verbose_eval=False)
        pred = bst.predict(xgb.DMatrix(
            X[:, lo:hi], feature_types=ft[lo:hi], enable_categorical=True))
        return bst.get_dump(with_stats=True), np.asarray(pred)

    for dump, pred in _run_threads(2, fn):
        assert dump == pooled_dump
        np.testing.assert_allclose(pred, pooled_pred, rtol=1e-5, atol=1e-6)


def test_vertical_approx_matches_pooled():
    """tree_method=approx over vertical federated parties (VERDICT r4
    #3): each rank re-sketches only the columns it owns with the
    broadcast hessians (per-feature sketches are independent, so local
    cuts equal the pooled run's), then the standard best-split /
    decision-bit exchange runs unchanged — reference updater_approx.cc
    under DataSplitMode::kCol."""
    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
              "max_bin": 64, "tree_method": "approx"}
    X, y = _make_data(n=1500, F=9, seed=13)
    pooled = xgb.train(params, xgb.DMatrix(X, label=y), 4,
                       verbose_eval=False)
    pooled_dump = pooled.get_dump(with_stats=True)

    def fn(comm, rank):
        return _train_vertical(params, X, y, comm, rank,
                               rounds=4).get_dump(with_stats=True)

    for dump in _run_threads(3, fn):
        assert dump == pooled_dump


def test_vertical_lossguide_matches_pooled():
    """grow_policy=lossguide over vertical parties (VERDICT r4 #4): the
    greedy pop loop replicates on every rank; winners cross through one
    allgather per split and rows advance via the owner's decision bits.
    Dump equality against the pooled lossguide run, stats included."""
    params = {"objective": "binary:logistic", "eta": 0.3, "max_bin": 64,
              "grow_policy": "lossguide", "max_leaves": 8, "max_depth": 0}
    X, y = _make_data(n=1800, F=9, seed=21)
    pooled = xgb.train(params, xgb.DMatrix(X, label=y), 4,
                       verbose_eval=False)
    pooled_dump = pooled.get_dump(with_stats=True)

    def fn(comm, rank):
        return _train_vertical(params, X, y, comm, rank,
                               rounds=4).get_dump(with_stats=True)

    for dump in _run_threads(3, fn):
        assert dump == pooled_dump


def test_vertical_lossguide_monotone_interaction_matches_pooled():
    """Structure/threshold/leaf parity. Stats are compared WITHOUT gains:
    the monotone gain recompute (clipped-weight path) drifts in the
    low-order f32 bits between the pooled width-F eval and the local
    width-F_loc eval (XLA vectorises the two widths differently on CPU)
    — splits, sums and thresholds stay bit-identical, verified by spying
    the pq payloads."""
    params = {"objective": "reg:squarederror", "eta": 0.4, "max_bin": 64,
              "grow_policy": "lossguide", "max_leaves": 6, "max_depth": 0,
              "monotone_constraints": "(1,-1,0,0,0,0)",
              "interaction_constraints": "[[0,1,2],[2,3,4,5]]"}
    rng = np.random.RandomState(31)
    X = rng.randn(1200, 6).astype(np.float32)
    y = (X[:, 0] - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
         + 0.1 * rng.randn(1200)).astype(np.float32)
    pooled = xgb.train(params, xgb.DMatrix(X, label=y), 3,
                       verbose_eval=False)
    pooled_dump = pooled.get_dump(with_stats=False)
    pooled_pred = pooled.predict(xgb.DMatrix(X))

    def fn(comm, rank):
        bst = _train_vertical(params, X, y, comm, rank, rounds=3)
        lo, hi = _column_blocks(X.shape[1], comm.get_world_size())[rank]
        pred = bst.predict(xgb.DMatrix(X[:, lo:hi],
                                       data_split_mode="col"))
        return bst.get_dump(with_stats=False), pred

    for dump, pred in _run_threads(2, fn):
        assert dump == pooled_dump
        np.testing.assert_allclose(pred, pooled_pred, rtol=1e-5,
                                   atol=1e-6)


def test_vertical_dart_matches_pooled():
    """booster=dart over vertical parties (r5 lift): the dropout draws
    key off the replicated iteration counter, so every rank drops the
    same trees; tree growth itself is the depthwise vertical protocol."""
    params = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.4,
              "max_bin": 64, "booster": "dart", "rate_drop": 0.5,
              "seed": 5}
    X, y = _make_data(n=1500, F=8, seed=23)
    pooled = xgb.train(params, xgb.DMatrix(X, label=y), 5,
                       verbose_eval=False)
    pooled_dump = pooled.get_dump(with_stats=True)

    def fn(comm, rank):
        return _train_vertical(params, X, y, comm, rank,
                               rounds=5).get_dump(with_stats=True)

    for dump in _run_threads(2, fn):
        assert dump == pooled_dump


def test_vertical_coarse_hist_method_warns_and_falls_back():
    """hist_method='coarse'/'fused' is a row-split resident/paged scheme;
    the vertical federated growers now degrade to the exact one-pass
    kernels with a warning instead of raising. Asserted single-threaded
    on the grower constructors — warning capture is process-global and
    must stay out of the multi-rank thread harness."""
    from xgboost_tpu.tree.param import TrainParam
    from xgboost_tpu.tree.vertical import (VerticalFederatedGrower,
                                           VerticalLossguideGrower)

    X, y = _make_data(n=300, F=4)
    binned = xgb.DMatrix(X, label=y).binned(32)
    for cls, extra in ((VerticalFederatedGrower, {}),
                       (VerticalLossguideGrower, {"max_leaves": 6})):
        param = TrainParam()
        param.update_allow_unknown({"max_depth": 3, **extra})
        for hm, resolved in (("coarse", "auto"), ("fused", "auto")):
            with pytest.warns(UserWarning, match="requires row split"):
                g = cls(param, binned.max_nbins, binned.cuts,
                        hist_method=hm)
            assert g.hist_method == resolved
            assert not getattr(g, "_coarse", False)
            assert not getattr(g, "_fused", False)
