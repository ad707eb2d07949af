"""The eval walk from the device heap (``ops/histogram.py heap_walk_delta``).

A round's new tree is still on the device when the held-out rows want its
margin increment. ``GBTree.margin_delta_binned`` walks them down the heap
arrays there, by the two gather-free advances ``_grow`` takes for the
training rows, where a TPU runs the kernel; the CPU keeps the
``ForestPredictor``'s gather walk, so these tests open the gate with the
Pallas interpreter (``GBTree._heap_walk_interpret``, a seam for them
alone). What they hold: the same margins bit for bit, the same eval lines
and model bytes, the gate by ``xtpu_eval_walk_total{kind}``, and that the
trees stay pending through ``eval_set``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import xgboost_tpu as xgb
from test_data_iterator import BatchIter
from xgboost_tpu.boosting.gbtree import GBTree, _PendingTree
from xgboost_tpu.obs.metrics import eval_walk_counts, get_registry
from xgboost_tpu.ops.histogram import heap_walk_delta, heap_walk_takes
from xgboost_tpu.ops.pallas.histogram import ADVANCE_LEAF_MAX_NODES


def _bits(x):
    return np.ascontiguousarray(np.asarray(x, np.float32)).view(np.int32)


def _data(n, F, missing, seed):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    y = (X @ rng.randn(F) + 0.3 * rng.randn(n) > 0).astype(np.float32)
    if missing:
        X[rng.rand(n, F) < missing] = np.nan
    return X, y


def _flushes():
    return get_registry().get("xtpu_tree_flushes_total", ())


def _walk_grew(before):
    after = eval_walk_counts()
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


# (max_depth, held-out rows, F, max_bin, missing share, min_child_weight):
# every depth of the gate's range on both sides of DENSE_LEVEL_MAX and at
# its end, u8 and u16 bins, row counts off the kernel's block, F = 1 and
# the two cells' widths
CASES = [
    (1, 127, 1, 256, 0.0, 1),          # a stump, one feature
    (1, 1, 28, 255, 0.2, 1),           # one row
    (3, 2049, 28, 255, 0.2, 1),
    (3, 127, 28, 256, 0.0, 1e9),       # the root never splits
    (6, 30003, 28, 255, 0.2, 1),       # u8 bins, the missing bin 255
    (6, 2049, 220, 256, 0.0, 1),       # the ranking cell's shape
    (7, 2049, 28, 300, 0.2, 1),        # u16 bins; last level at 64 nodes
    (8, 30003, 28, 256, 0.2, 1),       # the HIGGS cells' shape, u16 (257)
    (8, 2049, 28, 255, 0.2, 40),       # leaves at every depth
    (8, 127, 220, 300, 0.2, 1),
    (10, 30003, 28, 256, 0.0, 1),      # kernel levels of 128, 256, 512
    (10, 2049, 1, 300, 0.2, 1),
]


@pytest.mark.parametrize("depth,n,F,max_bin,missing,mcw", CASES)
def test_heap_walk_matches_forest_walk_bit_for_bit(depth, n, F, max_bin,
                                                   missing, mcw):
    """``heap_walk_delta`` on the pending tree's heap against
    ``_predict_margin_binned`` on the same tree flushed: the increment the
    one-tree forest walk states (``leaf * 1.0 + 0``), to the last bit on
    every row."""
    X, y = _data(6000, F, missing, seed=depth * 1000 + n)
    Xe, _ = _data(n, F, missing, seed=depth * 1000 + n + 1)
    dtrain = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": depth,
                     "max_bin": max_bin, "min_child_weight": mcw,
                     "eta": 0.37}, dtrain, 1, verbose_eval=False)
    pending = bst.gbm._trees[0]
    assert isinstance(pending, _PendingTree) and pending.index is None
    binned = xgb.DMatrix(Xe).binned(
        max_bin, ref_cuts=dtrain.binned(max_bin).cuts)
    assert binned.bins.dtype == (
        jnp.uint8 if max_bin + bool(missing) <= 256 else jnp.uint16)
    assert heap_walk_takes(F, binned.missing_bin, depth, interpret=True)

    delta = heap_walk_delta(pending.arrays, binned.bins, binned.missing_bin,
                            depth, interpret=True)
    assert bst.gbm._trees[0] is pending          # the walk pulled nothing

    tree = bst.gbm.trees[0]                      # flushes
    margin, _ = bst.gbm._predictor(0, 1).margin_binned(
        binned.bins, binned.missing_bin, np.zeros(1, np.float32))
    # a leaf of -0.0 leaves the forest walk as +0.0 (it adds the base)
    np.testing.assert_array_equal(
        _bits(np.asarray(delta) + np.float32(0)), _bits(margin[:, 0]))
    m0 = np.random.RandomState(3).randn(n).astype(np.float32)
    np.testing.assert_array_equal(_bits(m0 + np.asarray(delta)),
                                  _bits(m0 + np.asarray(margin[:, 0])))

    # the case is what its comment says it is
    split = ~tree.is_leaf
    if mcw >= 1e9:
        assert tree.num_nodes() == 1
    elif depth == 1:
        assert tree.num_nodes() == 3
    else:
        assert len(np.unique(np.asarray(margin))) > 2
    if missing and depth >= 6:
        dl = tree.default_left[split]
        assert dl.any() and not dl.all()         # both default ways
        assert (np.asarray(binned.bins) == binned.missing_bin).any()
    if mcw == 40:
        depths = np.zeros(tree.num_nodes(), int)
        for i in range(1, tree.num_nodes()):
            depths[i] = depths[tree.parent[i]] + 1
        at = set(depths[tree.is_leaf])
        assert len(at) >= 5 and min(at) <= 3 and max(at) == depth


def test_heap_walk_takes_is_the_kernels_range(monkeypatch):
    assert not heap_walk_takes(28, 256, 8)       # the CPU: no Mosaic kernel
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    top = ADVANCE_LEAF_MAX_NODES.bit_length()    # 2^(d-1) nodes: depth 10
    assert heap_walk_takes(28, 256, 1) and heap_walk_takes(220, 256, top)
    assert not heap_walk_takes(28, 256, top + 1)
    assert not heap_walk_takes(28, 256, 0)
    assert not heap_walk_takes(28, 0x1000, 8)    # the split word's 12 bits
    assert not heap_walk_takes(0x10000, 256, 8)  # and its 16


PARAMS = {"objective": "binary:logistic", "max_depth": 5, "eta": 0.3,
          "max_bin": 64, "eval_metric": ["logloss", "error"]}


def _train_eval(params, rounds=4, data=None, **kw):
    X, y = data if data is not None else _data(3000, 7, 0.1, seed=11)
    dtrain = xgb.DMatrix(X[:2000], label=y[:2000], **kw)
    dtest = xgb.DMatrix(X[2000:], label=y[2000:], **kw)
    res = {}
    bst = xgb.train(params, dtrain, rounds,
                    evals=[(dtrain, "train"), (dtest, "test")],
                    evals_result=res, verbose_eval=False)
    return bst, res, dtest


@pytest.mark.parametrize("depth", [5, 8])
def test_train_with_evals_same_lines_and_model_bytes(monkeypatch, depth):
    """A whole job either way: the same ``evals_result`` floats, the same
    saved model bytes, the same predictions; the heap walk every round and
    no flush before the job's end."""
    params = {**PARAMS, "max_depth": depth}
    before = eval_walk_counts()
    _, res_f, _ = _train_eval(params)
    assert _walk_grew(before) == {"forest": 4}

    monkeypatch.setattr(GBTree, "_heap_walk_interpret", True)
    before, flushes = eval_walk_counts(), _flushes()
    bst_h, res_h, dtest = _train_eval(params)
    assert _walk_grew(before) == {"heap": 4}
    assert res_h == res_f
    # nothing pulled the trees while the job ran ...
    assert _flushes() == flushes
    assert all(isinstance(t, _PendingTree) for t in bst_h.gbm._trees)
    line = bst_h.eval_set([(dtest, "test")], iteration=3)
    assert all(isinstance(t, _PendingTree) for t in bst_h.gbm._trees)
    assert line == "[3]\ttest-logloss:%.6f\ttest-error:%.6f" % (
        res_f["test"]["logloss"][-1], res_f["test"]["error"][-1])
    # ... and the first reader of host trees pulls them all, once
    raw_h = bytes(bst_h.save_raw("json"))
    assert _flushes() == flushes + 1
    pred_h = bst_h.predict(dtest)

    monkeypatch.setattr(GBTree, "_heap_walk_interpret", False)
    bst_f, _, dtest = _train_eval(params)
    assert bytes(bst_f.save_raw("json")) == raw_h
    np.testing.assert_array_equal(_bits(bst_f.predict(dtest)), _bits(pred_h))


def test_several_pending_trees_are_walked_in_one_call(monkeypatch):
    """Rounds boosted without an eval between them leave several pending
    trees for one increment, batch-grown ones (sliced on the device)
    among them."""
    monkeypatch.setattr(GBTree, "_heap_walk_interpret", True)
    X, y = _data(3000, 7, 0.1, seed=11)
    dtrain = xgb.DMatrix(X[:2000], label=y[:2000])
    dtest = xgb.DMatrix(X[2000:], label=y[2000:])
    params = {k: v for k, v in PARAMS.items() if k != "eval_metric"}
    bst = xgb.train(params, dtrain, 5, verbose_eval=False)   # batches 4 + 1
    assert [t.index for t in bst.gbm._trees] == [0, 1, 2, 3, None]
    before = eval_walk_counts()
    bst.eval_set([(dtest, "test")])
    assert _walk_grew(before) == {"heap": 1}
    assert all(isinstance(t, _PendingTree) for t in bst.gbm._trees)
    state = bst._caches[id(dtest)]
    forest, _ = bst.gbm._predictor(0, 5).margin_binned(        # flushes
        state["binned"].bins, state["binned"].missing_bin,
        np.asarray(bst._base_np(), np.float32))
    # five leaves added in tree order against the forest's own reduction
    np.testing.assert_allclose(np.asarray(state["margin"]),
                               np.asarray(forest), rtol=0, atol=1e-6)
    assert np.ptp(np.asarray(forest)) > 1


def _cat_data():
    rng = np.random.RandomState(5)
    X = rng.randn(3000, 4).astype(np.float32)
    X[:, 0] = rng.randint(0, 6, 3000)
    y = ((X[:, 0] % 2 == 0) ^ (X[:, 1] > 0)).astype(np.float32)
    return X, y


@pytest.mark.parametrize("name,params,kind", [
    ("depthwise", {}, "heap"),
    ("lossguide", {"grow_policy": "lossguide", "max_leaves": 8,
                   "max_depth": 0}, "forest"),
    ("max_leaves", {"max_leaves": 6}, "forest"),
    ("categorical", {}, "forest"),
    ("num_class", {"objective": "multi:softprob", "num_class": 3,
                   "eval_metric": "mlogloss"}, "forest"),
    ("depth_11", {"max_depth": 11}, "forest"),
])
def test_gate_by_eval_walk_counter(monkeypatch, name, params, kind):
    """``xtpu_eval_walk_total{kind}``: the heap walk for the depthwise
    default, the forest walk wherever the heap is not what the host will
    state or the kernel does not take the level."""
    monkeypatch.setattr(GBTree, "_heap_walk_interpret", True)
    data, kw = None, {}
    if name == "categorical":
        data = _cat_data()
        kw = {"feature_types": ["c", "float", "float", "float"],
              "enable_categorical": True}
    elif name == "num_class":
        X, _ = _data(3000, 7, 0.1, seed=11)
        data = (X, np.random.RandomState(2).randint(0, 3, 3000).astype(
            np.float32))
    before = eval_walk_counts()
    _train_eval({**PARAMS, **params}, rounds=2, data=data, **kw)
    assert _walk_grew(before) == {kind: 2}


def test_gate_paged_matrix_and_loaded_model(monkeypatch, tmp_path):
    monkeypatch.setattr(GBTree, "_heap_walk_interpret", True)
    monkeypatch.setenv("XTPU_PAGE_ROWS", "700")
    monkeypatch.setenv("XTPU_PAGED_COLLAPSE", "0")
    X, y = _data(3000, 7, 0.1, seed=11)
    it = BatchIter(X[:2000], y[:2000], n_batches=3)
    it.cache_prefix = str(tmp_path / "pc")
    paged = xgb.QuantileDMatrix(it, max_bin=64)
    assert paged._binned.is_paged
    dtest = xgb.DMatrix(X[2000:], label=y[2000:])
    before = eval_walk_counts()
    bst = xgb.train(PARAMS, paged, 2, evals=[(dtest, "test")],
                    verbose_eval=False)
    assert _walk_grew(before) == {"forest": 2}   # the paged grower's trees

    # host trees (a model that was flushed, or loaded) over a matrix this
    # booster has not seen: the forest walk, whole
    again = xgb.DMatrix(X[2000:], label=y[2000:])
    before = eval_walk_counts()
    bst.eval_set([(again, "again")])
    assert _walk_grew(before) == {"forest": 1}

    # continuing a loaded model: its host trees by the forest walk, then
    # each new round's tree from the heap
    dtrain = xgb.DMatrix(X[:2000], label=y[:2000])
    loaded = xgb.Booster(model_file=bytearray(bst.save_raw("ubj")))
    before = eval_walk_counts()
    xgb.train(PARAMS, dtrain, 3, evals=[(dtest, "test")], xgb_model=loaded,
              verbose_eval=False)
    assert _walk_grew(before) == {"forest": 1, "heap": 2}


def test_depth_changed_between_calls(monkeypatch):
    """``set_param`` edits the growers' ``TrainParam`` in place: the walk
    reads a heap's depth off its arrays, and trees of two depths in one
    increment take the forest walk."""
    monkeypatch.setattr(GBTree, "_heap_walk_interpret", True)
    X, y = _data(3000, 7, 0.1, seed=11)
    dtrain = xgb.DMatrix(X[:2000], label=y[:2000])
    dtest = xgb.DMatrix(X[2000:], label=y[2000:])
    shallow = {**PARAMS, "max_depth": 3}
    bst = xgb.train(shallow, dtrain, 1, verbose_eval=False)
    bst.set_param({"max_depth": 5})
    assert bst.gbm._trees[0].grower.param.max_depth == 5    # edited in place
    before = eval_walk_counts()
    line = bst.eval_set([(dtest, "test")])
    assert _walk_grew(before) == {"heap": 1}
    ref = xgb.train(shallow, dtrain, 1, verbose_eval=False)
    assert ref.eval_set([(xgb.DMatrix(X[2000:], label=y[2000:]), "test")]) \
        == line

    bst = xgb.train(shallow, dtrain, 1, verbose_eval=False)
    before = eval_walk_counts()
    xgb.train({**PARAMS, "max_depth": 5}, dtrain, 1, evals=[(dtest, "test")],
              xgb_model=bst, verbose_eval=False)
    assert _walk_grew(before) == {"forest": 1}
