"""Row-split training over a device mesh, fed by an iterator, against the
benchmark's blockwise reference (``benchmark/lib/reference_blocks.py``: numpy,
float64 sums, nothing of the program) on seeded data on the CPU mesh. The
reference is loaded by path, under a package name of its own, so nothing is
put on ``sys.path``."""

import importlib
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import xgboost_tpu as xgb
from xgboost_tpu.data import quantile
from xgboost_tpu.obs.metrics import mesh_counts
from xgboost_tpu.obs.trace import MESH_SCOPES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARAMS = {"objective": "binary:logistic", "max_depth": 3, "eta": 0.3,
          "max_bin": 256, "tree_method": "hist"}
ROWS, FEATURES, BATCH = 8000, 6, 2000


def _lib(module):
    name = "xtpu_benchlib"
    if name not in sys.modules:
        lib = os.path.join(ROOT, "benchmark", "lib")
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(lib, "__init__.py"),
            submodule_search_locations=[lib])
        pkg = importlib.util.module_from_spec(spec)
        sys.modules[name] = pkg
        spec.loader.exec_module(pkg)
    return importlib.import_module(name + "." + module)


def _data(seed=11, rows=ROWS):
    """Tie-free for the splits that matter: every feature takes 64 equally
    likely levels, so the program's sketch and the reference's exact cuts
    are the same set (every level is a cut), and a strong staircase score
    keeps the best gain of a node far from the second."""
    rng = np.random.RandomState(seed)
    X = (rng.randint(0, 64, (rows, FEATURES)) / 8.0).astype(np.float32)
    z = (1.5 * (X[:, 0] > 4.1) + 1.1 * (X[:, 1] > 2.6) * (X[:, 2] > 5.2)
         - 0.9 * (X[:, 3] > 6.3) + 0.2 * X[:, 4])
    y = (z + 0.15 * rng.randn(rows) > 1.2).astype(np.float32)
    return X, y


class _Batches(xgb.DataIter):
    def __init__(self, X, y, batch=BATCH):
        super().__init__()
        self.X, self.y, self.batch, self.at = X, y, batch, 0

    def reset(self):
        self.at = 0

    def next(self, input_data):
        if self.at >= len(self.y):
            return 0
        s = slice(self.at, self.at + self.batch)
        input_data(data=self.X[s], label=self.y[s])
        self.at += self.batch
        return 1


def _mesh(n=4):
    return xgb.make_data_mesh(n)


def _trees(bst):
    model = json.loads(bytes(bst.save_raw("json")))["learner"]
    return [{"left": np.asarray(t["left_children"], np.int64),
             "right": np.asarray(t["right_children"], np.int64),
             "feat": np.asarray(t["split_indices"], np.int64),
             "thr": np.asarray(t["split_conditions"], np.float32),
             "value": np.asarray(t["split_conditions"], np.float32),
             "sum_hess": np.asarray(t["sum_hessian"], np.float64),
             "gain": np.asarray(t["loss_changes"], np.float64)}
            for t in model["gradient_booster"]["trees"]], \
        float(model["learner_model_param"]["base_score"][0])


def _margin(bst, dm):
    return np.asarray(bst._state_of(dm, is_train=True)["margin"],
                      np.float32).reshape(-1)


def test_blockwise_reference_equals_the_plain_reference():
    ref, rb = _lib("reference"), _lib("reference_blocks")
    X, y = _data(rows=3000)
    whole = ref.train(X, y, PARAMS, 3)
    blocks = rb.train(rb.array_source(X, y, 700), PARAMS, 3)
    assert blocks["base_margin"] == whole["base_margin"]
    for a, b in zip(whole["trees"], blocks["trees"]):
        for k in ("left", "right", "feat", "thr"):
            assert np.array_equal(a[k], b[k]), k
        # float64 sums in another order (by block, not by thread)
        assert np.allclose(a["value"], b["value"], rtol=1e-6, atol=1e-9)
        assert np.allclose(a["sum_hess"], b["sum_hess"], rtol=1e-9)
    assert np.allclose(whole["margin"], blocks["margin"], atol=1e-6)
    # and its streaming comparison reads the plain reference's own trees as
    # sound: every node sum, leaf and gain from raw-value routing
    out = {"trees": whole["trees"], "warm_rounds": 1,
           "base_margin": whole["base_margin"], "margin": whole["margin"],
           "replica_gap": 0.0, "rounds_claimed": 3}
    got = rb.numbers(out, rb.array_source(X, y, 700), PARAMS, 2, 1, 2)
    assert got["node_hess_gap"] < 1e-9 and got["gain_gap"] < 1e-9
    assert got["leaf_gap"] < 1e-6          # leaves are stated in float32
    assert got["split_gap"] == 0.0 and got["margin_gap"] == 0.0
    assert got["rounds_gap"] == 0.0


def test_iterator_and_mesh_against_the_blockwise_reference():
    rb = _lib("reference_blocks")
    X, y = _data()
    dm = xgb.QuantileDMatrix(_Batches(X, y), max_bin=256)
    bst = xgb.train({**PARAMS, "mesh": _mesh()}, dm, 3, verbose_eval=False)
    source = rb.array_source(X, y, BATCH)
    want = rb.train(source, PARAMS, 3)
    trees, base = _trees(bst)
    assert abs(base - want["base_margin"]) < 1e-6
    for got, ref_tree in zip(trees, want["trees"]):
        inner = np.flatnonzero(got["left"] >= 0)
        # compact ids (the program) against heap ids (the reference): walk
        # both from the root
        pairs, stack = [], [(0, 0)]
        while stack:
            a, b = stack.pop()
            pairs.append((a, b))
            if got["left"][a] >= 0:
                assert ref_tree["left"][b] >= 0, "the reference has a leaf"
                stack += [(got["left"][a], 2 * b + 1),
                          (got["right"][a], 2 * b + 2)]
            else:
                assert ref_tree["left"][b] < 0, "the program has a leaf"
        assert len(inner) and len(pairs) == len(got["left"])
        for a, b in pairs:
            if got["left"][a] >= 0:
                assert got["feat"][a] == ref_tree["feat"][b]
                assert got["thr"][a] == ref_tree["thr"][b]
            else:
                # float32 histogram sums (XLA:CPU's segment build) against
                # float64: a leaf of a few rows beside a large sibling is
                # its parent's sum less its sibling's
                assert abs(got["value"][a] - ref_tree["value"][b]) < 2e-3
    # the margin: float32 leaf values added in the same order
    assert np.abs(_margin(bst, dm) - want["margin"]).max() < 5e-3
    # and the streaming comparison, held to the cell's own limits
    out = {"trees": trees, "warm_rounds": 1, "base_margin": base,
           "margin": _margin(bst, dm), "replica_gap": 0.0,
           "rounds_claimed": 3}
    got = rb.numbers(out, source, PARAMS, 2, 1, 2)
    limits = json.load(open(os.path.join(
        ROOT, "benchmark", "limits", "criteo-ctr.mesh-train.json")))
    assert set(got) == set(limits)
    assert got["margin_gap"] == 0.0 and got["split_gap"] == 0.0
    assert got["node_hess_gap"] < limits["node_hess_gap"]
    assert got["gain_gap"] < limits["gain_gap"]


def test_iterator_mesh_equals_in_memory_mesh_equals_one_device():
    X, y = _data()
    mesh = {**PARAMS, "mesh": _mesh()}
    by_iter = xgb.train(mesh, xgb.QuantileDMatrix(_Batches(X, y)), 3,
                        verbose_eval=False)
    in_memory = xgb.train(mesh, xgb.DMatrix(X, label=y), 3,
                          verbose_eval=False)
    one = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 3, verbose_eval=False)
    raw = [bytes(b.save_raw("json")) for b in (by_iter, in_memory, one)]
    assert raw[0] == raw[1]
    # the CPU mesh sums each shard's float32 histogram and then the shards:
    # another order than one device's, so equal splits and leaves to float32
    for a, b in zip(_trees(by_iter)[0], _trees(one)[0]):
        assert np.array_equal(a["feat"], b["feat"])
        assert np.array_equal(a["left"], b["left"])
        inner = a["left"] >= 0
        assert np.array_equal(a["thr"][inner], b["thr"][inner])
        assert np.allclose(a["value"], b["value"], rtol=1e-4, atol=1e-6)


def test_the_shares_add_up():
    """Four shards' histograms of one level, each built alone, sum to the
    one-device histogram; the split search, which every chip computes alike
    from the summed histogram, is counted once: it reads the same splits
    from the sum as from the one-device histogram."""
    from xgboost_tpu.ops.histogram import build_hist
    from xgboost_tpu.ops.split import evaluate_splits
    from xgboost_tpu.tree.param import TrainParam

    X, y = _data()
    dm = xgb.DMatrix(X, label=y)
    bins = np.asarray(dm.binned(256).bins)
    n_real = jnp.asarray(dm.binned(256).n_real_bins())
    rng = np.random.RandomState(5)
    gpair = np.stack([rng.randn(ROWS), rng.rand(ROWS) + 0.1],
                     axis=1).astype(np.float32)
    rel = rng.randint(0, 4, ROWS).astype(np.int32)       # a level of 4 nodes
    nb = dm.binned(256).max_nbins

    def hist(rows):
        return np.asarray(build_hist(jnp.asarray(bins[rows]),
                                     jnp.asarray(gpair[rows]),
                                     jnp.asarray(rel[rows]), 4, nb))
    whole = hist(slice(None))
    shares = sum(hist(slice(k * ROWS // 4, (k + 1) * ROWS // 4))
                 for k in range(4))
    # every row in exactly one share: the counts (hessian-free) are exact
    assert np.allclose(shares, whole, rtol=1e-5, atol=1e-4)
    assert np.isclose(shares[..., 1].sum() / FEATURES, gpair[:, 1].sum(),
                      rtol=1e-5)
    param = TrainParam(max_depth=3)
    parent = jnp.asarray(whole[:, 0].sum(axis=1))
    a = evaluate_splits(jnp.asarray(whole), parent, n_real, param,
                        has_missing=dm.binned(256).has_missing)
    b = evaluate_splits(jnp.asarray(shares), parent, n_real, param,
                        has_missing=dm.binned(256).has_missing)
    assert np.array_equal(np.asarray(a.feature), np.asarray(b.feature))
    assert np.array_equal(np.asarray(a.bin), np.asarray(b.bin))


def test_no_device_holds_more_than_its_shard_after_ingest(monkeypatch):
    X, y = _data()
    put = []
    real = jax.device_put

    def spy(x, device=None, *a, **kw):
        if isinstance(device, jax.Device):
            put.append(tuple(np.shape(x)))
        return real(x, device, *a, **kw)
    monkeypatch.setattr(jax, "device_put", spy)
    dm = xgb.QuantileDMatrix(_Batches(X, y), max_bin=256)
    # nothing is on a device until a booster says where it goes
    assert dm.__dict__["_host_bins"] is not None
    bst = xgb.train({**PARAMS, "mesh": _mesh()}, dm, 1, verbose_eval=False)
    state = bst._state_of(dm, is_train=True)
    bins = state["binned"].bins
    assert bins.shape == (ROWS, FEATURES)
    assert len({s.device for s in bins.addressable_shards}) == 4
    assert all(s.data.shape == (ROWS // 4, FEATURES)
               for s in bins.addressable_shards)
    for arr in (state["margin"], state["info"].labels_device()):
        assert {s.data.shape[0] for s in arr.addressable_shards} \
            == {ROWS // 4}
    # the whole matrix was never handed to one device, is not alive on one
    # now, and the host copy is gone
    assert (ROWS, FEATURES) not in put and (ROWS // 4, FEATURES) in put
    assert dm.__dict__["_host_bins"] is None
    whole_on_one = [a for a in jax.live_arrays()
                    if a.shape == (ROWS, FEATURES) and a.dtype == jnp.uint8
                    and len(a.sharding.device_set) == 1]
    assert not whole_on_one
    counts = mesh_counts()
    assert counts["shards"] == 4 and counts["rows_per_shard"] == ROWS // 4


def test_rows_that_do_not_fill_the_shards_are_padded_with_weight_zero():
    X, y = _data(rows=ROWS - 3)
    dm = xgb.QuantileDMatrix(_Batches(X, y), max_bin=256)
    bst = xgb.train({**PARAMS, "mesh": _mesh()}, dm, 2, verbose_eval=False)
    one = xgb.train(PARAMS, xgb.DMatrix(X, label=y), 2, verbose_eval=False)
    state = bst._state_of(dm, is_train=True)
    assert state["binned"].bins.shape[0] == ROWS
    assert np.asarray(state["info"].weights)[-3:].sum() == 0
    for a, b in zip(_trees(bst)[0], _trees(one)[0]):
        assert np.array_equal(a["feat"], b["feat"])
        # float32 sums in another order; the pad rows add nothing
        assert np.allclose(a["sum_hess"], b["sum_hess"], rtol=1e-4,
                           atol=5e-3)
    # the padded matrix is the booster's; the DMatrix keeps its own rows
    assert dm.num_row() == ROWS - 3
    assert bst.predict(dm).shape == (ROWS - 3,)


@pytest.mark.parametrize("ingest", ["in_memory", "iterator"])
def test_a_row_above_the_samples_maximum_trains_as_it_predicts(monkeypatch,
                                                               ingest):
    """The default sketch works on a strided sample of the rows. A split on
    a feature's last real bin (its values to the left, the missing to the
    right) sent a row above the SAMPLE's maximum left in training, by its
    clamped bin, and right in ``predict``, by its value. The last cut now
    lies above the column's true maximum."""
    monkeypatch.setattr(quantile, "SKETCH_SAMPLE_ROWS", 2000)
    rng = np.random.default_rng(3)
    n = 40000
    X = rng.standard_normal((n, 3)).astype(np.float32)
    stride = -(-n // 2000)                  # of both sketches at this size
    skipped = np.arange(n)[np.arange(n) % stride != 0][::37][:200]
    X[skipped, 0] = 6.0 + rng.random(len(skipped)).astype(np.float32)
    missing = rng.random(n) < 0.3
    missing[skipped] = False
    X[missing, 0] = np.nan
    y = (missing ^ (rng.random(n) < 0.02)).astype(np.float32)
    dm = xgb.DMatrix(X, label=y) if ingest == "in_memory" \
        else xgb.QuantileDMatrix(_Batches(X, y, 10000), max_bin=256)
    cuts = dm.binned(256).cuts
    assert X[::stride, 0][~np.isnan(X[::stride, 0])].max() < 6.0
    assert cuts.values[cuts.ptrs[1] - 1] >= np.nanmax(X[:, 0])
    bst = xgb.train({**PARAMS, "max_depth": 2, "eta": 0.5}, dm, 2,
                    verbose_eval=False)
    assert 0 in _trees(bst)[0][0]["feat"][:1]     # the root splits on it
    walked = bst.predict(xgb.DMatrix(X), output_margin=True)
    assert np.abs(_margin(bst, dm) - walked).max() < 1e-5


def test_cover_maxima_moves_only_a_last_cut_below_its_columns_maximum():
    cuts = quantile.cuts_from_summaries(
        [quantile.FeatureSummary.from_data(np.arange(10, dtype=np.float32)),
         quantile.FeatureSummary.from_data(np.arange(5, dtype=np.float32))],
        256)
    same = quantile.cover_maxima(cuts, np.asarray([9.0, -np.inf]))
    assert same is cuts
    raised = quantile.cover_maxima(cuts, np.asarray([9.0, 7.5]))
    assert np.array_equal(raised.values[:10], cuts.values[:10])
    assert raised.values[-1] >= np.float32(7.5)
    assert np.array_equal(raised.values[10:-1], cuts.values[10:-1])


def test_mesh_scopes_are_in_the_round_program_and_the_counters_count():
    X, y = _data()
    dm = xgb.DMatrix(X, label=y)
    before = mesh_counts()
    bst = xgb.train({**PARAMS, "mesh": _mesh()}, dm, 2, verbose_eval=False)
    after = mesh_counts()
    grew = {k: after["allreduce"].get(k, 0) - before["allreduce"].get(k, 0)
            for k in after["allreduce"]}
    # a root sum a tree; a histogram exchange a level; nothing unscoped
    assert grew["root_psum"] == 2
    assert grew["hist_psum"] == 2 * PARAMS["max_depth"]
    assert set(grew) <= set(MESH_SCOPES)
    nb = bst._state_of(dm, is_train=True)["binned"].max_nbins
    level_bytes = sum(2 ** d for d in range(PARAMS["max_depth"])) \
        * FEATURES * nb * 2 * 4
    assert after["bytes"]["hist_psum"] - before["bytes"].get(
        "hist_psum", 0) == 2 * level_bytes
    # the scopes are in the lowered round program's text
    grower = bst.gbm._grower_for(bst._state_of(dm, is_train=True)["binned"])
    text = grower.sharded_program().lower(
        jax.ShapeDtypeStruct((ROWS, FEATURES), jnp.uint8),
        jax.ShapeDtypeStruct((ROWS, 2), jnp.float32),
        jax.ShapeDtypeStruct((FEATURES,), jnp.int32),
        jax.ShapeDtypeStruct((FEATURES,), jnp.bool_),
        jax.ShapeDtypeStruct((2,), jnp.uint32), None, None, None).as_text(
            debug_info=True)
    assert "mesh.hist_psum" in text and "mesh.root_psum" in text
    # a continuation call rebinds its grower and must not trace again
    assert grower.sharded_program() is xgb.tree.grow._mesh_program(
        grower.mesh, grower.param, grower.max_nbins, grower.hist_method,
        grower.has_missing, grower.split_mode)
