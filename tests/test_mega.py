"""One-dispatch-per-tree megakernel tier (hist_method="mega", r14).

The mega tier rolls the whole per-tree level loop into a single compiled
program: depthwise runs the level stages inside one ``lax.fori_loop``
with traced ``(lo, n_level)`` carries (tree/grow.py ``_mega_body``), and
lossguide replays the host heapq greedy order in-trace over compact
``cap``-padded node arrays (tree/lossguide.py ``_mega_greedy_loop``).
Neither reorders any arithmetic relative to the scan formulation, so the
bar everywhere is strict bit-parity — pinned at two altitudes:

- model:    trains with hist_method 'mega' vs 'scan' — resident
            depthwise (+missing, option grid, multiclass), lossguide
            (+missing, fallback tiers), paged external memory, mesh
            row/col splits x both growers — identical dumps AND
            byte-identical ``save_raw`` after normalising the stored
            hist_method param string (tools/validate_mega.py runs the
            same contract over the full promotion grid);
- dispatch: a steady resident boosting round is <=2 compiled-program
            launches (the fused round megakernel + the NaN-guard
            reduction) and retriggers ZERO compilations — the
            bounded-shape carries never re-trace
            (``test_mega_dispatch_count_resident``).

Plus the satellites that ride along: the root-level (n_nodes==1)
counting-sort identity path must stay traceable under ``shard_map`` with
the replication checker ON (the sort primitive has no replication rule;
ops/partition.py switches to a cumsum counting rank), and
``XTPU_SCAN_ACC=auto`` resolves to bf16/f32 through the measured RMS
error-bound probe (ops/histogram.py ``resolve_scan_acc``).
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import xgboost_tpu as xgb
from xgboost_tpu.context import DATA_AXIS
from xgboost_tpu.ops.partition import counting_sort_by_node

P = jax.sharding.PartitionSpec


def _binary_data(n=2500, F=8, missing=False, seed=11):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F).astype(np.float32)
    y = (np.nan_to_num(X) @ rng.randn(F) > 0).astype(np.float32)
    if missing:
        X[rng.rand(n, F) < 0.1] = np.nan
    return X, y


def _norm_raw(raw):
    """save_raw stores the hist_method param string — the tree bytes are
    the parity surface, so normalise the label before comparing."""
    return bytes(raw).replace(b"i\x04mega", b"i\x04scan")


def _assert_parity(params, X, y, rounds=4):
    """Train scan vs mega on the same data: dumps equal, raw bytes equal."""
    b_s = xgb.train({**params, "hist_method": "scan"},
                    xgb.DMatrix(X, label=y), rounds, verbose_eval=False)
    b_m = xgb.train({**params, "hist_method": "mega"},
                    xgb.DMatrix(X, label=y), rounds, verbose_eval=False)
    assert b_m.get_dump(with_stats=True) == b_s.get_dump(with_stats=True)
    assert _norm_raw(b_m.save_raw()) == _norm_raw(b_s.save_raw())


# ---------------------------------------------------------------- model


@pytest.mark.parametrize("missing", [False, True])
def test_mega_train_depthwise_matches_scan(missing):
    X, y = _binary_data(missing=missing)
    _assert_parity({"objective": "binary:logistic", "eta": 0.3,
                    "max_bin": 64, "max_depth": 4}, X, y)


@pytest.mark.parametrize("extra", [
    # two merged configs, not one-option-per-cell: every distinct param
    # set compiles scan AND mega from scratch, so compile count (not the
    # option count) is this grid's wall-clock cost
    {"gamma": 0.5, "min_child_weight": 5.0},
    {"colsample_bytree": 0.6, "subsample": 0.8,
     "reg_alpha": 0.5, "max_delta_step": 0.7},
])
def test_mega_depthwise_option_grid(extra):
    X, y = _binary_data(n=1500, seed=12)
    _assert_parity({"objective": "binary:logistic", "eta": 0.3,
                    "max_bin": 64, "max_depth": 3, **extra}, X, y,
                   rounds=3)


def test_mega_multiclass_matches_scan():
    rng = np.random.RandomState(13)
    X = rng.randn(1500, 6).astype(np.float32)
    y = (np.abs(X @ rng.randn(6)) * 2).astype(np.int32) % 4
    _assert_parity({"objective": "multi:softprob", "num_class": 4,
                    "eta": 0.3, "max_bin": 64, "max_depth": 3},
                   X, y.astype(np.float32), rounds=3)


@pytest.mark.parametrize("missing", [False, True])
def test_mega_lossguide_matches_scan(missing):
    X, y = _binary_data(missing=missing, seed=14)
    _assert_parity({"objective": "binary:logistic", "eta": 0.3,
                    "max_bin": 64, "grow_policy": "lossguide",
                    "max_leaves": 10, "max_depth": 0}, X, y)


@pytest.mark.parametrize("extra", [
    # tiers the in-trace greedy loop does NOT cover: mega falls back to
    # the host scan loop for these, which must stay transparently exact
    {"colsample_bylevel": 0.7},
    {"monotone_constraints": "(1,-1,0,0,0,0,0,0)"},
])
def test_mega_lossguide_fallback_tiers(extra):
    X, y = _binary_data(n=1500, seed=15)
    _assert_parity({"objective": "binary:logistic", "eta": 0.3,
                    "max_bin": 64, "grow_policy": "lossguide",
                    "max_leaves": 8, "max_depth": 0, **extra}, X, y,
                   rounds=3)


def test_mega_paged_matches_scan(tmp_path, monkeypatch):
    """External-memory tier: mega lowers to the page-major two-level
    schedule (tree/paged.py), bit-identical to the scan lowering."""
    from xgboost_tpu.data.dmatrix import DataIter

    monkeypatch.setenv("XTPU_PAGE_ROWS", "1024")
    monkeypatch.setenv("XTPU_PAGED_COLLAPSE", "0")
    X, y = _binary_data(n=3000, seed=16)

    def make_dm():
        class It(DataIter):
            def __init__(self):
                super().__init__()
                self.parts = np.array_split(np.arange(len(y)), 3)
                self.i = 0

            def next(self, input_data):
                if self.i >= len(self.parts):
                    return 0
                idx = self.parts[self.i]
                input_data(data=X[idx], label=y[idx])
                self.i += 1
                return 1

            def reset(self):
                self.i = 0

        it = It()
        it.cache_prefix = str(tmp_path / "pc")
        return xgb.QuantileDMatrix(it, max_bin=64)

    params = {"objective": "binary:logistic", "eta": 0.3, "max_bin": 64,
              "max_depth": 3}
    b_s = xgb.train({**params, "hist_method": "scan"}, make_dm(), 3,
                    verbose_eval=False)
    b_m = xgb.train({**params, "hist_method": "mega"}, make_dm(), 3,
                    verbose_eval=False)
    assert b_m.get_dump(with_stats=True) == b_s.get_dump(with_stats=True)
    assert _norm_raw(b_m.save_raw()) == _norm_raw(b_s.save_raw())


# ----------------------------------------------------------------- mesh


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 2:
        pytest.skip("needs multi-device (virtual) platform")
    return xgb.make_data_mesh()


def test_mega_mesh_row_depthwise_matches_scan(mesh):
    X, y = _binary_data(n=4096, F=6, seed=17)
    _assert_parity({"objective": "binary:logistic", "eta": 0.3,
                    "max_bin": 64, "max_depth": 4, "mesh": mesh},
                   X, y, rounds=3)


def test_mega_mesh_row_lossguide_matches_scan(mesh):
    X, y = _binary_data(n=4096, F=6, seed=18)
    _assert_parity({"objective": "binary:logistic", "eta": 0.3,
                    "max_bin": 64, "grow_policy": "lossguide",
                    "max_leaves": 8, "max_depth": 0, "mesh": mesh},
                   X, y, rounds=3)


def test_mega_mesh_col_lossguide_matches_scan(mesh):
    X, y = _binary_data(n=3000, F=6, seed=19)
    _assert_parity({"objective": "binary:logistic", "eta": 0.3,
                    "max_bin": 64, "grow_policy": "lossguide",
                    "max_leaves": 8, "max_depth": 0, "mesh": mesh,
                    "data_split_mode": "col"}, X, y, rounds=3)


# ------------------------------------------------------------- dispatch


def test_mega_dispatch_count_resident(monkeypatch):
    """A steady resident boosting round is <=2 compiled-program launches.

    jax 0.4.x runs cache-hit jit calls AND cache-hit eager ops entirely
    on the C++ fast path — invisible to any Python hook (neither
    ``pjit._pjit_call_impl`` nor ``ExecuteReplicated.__call__`` fires).
    Only a program's FIRST execution after compilation routes through
    Python ``ExecuteReplicated``. So the launch count is pinned from two
    directions:

    - steady rounds: the two known entry points (``_fused_round_fn``,
      ``_margin_bad_rows``) are each called exactly once per round and
      ZERO fresh executions happen — no recompiles, no stray eager ops
      with novel shapes (the bounded-shape carries never re-trace);
    - after ``jax.clear_caches()``: ONE round re-executes exactly 2
      distinct compiled programs — every launch is a first launch, so
      the Python path sees them all.
    """
    import jax._src.interpreters.pxla as pxla

    from xgboost_tpu import core

    X, y = _binary_data(n=2000, seed=20)
    dtr = xgb.DMatrix(X, label=y)
    params = {"objective": "binary:logistic", "eta": 0.3, "max_bin": 64,
              "max_depth": 3, "hist_method": "mega", "seed": 0}
    bst = xgb.train(params, dtr, 3, verbose_eval=False)
    assert bst._fused_round is not None  # megakernel fast path engaged

    calls = {"fused": 0, "margin": 0, "exec": 0}
    orig_fused, orig_margin = core._fused_round_fn, core._margin_bad_rows
    monkeypatch.setattr(core, "_fused_round_fn", lambda *a, **k: (
        calls.__setitem__("fused", calls["fused"] + 1),
        orig_fused(*a, **k))[1])
    monkeypatch.setattr(core, "_margin_bad_rows", lambda *a, **k: (
        calls.__setitem__("margin", calls["margin"] + 1),
        orig_margin(*a, **k))[1])
    orig_exec = pxla.ExecuteReplicated.__call__

    def spy(self, *a, **k):
        calls["exec"] += 1
        return orig_exec(self, *a, **k)

    monkeypatch.setattr(pxla.ExecuteReplicated, "__call__", spy)
    for it in (3, 4, 5):
        bst.update(dtr, it)
    assert calls["fused"] == 3      # one megakernel launch per round
    assert calls["margin"] == 3     # one NaN-guard launch per round
    assert calls["exec"] == 0       # zero fresh compiles in steady state

    jax.clear_caches()
    calls["exec"] = 0
    bst.update(dtr, 6)
    assert calls["exec"] <= 2       # the whole round is <=2 programs


# ----------------------------------------------- root-level shard_map


def test_counting_sort_single_node_under_shard_map(mesh):
    """n_nodes==1 regression (r14): the root level's grouping permutation
    must trace under ``shard_map`` with the replication checker ON even
    when ``rel_pos`` is a traced CONSTANT — the sort primitive has no
    replication rule (check_vma crashes on it), so the one-node tier is
    a cumsum counting rank instead."""
    ndev = len(jax.devices())
    n = 128 * ndev

    def root_perm(x):
        # rel derived from data but constant-foldable to all-active:
        # the shape the megakernel's first iteration sees
        rel = jnp.zeros(x.shape[0], jnp.int32)
        return counting_sort_by_node(rel, 1)

    fn = jax.jit(jax.shard_map(root_perm, mesh=mesh,
                               in_specs=(P(DATA_AXIS),),
                               out_specs=P(DATA_AXIS)))
    out = np.asarray(fn(jnp.arange(n, dtype=jnp.float32)))
    local = n // ndev
    expect = np.tile(np.arange(local, dtype=np.int32), ndev)
    np.testing.assert_array_equal(out, expect)  # identity per shard

    # mixed active/stray rows: stable grouping == stable argsort
    rng = np.random.RandomState(21)
    rel_np = (rng.rand(n) < 0.2).astype(np.int32)  # 1 == inactive stray

    def perm_of(rel):
        return counting_sort_by_node(rel, 1)

    fn2 = jax.jit(jax.shard_map(perm_of, mesh=mesh,
                                in_specs=(P(DATA_AXIS),),
                                out_specs=P(DATA_AXIS)))
    out2 = np.asarray(fn2(jnp.asarray(rel_np)))
    for d in range(ndev):
        lo = d * local
        want = np.argsort(rel_np[lo:lo + local], kind="stable")
        np.testing.assert_array_equal(out2[lo:lo + local], want)


# ------------------------------------------------------- scan_acc auto


def test_resolve_scan_acc_obeys_rms_bound(monkeypatch):
    from xgboost_tpu.ops import histogram as H

    rng = np.random.RandomState(22)
    bins = jnp.asarray(rng.randint(0, 64, (512, 4)).astype(np.uint8))
    gpair = jnp.asarray(rng.randn(512, 2).astype(np.float32))
    monkeypatch.setattr(H, "SCAN_ACC_RMS_BOUND", float("inf"))
    assert H.resolve_scan_acc(bins, gpair, 64) == "bf16"
    monkeypatch.setattr(H, "SCAN_ACC_RMS_BOUND", -1.0)
    assert H.resolve_scan_acc(bins, gpair, 64) == "f32"


def test_scan_acc_auto_trains_with_parity(monkeypatch):
    """XTPU_SCAN_ACC=auto resolves once per grower via the measured RMS
    probe; whichever accumulator it picks, scan and mega resolve the
    SAME one (same probe, same data), so parity must hold."""
    monkeypatch.setenv("XTPU_SCAN_ACC", "auto")
    X, y = _binary_data(n=1500, seed=23)
    _assert_parity({"objective": "binary:logistic", "eta": 0.3,
                    "max_bin": 64, "max_depth": 3}, X, y, rounds=3)
