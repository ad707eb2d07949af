"""The sparse cell's own files: the seeded, block-missing generator, the
sparsity-aware reference, the work count and the three readers, the control
and the planted faults under the cell's limits, and whole rehearsal runs
with the timed path broken underneath."""

import json

import numpy as np
import pytest

import check_line
import run as bench_run
from drivers import sparse_loop, train_loop
from lib import compare, data_sparse, manifest as mf, sparse_work
from lib import reference_sparse as rs

CELL = "bosch-line.sparse-train"


# ---- the generator ----------------------------------------------------------

@pytest.mark.parametrize("features", [96, 968])
def test_layout_keeps_the_published_shape(features):
    lay = data_sparse.layout(features)
    assert lay["widths"].sum() == features and lay["widths"].min() >= 1
    assert len(lay["widths"]) == data_sparse.STATIONS == 52
    share = float(lay["freq"] @ (lay["member"] @ lay["widths"])) / features
    assert abs(share - 0.19) <= 0.003              # 81% missing
    assert lay["member"].any(axis=0).all()         # no column wholly missing
    assert (lay["visit"] < 0.01).sum() >= 3        # line 2: rare routes only
    assert lay["freq"][0] > 50 * lay["freq"][-1]   # very unequal families
    a, b, c = (lay["visit"][lay[k]] for k in "ABC")
    assert 0.4 < a < 0.8 and 0.2 < b < 0.5 and 0.03 < c < 0.15


def test_generator_is_seeded_block_independent_and_block_missing():
    seed = 2 ** 31 + 35
    X, y = data_sparse.bosch_like(40000, 96, seed)
    X2, y2 = data_sparse.bosch_like(40000, 96, seed)
    assert np.array_equal(X, X2, equal_nan=True) and np.array_equal(y, y2)
    assert not np.array_equal(
        X, data_sparse.bosch_like(40000, 96, seed + 1)[0], equal_nan=True)
    # the first rows do not depend on how many are asked for
    Xs, ys = data_sparse.bosch_like(20000, 96, seed)
    assert np.array_equal(Xs, X[:20000], equal_nan=True)
    assert np.array_equal(ys, y[:20000])
    # a block depends on (seed, stream, b) alone
    Xb, yb = data_sparse.block(seed, 0, 1, 96)
    lo = data_sparse.BLOCK
    assert np.array_equal(Xb, X[lo:2 * lo], equal_nan=True)
    assert not np.array_equal(data_sparse.block(seed, 1, 1, 96)[0], Xb,
                              equal_nan=True)
    # a station's columns are present or absent together
    lay = data_sparse.layout(96)
    here = ~np.isnan(X)
    for s in range(data_sparse.STATIONS):
        cols = here[:, lay["starts"][s]:lay["starts"][s + 1]]
        assert (cols == cols[:, :1]).all()
    assert X.dtype == np.float32 and 0.80 <= 1 - here.mean() <= 0.82
    present = here.mean(axis=0)
    assert present.min() > 0 and (present < 0.01).any()
    assert 0.005 <= y.mean() <= 0.007 and set(np.unique(y)) == {0.0, 1.0}
    vals = X[here]
    assert vals.min() >= -1 and vals.max() <= 1
    assert np.array_equal(vals, np.round(vals, 3))
    tern = np.flatnonzero(lay["ternary"] & (present > 0.05))[0]
    assert len(np.unique(X[here[:, tern], tern])) == 3


# ---- the reference ----------------------------------------------------------

def test_reference_learns_a_default_direction_in_closed_form():
    """Eight present rows and eight missing: the missing rows carry the left
    side's gradient, so the scan with the missing mass on the left wins."""
    x = np.array([0.1, 0.2, 0.3, 0.4, 0.6, 0.7, 0.8, 0.9] + [np.nan] * 8,
                 np.float32)
    X = np.stack([x, np.zeros(16, np.float32)], axis=1)
    g = np.array([1.0] * 4 + [-1.0] * 4 + [1.0] * 8, np.float32)
    h = np.ones(16, np.float32)
    tree, pos = rs.grow_tree(X, rs.make_binned(X, 256), g, h, max_depth=1,
                             eta=1.0, lam=0.0, min_child_weight=1.0)
    assert tree["feat"][0] == 0 and tree["thr"][0] == np.float32(0.4)
    assert tree["dleft"][0]
    # left: 12 rows of +1, right: 4 rows of -1
    assert tree["gain"][0] == pytest.approx(144 / 12 + 16 / 4 - 64 / 16)
    assert list(tree["value"][1:3]) == [-1.0, 1.0]
    assert (pos[8:] == 1).all() and (rs.walk_nodes(tree, X) == pos).all()
    assert rs.default_dir_gap(tree, X, g, h, 0.0, 1.0) == 0.0
    # the same cut with the missing rows sent right: what flipping would gain
    wrong = dict(tree, dleft=np.zeros(3, bool))
    stated = 16 / 4 + 16 / 12 - 64 / 16
    assert rs.default_dir_gap(wrong, X, g, h, 0.0, 1.0) == pytest.approx(
        (12.0 - stated) / stated)
    forced, _ = rs.grow_tree(X, rs.make_binned(X, 256), g, h, max_depth=1,
                             eta=1.0, lam=0.0, min_child_weight=1.0,
                             force_right=True)
    assert not forced["dleft"].any() and forced["thr"][0] == tree["thr"][0]


def test_reference_cuts_and_columns_from_present_values():
    X, _ = data_sparse.bosch_like(20000, 96, 3)
    ptr, rows, vals = rs.make_present(X)
    here = ~np.isnan(X)
    assert ptr[-1] == here.sum()
    for f in (0, 17, 95):
        idx = np.flatnonzero(here[:, f])
        assert np.array_equal(rows[ptr[f]:ptr[f + 1]], idx)
        assert np.array_equal(vals[ptr[f]:ptr[f + 1]], X[idx, f])
    cuts = rs.make_cuts(ptr, vals, 256)
    assert all(len(c) <= 256 for c in cuts)
    assert all(c[-1] == X[here[:, f], f].max() for f, c in enumerate(cuts))
    bins = rs.bin_present(ptr, vals, cuts)
    f = 17
    col = vals[ptr[f]:ptr[f + 1]]
    b = bins[ptr[f]:ptr[f + 1]]
    assert (col <= cuts[f][b]).all()
    assert (col[b > 0] > cuts[f][b[b > 0] - 1]).all()


# ---- the work count and the readers -----------------------------------------

def test_least_bytes_count_present_values_only():
    config = mf.cell(mf.load(), CELL)["config"]
    got = sparse_work.round_present_bytes(config)
    assert got == pytest.approx(
        8 * (1183747 * 968 * 0.19 + 1183747 * 8), rel=1e-12)
    assert 1.80e9 < got < 1.83e9
    assert sparse_work.round_least_seconds(config, 819e9) == pytest.approx(
        2.2e-3, rel=0.02)


def test_readers():
    read = mf.layer_reader("fused_boundary_kernel_pct").read
    assert read({"sparse": {"boundary": {"kernel": 5, "xla": 2}}}) \
        == pytest.approx(100 * 5 / 7)
    assert read({"sparse": {"boundary": {}}}) is None
    assert read({"sparse": None}) is None and read({}) is None
    read = mf.layer_reader("bin_bytes_per_value").read
    assert read({"sparse": {"bin_bytes": 2}}) == 2.0
    assert read({"sparse": None}) is None
    read = mf.layer_reader("hist_body_features_max").read
    assert read({"sparse": {"body_features": 242}}) == 242.0
    assert read({"sparse": {"body_features": 0}}) is None
    assert read({"sparse": None}) is None and read({}) is None
    read = mf.layer_reader("hist_kernel_roofline").read
    config = mf.cell(mf.load(), CELL)["config"]
    mark = 'custom_call_target="tpu_custom_call"'
    facts = {"config": config, "device_kind": "TPU v5e", "trace": {
        "rounds": 4, "op_self": {f"%k.1 = {mark}": 2.0, "%fusion": 9.0,
                                 f"%k.2 = {mark}": 2.0}}}
    least = sparse_work.round_least_seconds(config, 819e9)
    assert read(facts) == pytest.approx(100 * least / 1.0)
    assert read(dict(facts, trace=None)) is None
    assert read(dict(facts, config={"params": {}})) is None
    facts["trace"]["op_self"] = {"%fusion": 9.0}
    assert read(facts) is None


# ---- the control and the faults, under the cell's limits --------------------

@pytest.fixture(scope="module")
def readings():
    cell = mf.cell(mf.load(), CELL)
    config = {**cell["config"], **cell["config"]["rehearse"],
              "params": {**cell["config"]["params"],
                         **cell["config"]["rehearse"]["params"]}}
    return cell["limits"], sparse_loop.control_readings(
        config, cell["traffic"], seed=2 ** 31 + 7)


@pytest.mark.parametrize("case, want", [
    ("sound", True), ("control_bf16", False), ("missing_right", False),
    ("imputed_zero", False), ("station_left_out", False),
    ("half_batch", False), ("state_unchanged", False)])
def test_cases_against_the_cells_limits(readings, case, want):
    limits, by_case = readings
    ok, table = compare.judge(by_case[case], limits)
    assert ok is want, (case, table)


def test_what_fails_what(readings):
    limits, by_case = readings
    over = {case: {k for k, v in vals.items() if not v <= limits[k]}
            for case, vals in by_case.items()}
    assert "margin_gap" in over["control_bf16"], over
    assert over["missing_right"] == {"default_dir_gap"}, over
    assert {"margin_gap", "default_dir_gap"} <= over["imputed_zero"], over
    assert "loss_gap" in over["station_left_out"], over
    assert "grad_gap" in over["half_batch"], over
    assert {"update_gap", "rounds_gap", "loss_gap"} <= \
        over["state_unchanged"], over
    assert by_case["state_unchanged"]["update_gap"] == 1.0
    assert by_case["half_batch"]["grad_gap"] == pytest.approx(0.5, abs=0.01)
    assert all(v == 0 for v in by_case["sound"].values())
    # each with room: three times its limit or more
    assert by_case["missing_right"]["default_dir_gap"] \
        > 3 * limits["default_dir_gap"]
    assert by_case["control_bf16"]["margin_gap"] > 3 * limits["margin_gap"]


# ---- whole runs, the timed path broken underneath ---------------------------

def drive(capsys, seed=2 ** 31 + 99):
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed),
                         "--seconds", "0.5", "--trace", "0", "--rehearse"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert check_line.problems(line, mf.load(), CELL, False) == []
    return json.loads(line)


def break_window(monkeypatch, change):
    """``change(xgb, params, dtrain) -> (params, dtrain)`` for the window's
    continuation calls only: the warm-up call in set-up stays sound."""
    real = train_loop.train_call

    def broken(xgb, params, dtrain, rounds, bst, evals, sink):
        if bst is not None:
            params, dtrain = change(xgb, params, dtrain)
        return real(xgb, params, dtrain, rounds, bst, evals, sink)
    monkeypatch.setattr(train_loop, "train_call", broken)


def test_sound_rehearsal_prints_a_line_the_contract_accepts(capsys):
    result = drive(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["compared"]) == {
        "loss_gap", "grad_gap", "update_gap", "margin_gap",
        "default_dir_gap", "rounds_gap"}


def test_traced_rehearsal_reports_the_program_counters(capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", "7", "--seconds",
                         "0.5", "--trace", "1", "--rehearse"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    metrics = json.loads(line)["metrics"]
    assert metrics["bin_bytes_per_value"] == {"value": 2.0, "unit": "B"}
    # the CPU's ``auto`` is no fused schedule and runs no Mosaic kernel: no
    # boundary to count, no kernel body or kernel time to read; nothing else
    # is missing
    assert "hist_kernel_roofline" not in metrics
    assert sorted(check_line.problems(line, mf.load(), CELL, True)) == [
        f"metric {name!r} of bosch-line.sparse-train missing in the traced "
        "run" for name in ("fused_boundary_kernel_pct",
                           "hist_body_features_max")]


def test_nan_imputed_in_the_window(capsys, monkeypatch):
    other = {}

    def change(xgb, params, dtrain):
        if "dm" not in other:
            other["dm"] = xgb.DMatrix(
                np.nan_to_num(np.asarray(dtrain.values())),
                label=np.asarray(dtrain.get_label()))
        return params, other["dm"]
    break_window(monkeypatch, change)
    result = drive(capsys)
    assert result["correct"] is False
    over = {k for k, v in result["compared"].items()
            if v["value"] > v["limit"]}
    assert over, result["compared"]


def test_half_of_the_batch_left_out_in_the_window(capsys, monkeypatch):
    half = {}

    def change(xgb, params, dtrain):
        if "dm" not in half:
            X = np.asarray(dtrain.values())
            y = np.asarray(dtrain.get_label())
            half["dm"] = xgb.DMatrix(X[:len(y) // 2], label=y[:len(y) // 2])
        return params, half["dm"]
    break_window(monkeypatch, change)
    assert drive(capsys)["correct"] is False


def test_a_call_that_raises_fails_the_run(capsys, monkeypatch):
    def change(xgb, params, dtrain):
        raise RuntimeError("planted")
    break_window(monkeypatch, change)
    result = drive(capsys)
    assert result["correct"] is False and result["failed"] == 1


def test_another_schedule_fails_the_run(capsys, monkeypatch):
    # a grow program traced under another schedule than the mix expects (a
    # continuing booster keeps the programs it has: a new one is what traces)
    def change(xgb, params, dtrain):
        xgb.train(dict(params, hist_method="coarse"), dtrain, 1)
        return params, dtrain
    break_window(monkeypatch, change)
    result = drive(capsys)
    assert result["correct"] is False and result["failed"] == 1


def test_a_degrade_fails_the_run(capsys, monkeypatch):
    from xgboost_tpu.obs import metrics as obs_metrics

    monkeypatch.setattr(obs_metrics, "degrade_counts",
                        lambda: {"insight_disarm": 0, "paged_collapse": 1})
    result = drive(capsys)
    assert result["correct"] is False and result["failed"] == 1
