"""The airline cell's own files: the seeded generator, the readers, the
control and the planted faults under the cell's limits, whole rehearsal
runs with the timed path broken underneath, and the refusal of a program
that cannot run the cell."""

import json

import numpy as np
import pytest

import check_line
import run as bench_run
from drivers import cat_loop, train_loop
from lib import cat_work, compare, data_airline, manifest as mf

CELL = "airline-ontime.cat-train"


# ---- the generator ----------------------------------------------------------

def test_generator_is_seeded_block_independent_and_airline_shaped():
    seed = 2 ** 31 + 41
    X, y = data_airline.airline_like(200_000, 13, seed)
    X2, y2 = data_airline.airline_like(200_000, 13, seed)
    assert np.array_equal(X, X2, equal_nan=True) and np.array_equal(y, y2)
    assert not np.array_equal(X[:100], data_airline.airline_like(
        100, 13, seed + 1)[0], equal_nan=True)
    # the first rows do not depend on how many are asked for
    Xs, ys = data_airline.airline_like(100, 13, seed)
    assert np.array_equal(Xs, X[:100], equal_nan=True)
    assert np.array_equal(ys, y[:100])
    # a block depends on (seed, stream, b) alone
    Xb, _ = data_airline.block(seed, 0, 1)
    lo = data_airline.BLOCK
    assert np.array_equal(Xb, X[lo:2 * lo], equal_nan=True)
    assert X.dtype == np.float32 and 0.42 < y.mean() < 0.48
    # the categorical columns hold codes 0..n-1, every one drawn, a few
    # carrying most flights
    for name, n_cat in data_airline.CATEGORIES.items():
        col = X[:, data_airline.COLUMNS.index(name)]
        assert set(np.unique(col)) == set(range(n_cat)), name
        share = np.sort(np.bincount(col.astype(int)))[::-1] / len(col)
        assert share[:n_cat // 10].sum() > 0.3, name
    # about 2% of ActualElapsedTime missing, nothing else
    miss = np.isnan(X).mean(axis=0)
    at = data_airline.COLUMNS.index("ActualElapsedTime")
    assert 0.015 < miss[at] < 0.025 and miss.sum() == miss[at]
    assert data_airline.feature_types().count("c") == 3


# ---- the readers --------------------------------------------------------------

def test_the_readers():
    config = mf.cell(mf.load(), CELL)["config"]
    # two-byte ids of the two airport columns, one byte of the carrier, and
    # the gradient pair, a level
    assert cat_work.round_cat_bytes(config) == 8 * 115069017 * (2 + 2 + 1
                                                                  + 8)
    pct = mf.layer_reader("cat_split_pct").read
    assert pct({"cat": {"splits": {"partition": 30, "onehot": 10,
                                   "numeric": 60}}}) == pytest.approx(40.0)
    assert pct({"cat": {"splits": {}}}) is None and pct({}) is None
    roof = mf.layer_reader("cat_hist_roofline").read
    assert roof({"config": config, "trace": None}) is None
    assert roof({"trace": {"rounds": 4}, "config": {
        k: v for k, v in config.items() if k != "categorical"}}) is None
    assert mf.layer_reader("cat_split_ms").read({"trace": None}) is None


# ---- the control and the faults, under the cell's limits --------------------

@pytest.fixture(scope="module")
def readings():
    cell = mf.cell(mf.load(), CELL)
    config = {**cell["config"], **cell["config"]["rehearse"],
              "params": {**cell["config"]["params"],
                         **cell["config"]["rehearse"]["params"]}}
    return cell["limits"], cat_loop.control_readings(
        config, cell["traffic"], seed=2 ** 31 + 7)


@pytest.mark.parametrize("case, want", [
    ("sound", True), ("control_bf16", False), ("threshold_routed", False),
    ("partition_reversed", False), ("column_left_out", False),
    ("half_batch", False), ("state_unchanged", False)])
def test_cases_against_the_cells_limits(readings, case, want):
    limits, by_case = readings
    ok, table = compare.judge(by_case[case], limits)
    assert ok is want, (case, table)


def test_what_fails_what(readings):
    limits, by_case = readings
    over = {case: {k for k, v in vals.items() if not v <= limits[k]}
            for case, vals in by_case.items()}
    assert {"leaf_gap", "margin_gap"} <= over["control_bf16"], over
    assert {"node_hess_gap", "margin_gap"} <= over["threshold_routed"], over
    assert over["partition_reversed"] == {"split_gap"}, over
    assert over["column_left_out"] == {"split_gap"}, over
    assert "node_hess_gap" in over["half_batch"], over
    assert {"node_hess_gap", "rounds_gap"} <= over["state_unchanged"], over
    # each with room: three times its limit or more
    for case, number in (("partition_reversed", "split_gap"),
                         ("column_left_out", "split_gap"),
                         ("threshold_routed", "node_hess_gap"),
                         ("control_bf16", "margin_gap"),
                         ("control_bf16", "leaf_gap")):
        assert by_case[case][number] > 3 * limits[number], (case, number)


# ---- whole runs, the timed path broken underneath ---------------------------

def drive(capsys, seed=2 ** 31 + 99, trace=0):
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed),
                         "--seconds", "0.5", "--trace", str(trace),
                         "--rehearse"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert check_line.problems(line, mf.load(), CELL, bool(trace)) == []
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
        "node_hess_gap", "leaf_gap", "gain_gap", "split_gap", "margin_gap",
        "replica_gap", "rounds_gap"}


def test_traced_rehearsal_reports_the_categorical_share(capsys):
    result = drive(capsys, seed=7, trace=1)
    # most of a round's splits are categorical on this data
    assert result["metrics"]["cat_split_pct"]["value"] > 30
    assert result["metrics"]["setup_compiled_programs"]["unit"] == "programs"


def test_half_of_the_batch_left_out_in_the_window(capsys, monkeypatch):
    half = {}

    def change(xgb, params, dtrain):
        if "dm" not in half:
            X = np.asarray(dtrain.values())
            y = np.asarray(dtrain.get_label())
            half["dm"] = xgb.DMatrix(
                X[:len(y) // 2], label=y[:len(y) // 2],
                feature_types=data_airline.feature_types(),
                enable_categorical=True)
        return params, half["dm"]
    break_window(monkeypatch, change)
    assert drive(capsys)["correct"] is False


def test_categories_read_as_numbers_in_the_window(capsys, monkeypatch):
    """The window's calls see the category codes as numbers: threshold
    splits where the trees state left sets."""
    numeric = {}

    def change(xgb, params, dtrain):
        if "dm" not in numeric:
            numeric["dm"] = xgb.DMatrix(np.asarray(dtrain.values()),
                                        label=np.asarray(dtrain.get_label()))
        return params, numeric["dm"]
    break_window(monkeypatch, change)
    assert drive(capsys)["correct"] is False


def test_a_call_that_raises_fails_the_run(capsys, monkeypatch):
    def change(xgb, params, dtrain):
        raise RuntimeError("planted")
    break_window(monkeypatch, change)
    result = drive(capsys)
    assert result["correct"] is False and result["failed"] == 1


def test_another_schedule_fails_the_run(capsys, monkeypatch):
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


# ---- a program that cannot run the cell ---------------------------------------

def test_a_program_without_the_categorical_counter_is_refused(monkeypatch):
    """The parent of the categorical schedule is refused before any data is
    made (exit 4), not left to exhaust the chip at its first compile."""
    from xgboost_tpu.obs import metrics as obs_metrics

    monkeypatch.delattr(obs_metrics, "cat_split_counts")
    config = mf.cell(mf.load(), CELL)["config"]
    with pytest.raises(SystemExit) as e:
        cat_loop.require_program(config, "cpu", "auto")
    assert e.value.code == 4


def test_a_program_that_resolves_another_schedule_is_refused():
    config = mf.cell(mf.load(), CELL)["config"]
    # on the CPU the program resolves the one-pass kernel, not "fused"
    with pytest.raises(SystemExit) as e:
        cat_loop.require_program(config, "cpu", "fused")
    assert e.value.code == 4
    assert cat_loop.require_program(config, "cpu", "auto") is not None
