"""The dense wide cell's own files: the seeded generator, the reader, the
control and the planted faults under the cell's limits, and whole rehearsal
runs with the timed path broken underneath."""

import json

import numpy as np
import pytest

import check_line
import run as bench_run
from drivers import train_loop, wide_loop
from lib import compare, data_wide, manifest as mf, work

CELL = "pascal-epsilon.wide-train"


# ---- the generator ----------------------------------------------------------

def test_layout_spreads_the_signal_over_the_width():
    lay = data_wide.layout(2000)
    cols = np.r_[lay["cols"], lay["strong"]]
    assert len(set(cols)) == data_wide.SIGNAL == 32
    assert cols.min() < 2000 // 32 and cols.max() >= 2000 - 2000 // 32
    # every refine block and every sweep group reads some of the signal
    for width in (wide_loop.REFINE_BLOCK, wide_loop.SWEEP_GROUP):
        assert set(range(2000 // width)) <= set(cols // width)
    assert data_wide.layout(2000) is lay                # not the seed's


def test_generator_is_seeded_block_independent_and_normalised():
    seed = 2 ** 31 + 41
    X, y = data_wide.epsilon_like(20000, 2000, seed)
    X2, y2 = data_wide.epsilon_like(20000, 2000, seed)
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    assert not np.array_equal(X[:100], data_wide.epsilon_like(
        100, 2000, seed + 1)[0])
    # the first rows do not depend on how many are asked for
    Xs, ys = data_wide.epsilon_like(100, 2000, seed)
    assert np.array_equal(Xs, X[:100]) and np.array_equal(ys, y[:100])
    # a block depends on (seed, stream, b) alone
    Xb, yb = data_wide.block(seed, 0, 1, 2000)
    lo = data_wide.BLOCK
    assert np.array_equal(Xb[:20000 - lo], X[lo:])
    assert not np.array_equal(data_wide.block(seed, 1, 1, 2000)[0], Xb)
    assert X.dtype == np.float32 and np.isfinite(X).all()
    np.testing.assert_allclose(np.linalg.norm(X, axis=1), 1.0, rtol=1e-5)
    # standardised columns before the rows' scaling: spread sqrt(1/F)
    assert 0.9 < float(X.std(axis=0).mean()) * np.sqrt(2000) < 1.1
    assert abs(float(X.mean())) < 1e-3
    # every column fills 256 bins, and the label is balanced
    assert min(len(np.unique(X[:, f])) for f in (0, 999, 1999)) > 19000
    assert 0.47 < y.mean() < 0.53 and set(np.unique(y)) == {0.0, 1.0}
    strong = data_wide.layout(2000)["strong"]
    assert np.corrcoef(X[:, strong], y)[0, 1] > 0.3


def test_the_reader_counts_dense_bytes():
    config = mf.cell(mf.load(), CELL)["config"]
    assert work.round_hbm_bytes(config) == 8 * 400000 * (2000 + 8)
    read = mf.layer_reader("wide_hist_roofline").read
    mark = 'custom_call_target="tpu_custom_call"'
    facts = {"config": config, "device_kind": "TPU v5e", "trace": {
        "rounds": 4, "op_self": {f"%k.1 = {mark}": 0.02, "%fusion": 9.0,
                                 f"%k.2 = {mark}": 0.02}}}
    least = work.round_least_seconds(config, 819e9)
    assert least == pytest.approx(7.85e-3, rel=1e-3)
    assert read(facts) == pytest.approx(100 * least / 0.01)
    assert read(dict(facts, trace=None)) is None
    facts["trace"]["op_self"] = {"%fusion": 9.0}
    assert read(facts) is None


# ---- the control and the faults, under the cell's limits --------------------

@pytest.fixture(scope="module")
def readings():
    cell = mf.cell(mf.load(), CELL)
    config = {**cell["config"], **cell["config"]["rehearse"],
              "params": {**cell["config"]["params"],
                         **cell["config"]["rehearse"]["params"]}}
    return cell["limits"], wide_loop.control_readings(
        config, cell["traffic"], seed=2 ** 31 + 7)


@pytest.mark.parametrize("case, want", [
    ("sound", True), ("control_bf16", False), ("block_left_out", False),
    ("group_left_out", False), ("last_level_unrouted", False),
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
    assert over["block_left_out"] == {"split_gap"}, over
    assert over["group_left_out"] == {"split_gap"}, over
    assert over["last_level_unrouted"] == {"margin_gap"}, over
    assert "node_hess_gap" in over["half_batch"], over
    assert {"node_hess_gap", "rounds_gap"} <= over["state_unchanged"], over
    assert by_case["half_batch"]["node_hess_gap"] == pytest.approx(0.5,
                                                                   abs=0.01)
    # each with room: three times its limit or more
    for case, number in (("block_left_out", "split_gap"),
                         ("group_left_out", "split_gap"),
                         ("last_level_unrouted", "margin_gap"),
                         ("control_bf16", "margin_gap")):
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


def test_traced_rehearsal_reports_every_program_metric(capsys):
    # the CPU's ``auto`` runs no Mosaic kernel: no kernel time to read
    result = drive(capsys, seed=7, trace=1)
    assert "wide_hist_roofline" not in result["metrics"]
    assert result["metrics"]["setup_compiled_programs"]["unit"] == "programs"


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


def test_a_program_without_the_vmem_gauge_runs_on(monkeypatch):
    """The parent of the gauge runs the cell: the driver reads it where the
    program has it and says so where it has not."""
    from xgboost_tpu.obs import metrics as obs_metrics

    assert isinstance(wide_loop.wide_counts()["kernel_vmem"], dict)
    monkeypatch.delattr(obs_metrics, "kernel_vmem_bytes")
    assert wide_loop.wide_counts()["kernel_vmem"] is None
