"""Drives a whole run of each cell (the harness's look for a chip skipped by
``--rehearse``) with the timed path broken underneath, and sees ``correct``
come out false: once for each fault a one-chip training cell can have. A sound
run of the same cell reads true."""

import json

import numpy as np
import pytest

import check_line
import run as bench_run
from drivers import train_loop
from lib import manifest as mf

CELLS = [w["name"] for w in mf.load()["workloads"]]


def drive(capsys, cell, seed=2 ** 31 + 99):
    rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", "0.5", "--trace", "0", "--rehearse"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert check_line.problems(line, mf.load(), cell, False) == []
    return json.loads(line)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(capsys, cell):
    result = drive(capsys, cell)
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_step_that_returns_its_state_unchanged(capsys, monkeypatch, cell):
    real = train_loop.train_call
    seen = {"n": 0}

    def broken(xgb, params, dtrain, rounds, bst, evals, sink):
        seen["n"] += 1
        if seen["n"] == 2 and bst is not None:     # first call of the window
            if evals:                              # the log it would have kept
                sink[evals[0][1]] = {params["eval_metric"]: []}
            return bst
        return real(xgb, params, dtrain, rounds, bst, evals, sink)
    monkeypatch.setattr(train_loop, "train_call", broken)
    result = drive(capsys, cell)
    assert result["correct"] is False
    assert result["compared"]["rounds_gap"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out(capsys, monkeypatch, cell):
    """Planted in the window's continuation calls only: the warm-up call in
    set-up is sound, so only numbers taken from the window can catch it."""
    real = train_loop.train_call
    half = {}

    def broken(xgb, params, dtrain, rounds, bst, evals, sink):
        if bst is None:
            return real(xgb, params, dtrain, rounds, bst, evals, sink)
        if "dm" not in half:
            X = np.asarray(dtrain.values())
            n = X.shape[0] // 2
            half["dm"] = xgb.DMatrix(
                X[:n], label=np.asarray(dtrain.get_label())[:n])
        return real(xgb, params, half["dm"], rounds, bst, evals, sink)
    monkeypatch.setattr(train_loop, "train_call", broken)
    result = drive(capsys, cell)
    assert result["correct"] is False
    assert abs(result["compared"]["grad_gap"]["value"] - 0.5) < 0.01
