"""The control has to come out as not correct, and so has every planted
fault, under the limits of each cell; the sound reference has to pass. Read
here at a size a test run can hold; ``benchmark/control.py`` reads the same at
the cell's own size (PERF.md has those readings)."""

import pytest

from drivers import train_loop
from lib import compare, manifest as mf
from lib import reference as ref

import numpy as np


@pytest.fixture(scope="module")
def readings():
    m = mf.load()
    out = {}
    for w in m["workloads"]:
        cell = mf.cell(m, w["name"])
        config = {**cell["config"], "rows": 40000, "held_out_rows": 4000,
                  "params": {**cell["config"]["params"], "max_depth": 5}}
        out[w["name"]] = (cell["limits"], train_loop.control_readings(
            config, cell["traffic"], seed=2 ** 31 + 7))
    return out


@pytest.mark.parametrize("case, want", [
    ("sound", True), ("control_bf16", False), ("half_batch", False),
    ("state_unchanged", False), ("stale_margin", False)])
def test_cases_against_each_cells_limits(readings, case, want):
    for cell, (limits, by_case) in readings.items():
        ok, table = compare.judge(by_case[case], limits)
        assert ok is want, (cell, case, table)


def test_what_fails_what(readings):
    for cell, (limits, by_case) in readings.items():
        over = {case: {k for k, v in vals.items() if not v <= limits[k]}
                for case, vals in by_case.items()}
        assert "margin_gap" in over["control_bf16"], (cell, over)
        assert "grad_gap" in over["half_batch"], (cell, over)
        assert "grad_gap" in over["stale_margin"], (cell, over)
        assert {"update_gap", "loss_gap", "rounds_gap"} <= \
            over["state_unchanged"], (cell, over)
        if "eval_stale" in by_case:
            assert over["eval_stale"] == {"eval_gap"}, (cell, over)
        assert abs(by_case["half_batch"]["grad_gap"] - 0.5) < 1e-3
        assert by_case["state_unchanged"]["update_gap"] == 1.0


def test_bf16_rounding_is_nearest_even():
    x = np.array([1.0, 1.00390625, 1.01171875, -0.3, 3.0e38], np.float32)
    got = ref.to_bf16(x)
    import ml_dtypes
    want = x.astype(ml_dtypes.bfloat16).astype(np.float32)
    assert np.array_equal(got, want)


def test_reference_walk_matches_its_own_margin():
    from lib import data
    X, y = data.higgs_like(20000, 28, 11)
    run = ref.train(X, y, {"max_depth": 4, "eta": 0.1, "max_bin": 256}, 3)
    m = np.full(len(y), np.float32(run["base_margin"]), np.float32)
    for tree in run["trees"]:
        m = m + ref.walk(tree, X)
    assert np.abs(m - run["margin"]).max() < 1e-6
    assert run["losses"][0] > run["losses"][1] > run["losses"][2]
