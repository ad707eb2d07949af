"""The mesh cell's own files: the seeded click-log generator, the blockwise
reference, the ``mesh.*`` readers and the interconnect arithmetic, the control
and the planted faults under the cell's limits, and whole rehearsal runs on
four virtual CPU devices with the timed path broken underneath."""

import json
import os

# four virtual devices for the rehearsal's mesh; read when jax first makes
# its CPU backend, which no module does while it is imported
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4"
                               ).strip()

import numpy as np
import pytest

import check_line
import run as bench_run
from drivers import mesh_loop, train_loop
from lib import compare, data_ctr, manifest as mf, mesh_trace, mesh_work
from lib import reference as ref
from lib import reference_blocks as rb

CELL = "criteo-ctr.mesh-train"


# ---- the generator ----------------------------------------------------------

def test_blocks_depend_on_seed_stream_and_index_alone():
    X, y = data_ctr.block(2 ** 31 + 5, 0, 3, rows=50000)
    X2, y2 = data_ctr.block(2 ** 31 + 5, 0, 3)
    assert np.array_equal(X, X2[:50000]) and np.array_equal(y, y2[:50000])
    assert not np.array_equal(X, data_ctr.block(2 ** 31 + 5, 0, 4, 50000)[0])
    assert not np.array_equal(X, data_ctr.block(2 ** 31 + 5, 1, 3, 50000)[0])
    # a batch of blocks is its blocks, whatever the stream's length
    n = data_ctr.BLOCK + 1000
    Xa, ya = data_ctr.blocks(7, 0, 0, 2, n)
    assert Xa.shape == (n, 67) and Xa.dtype == np.float32
    assert np.array_equal(Xa[data_ctr.BLOCK:],
                          data_ctr.block(7, 0, 1, rows=1000)[0])
    assert np.array_equal(data_ctr.criteo_like(n, 67, 7)[1], ya)


def test_columns_and_click_rate():
    X, y = data_ctr.block(11, 0, 0)
    ints, counts = X[:, :13], X[:, 13:39]
    assert np.array_equal(ints, np.floor(ints)) and ints.min() == 0
    assert np.array_equal(counts, np.floor(counts))
    assert np.median(ints[:, 0]) <= 3 and ints.max() > 1000   # heavy tail
    assert 0.0 <= X[:, 39:].min() and X[:, 39:].max() <= 1.0
    assert not np.isnan(X).any() and set(np.unique(y)) == {0.0, 1.0}
    assert 0.02 < y.mean() < 0.04


# ---- the reference ----------------------------------------------------------

def test_blockwise_training_is_the_plain_reference():
    X, y = data_ctr.criteo_like(20000, 67, 5)
    params = {"max_depth": 3, "eta": 0.1, "max_bin": 256}
    whole = ref.train(X, y, params, 2)
    parts = rb.train(rb.array_source(X, y, 6000), params, 2)
    for a, b in zip(whole["trees"], parts["trees"]):
        assert np.array_equal(a["feat"], b["feat"])
        assert np.array_equal(a["thr"], b["thr"])
        assert np.allclose(a["sum_hess"], b["sum_hess"], rtol=1e-9)
    assert np.allclose(whole["margin"], parts["margin"], atol=1e-6)


def test_node_sums_fold_children_into_parents():
    tree = {"left": np.array([1, 3, -1, -1, -1]),
            "right": np.array([2, 4, -1, -1, -1])}
    G, H = rb.node_sums(tree, np.array([0., 0, 1, 2, 4]),
                        np.array([0., 0, 10, 20, 40]))
    assert G.tolist() == [7, 6, 1, 2, 4] and H.tolist() == [70, 60, 10, 20, 40]


# ---- the readers ------------------------------------------------------------

def _planes():
    pre = "jit(_grow_mesh)/jit(main)/jit(shmap_body)/jit(_grow)/xtpu.grow/"

    def ev(name, start, dur, tf_op=None):
        return [name, start, dur], ({} if tf_op is None else {"tf_op": tf_op})
    ops = [ev("%fusion.1", 100, 300, pre + "xtpu.advance_hist/"
                                           "xtpu.kernel.fused/pallas_call"),
           ev("%all-reduce.1", 400, 40, pre + "xtpu.exchange/"
                                              "mesh.hist_psum/psum"),
           ev("%all-reduce.2", 450, 10, pre + "xtpu.advance_hist/"
              "xtpu.quantise/mesh.scale_pmax/pmax"),
           ev("%all-reduce.3", 470, 5, pre + "mesh.root_psum/psum"),
           ev("%copy", 500, 20)]
    planes = [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench.traced_window", 0, 2000]],
         "stats": [{}]}]}]
    for chip, busy in enumerate([600, 500, 540, 560]):
        planes.append({"name": f"/device:TPU:{chip}", "lines": [
            {"name": "XLA Modules", "events": [["jit__grow_mesh(1)", 100,
                                                900]], "stats": [{}]},
            {"name": "XLA Ops", "events": [e for e, _ in ops]
             + [["%pad", 1000, busy - 375]],
             "stats": [s for _, s in ops] + [{}]}]})
    return planes


def test_mesh_scope_readers():
    assert mesh_trace.what_of("a/xtpu.exchange/mesh.hist_psum/psum") \
        == "hist_psum"
    assert mesh_trace.what_of("jit(_grow)/xtpu.grow/xtpu.hist/add") == ""
    from lib import program_trace as pt
    assert pt.stage_of("a/xtpu.exchange/mesh.hist_psum/psum") == "exchange"
    planes = _planes()
    by_what = mesh_trace.collective_self_seconds(planes, "tpu",
                                                 ["_grow_mesh"])
    assert by_what["hist_psum"] == pytest.approx(40 / 1e9)
    assert by_what["scale_pmax"] == pytest.approx(10 / 1e9)
    assert by_what["root_psum"] == pytest.approx(5 / 1e9)
    busy = mesh_trace.chip_busy_seconds(planes, "tpu", 4)
    assert busy == pytest.approx([600e-9, 500e-9, 540e-9, 560e-9])


def test_interconnect_arithmetic_and_readers_with_nothing_to_read():
    facts = {"mesh": {"bytes": {"hist_psum": 8e6, "root_psum": 32}},
             "rounds": 4, "chips": 4, "device_kind": "TPU v5 lite"}
    assert mesh_work.round_exchange_bytes(facts) == pytest.approx(2e6 + 8)
    assert mesh_work.round_least_ici_seconds(facts) == pytest.approx(
        2 * 3 / 4 * (2e6 + 8) / 200e9)
    with pytest.raises(KeyError):
        mesh_work.ici_peak("TPU v9")
    for name in ("mesh_allreduce_ms", "mesh_allreduce_ici_pct",
                 "mesh_chip_skew_pct"):
        assert mf.layer_reader(name).read({"trace": None}) is None


# ---- the control and the faults, under the cell's limits --------------------

@pytest.fixture(scope="module")
def readings():
    cell = mf.cell(mf.load(), CELL)
    config = {**cell["config"], **cell["config"]["rehearse"],
              "params": {**cell["config"]["params"],
                         **cell["config"]["rehearse"]["params"]}}
    return cell["limits"], mesh_loop.control_readings(
        config, cell["traffic"], seed=2 ** 31 + 7)


@pytest.mark.parametrize("case, want", [
    ("sound", True), ("control_bf16", False), ("shard_left_out", False),
    ("half_batch", False), ("state_unchanged", False),
    ("stale_margin", False), ("clamped_bin", False),
    ("replica_differs", False)])
def test_cases_against_the_cells_limits(readings, case, want):
    limits, by_case = readings
    ok, table = compare.judge(by_case[case], limits)
    assert ok is want, (case, table)


def test_what_fails_what(readings):
    limits, by_case = readings
    over = {case: {k for k, v in vals.items() if not v <= limits[k]}
            for case, vals in by_case.items()}
    assert {"margin_gap", "leaf_gap", "node_hess_gap"} <= \
        over["control_bf16"], over
    assert {"node_hess_gap", "leaf_gap"} <= over["shard_left_out"], over
    assert {"node_hess_gap", "leaf_gap"} <= over["half_batch"], over
    assert "node_hess_gap" in over["stale_margin"], over
    assert over["clamped_bin"] == {"margin_gap"}, over
    assert over["replica_differs"] == {"replica_gap"}, over
    assert "rounds_gap" in over["state_unchanged"], over
    assert by_case["shard_left_out"]["node_hess_gap"] == pytest.approx(
        0.25, rel=0.05)
    assert by_case["half_batch"]["node_hess_gap"] == pytest.approx(
        0.5, rel=0.05)
    assert all(v < 1e-6 for v in by_case["sound"].values())   # f32 leaves


# ---- whole runs, the timed path broken underneath ---------------------------

def drive(capsys, seed=2 ** 31 + 99):
    rc = bench_run.main(["--workload", CELL, "--seed", str(seed),
                         "--seconds", "0.5", "--trace", "0", "--rehearse"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    assert check_line.problems(line, mf.load(), CELL, False) == []
    return json.loads(line)


def test_a_sound_rehearsal_is_correct(capsys):
    result = drive(capsys)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] >= 4
    assert set(result["compared"]) == {
        "node_hess_gap", "leaf_gap", "gain_gap", "split_gap", "margin_gap",
        "replica_gap", "rounds_gap"}


def test_a_window_that_boosts_at_another_rate(capsys, monkeypatch):
    """The window's calls state leaves of twice the step the configuration
    gives: every leaf of the followed trees reads 2x what its sums imply."""
    real = train_loop.train_call

    def broken(xgb, params, dtrain, rounds, bst, evals, sink):
        if bst is not None:
            params = dict(params, eta=2 * float(params["eta"]))
        return real(xgb, params, dtrain, rounds, bst, evals, sink)
    monkeypatch.setattr(train_loop, "train_call", broken)
    result = drive(capsys)
    assert result["correct"] is False
    assert result["compared"]["leaf_gap"]["value"] == pytest.approx(1.0,
                                                                    rel=0.05)


def test_a_window_that_returns_its_state_unchanged(capsys, monkeypatch):
    real = train_loop.train_call

    def broken(xgb, params, dtrain, rounds, bst, evals, sink):
        return bst if bst is not None else real(xgb, params, dtrain, rounds,
                                                bst, evals, sink)
    monkeypatch.setattr(train_loop, "train_call", broken)
    result = drive(capsys)
    assert result["correct"] is False
    assert result["compared"]["rounds_gap"]["value"] > 0
    # no followed tree to hold anything against
    assert result["compared"]["leaf_gap"]["value"] >= 1e300


def test_a_program_without_the_mesh_counters_is_refused(monkeypatch):
    import xgboost_tpu.obs.metrics as metrics
    monkeypatch.delattr(metrics, "mesh_counts")
    with pytest.raises(SystemExit) as stop:
        mesh_loop.require_program()
    assert stop.value.code == 4
