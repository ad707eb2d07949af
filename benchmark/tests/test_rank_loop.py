"""The ranking cell's own files: the seeded generator, the plain LambdaMART
reference, the pair counts, the ``rank.*`` readers, the control and the six
planted faults under the cell's limits, and whole rehearsal runs with the
timed path broken underneath."""

import json

import numpy as np
import pytest

import check_line
import run as bench_run
from drivers import rank_loop, train_loop
from lib import compare, data_rank, manifest as mf, rank_trace
from lib import reference_rank as rr

CELL = "istella-letor.train"


# ---- the generator ----------------------------------------------------------

def test_group_sizes_add_up_and_stay_in_range():
    for rows, groups, seed in [(40000, 128, 3), (7325625, 23219, 2 ** 31 + 5)]:
        sizes = data_rank.group_sizes(rows, groups, seed)
        assert sizes.sum() == rows and len(sizes) == groups
        assert sizes.min() >= data_rank.MIN_SIZE
        assert sizes.max() <= data_rank.MAX_SIZE
        assert np.array_equal(sizes, data_rank.group_sizes(rows, groups, seed))
    assert sizes.max() == 1024            # the longest group of the cell


def test_generator_is_seeded_and_labels_spread():
    X, y, ptr = data_rank.istella_like(30000, 220, 96, 11)
    X2, y2, ptr2 = data_rank.istella_like(30000, 220, 96, 11)
    assert np.array_equal(X, X2) and np.array_equal(y, y2)
    assert not np.array_equal(X, data_rank.istella_like(30000, 220, 96, 12)[0])
    assert X.dtype == np.float32 and set(np.unique(y)) <= {0, 1, 2, 3, 4}
    top = np.maximum.reduceat(y, ptr[:-1])
    assert (top == 0).any() and (top >= 3).any()   # barren and rich queries
    assert 0.7 < (y == 0).mean() < 0.97
    # a query-level feature reads nearly the same within a query
    q = np.repeat(np.arange(96), np.diff(ptr))
    within = np.mean([X[q == k, 0].std() for k in range(96)])
    assert within < 1.1 < X[:, 0].std()


# ---- the reference ----------------------------------------------------------

def test_reference_lambdas_cancel_and_count_each_pair_once():
    rng = np.random.default_rng(0)
    y = rng.integers(0, 5, 50).astype(np.float64)
    s = rng.standard_normal(50)
    g, h = rr.query_lambdas(s, y, 32)
    assert abs(g.sum()) < 1e-12 and (h >= 0).all()
    # two rows in the truncation: one pair, in closed form
    g2, h2 = rr.query_lambdas(np.array([0.3, -0.2]), np.array([0.0, 1.0]), 32)
    p = 1.0 / (1.0 + np.exp(-0.5))
    delta = abs(1.0 / np.log2(3.0) - 1.0)
    assert np.allclose(g2, [p * delta, -p * delta])
    assert np.allclose(h2, [p * (1 - p) * delta] * 2)
    # a truncation of 1 keeps the pairs of the best-ranked row only
    g1, _ = rr.query_lambdas(s, y, 1)
    best = np.argmax(s)
    others = np.delete(np.arange(50), best)
    assert np.count_nonzero(g1[others]) == np.count_nonzero(y != y[best])


def test_host_pair_counts_against_a_loop():
    ptr = np.array([0, 1, 6, 46, 48, 61])
    slots, kept = rank_loop.host_pair_counts(ptr, 8, chunk_rule=40 * 40 * 2)
    want = 0
    for n in np.diff(ptr):
        want += sum(n - 1 - r for r in range(min(8, n)))
    assert kept == want
    assert slots == 6 * 40 * 40           # 5 groups in chunks of 2: 6 swept
    assert rank_loop.host_pair_counts(ptr, 0)[1] == sum(
        n * (n - 1) // 2 for n in np.diff(ptr))


# ---- the readers ------------------------------------------------------------

def test_rank_scope_readers():
    pre = "jit(_lambda_grad_device)/xtpu.gradient/"
    assert rank_trace.part_of(pre + "rank.pairs/while/body/rank.order/sort"
                              ) == "order"
    assert rank_trace.part_of(pre + "rank.reduce/gather:") == "reduce"
    assert rank_trace.part_of("jit(_grow)/xtpu.grow/xtpu.hist/add") == ""
    from lib import program_trace as pt
    assert pt.stage_of(pre + "rank.pairs/while/body/mul") == "gradient"
    assert pt.group_of("gradient") == "objective"

    def ev(name, start, dur, tf_op=None):
        return [name, start, dur], ({} if tf_op is None else {"tf_op": tf_op})
    ops = [ev("%while", 100, 800, pre + "rank.pairs/while"),
           ev("%fusion.1", 120, 300, pre + "rank.pairs/while/body/mul"),
           ev("%sort.1", 450, 200, pre + "rank.pairs/while/body/rank.order/"
                                        "sort"),
           ev("%gather", 900, 50, pre + "rank.reduce/gather"),
           ev("%copy", 950, 10)]
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit__lambda_grad_device(1)", 100, 900]], "stats": [{}]},
            {"name": "XLA Ops", "events": [e for e, _ in ops],
             "stats": [s for _, s in ops]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["bench.traced_window", 0, 2000],
                                          ["round/gradient", 10, 40]],
             "stats": [{}, {"objective": "rank:ndcg", "groups": 5,
                            "layout_key_ms": 0.25}]}]}]
    parts = rank_trace.part_self_seconds(planes, "tpu", "_lambda_grad_device")
    assert parts["pairs"] == pytest.approx((800 - 500 + 300) / 1e9)
    assert parts["order"] == pytest.approx(200 / 1e9)
    assert parts["reduce"] == pytest.approx(50 / 1e9)
    assert parts[""] == pytest.approx(10 / 1e9)
    spans = rank_trace.gradient_span_stats(planes)
    assert spans == [{"objective": "rank:ndcg", "groups": 5,
                      "layout_key_ms": 0.25}]


def test_counter_reader_and_readers_with_nothing_to_read():
    read = mf.layer_reader("rank_pair_fill_pct").read
    assert read({"rank": {"pair_slots": 400.0, "pairs_kept": 3.0}}) == 0.75
    assert read({}) is None
    for name in ("rank_gradient_ms", "rank_pairs_ms", "rank_order_ms",
                 "rank_layout_ms", "idle_gradient_ms"):
        assert mf.layer_reader(name).read({"trace": None}) is None


# ---- the control and the faults, under the cell's limits --------------------

@pytest.fixture(scope="module")
def readings():
    cell = mf.cell(mf.load(), CELL)
    config = {**cell["config"], **cell["config"]["rehearse"],
              "params": {**cell["config"]["params"],
                         **cell["config"]["rehearse"]["params"]}}
    return cell["limits"], rank_loop.control_readings(
        config, cell["traffic"], seed=2 ** 31 + 7)


@pytest.mark.parametrize("case, want", [
    ("sound", True), ("control_bf16", False), ("half_queries", False),
    ("state_unchanged", False), ("stale_margin", False),
    ("groups_shifted", False), ("all_pairs", False)])
def test_cases_against_the_cells_limits(readings, case, want):
    limits, by_case = readings
    ok, table = compare.judge(by_case[case], limits)
    assert ok is want, (case, table)


def test_what_fails_what(readings):
    limits, by_case = readings
    over = {case: {k for k, v in vals.items() if not v <= limits[k]}
            for case, vals in by_case.items()}
    assert "margin_gap" in over["control_bf16"], over
    assert "grad_gap" in over["half_queries"], over
    assert "grad_gap" in over["stale_margin"], over
    assert {"grad_gap", "metric_gap"} <= over["groups_shifted"], over
    assert "grad_gap" in over["all_pairs"], over
    assert {"update_gap", "rounds_gap", "ndcg_gap"} <= \
        over["state_unchanged"], over
    assert by_case["state_unchanged"]["update_gap"] == 1.0
    assert all(v < 1e-6 for v in by_case["sound"].values())   # f32 leaves


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


def test_truncation_ignored_in_the_window(capsys, monkeypatch):
    break_window(monkeypatch, lambda xgb, params, dtrain: (
        dict(params, lambdarank_num_pair_per_sample=0), dtrain))
    result = drive(capsys)
    assert result["correct"] is False
    limit = result["compared"]["grad_gap"]["limit"]
    assert result["compared"]["grad_gap"]["value"] > 3 * limit


def test_half_of_the_queries_left_out_in_the_window(capsys, monkeypatch):
    half = {}

    def change(xgb, params, dtrain):
        if "dm" not in half:
            X = np.asarray(dtrain.values())
            y = np.asarray(dtrain.get_label())
            sizes = np.diff(np.asarray(dtrain.info.group_ptr))
            k = len(sizes) // 2
            n = int(sizes[:k].sum())
            half["dm"] = xgb.DMatrix(X[:n], label=y[:n], group=sizes[:k])
        return params, half["dm"]
    break_window(monkeypatch, change)
    result = drive(capsys)
    assert result["correct"] is False
