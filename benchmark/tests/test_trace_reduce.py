"""The reduction from a profiler trace to numbers: on the cut-down copy of a
real TPU trace recorded in this PR's first chip call, and on a synthetic trace
with overlapping lines and two device planes, which must not double-count."""

import os

import pytest

from conftest import BENCH_DIR
from lib import trace_reduce as tr

FIXTURE = os.path.join(BENCH_DIR, "fixtures", "tpu-v5e-train.json.gz")
MS = 1e6   # ns


def synthetic():
    """Window 100..1100 ms. Device 0's op line: ops at 150+200, 300+50
    (inside the first: a loop's body), 600+300, one before the window (50+100,
    clipped to 100..150) -> union 50+200+300 = 550 ms. Its module line holds
    the enclosing programs, its step line the whole step: summing lines would
    double-count. Device 1 is busy 900 ms: summing planes would exceed the
    window."""
    dev0 = {"name": "/device:TPU:0", "lines": [
        {"name": "Steps", "events": [["0", 100 * MS, 1000 * MS]]},
        {"name": "XLA Modules", "events": [
            ["jit__fused_multi_round_fn(123)", 150 * MS, 200 * MS],
            ["jit__fused_multi_round_fn(123)", 600 * MS, 250 * MS],
            ["jit__eval_partials_fn(9)", 850 * MS, 50 * MS]]},
        {"name": "XLA Ops", "events": [
            ["sort.1", 50 * MS, 100 * MS], ["while.2", 150 * MS, 200 * MS],
            ["fusion.3", 300 * MS, 50 * MS], ["custom-call.4", 600 * MS,
                                               300 * MS]]}]}
    dev1 = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["fusion.9", 150 * MS, 900 * MS]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        [tr.WINDOW_SPAN, 100 * MS, 1000 * MS],
        ["bench.call", 100 * MS, 480 * MS], ["bench.call", 580 * MS, 520 * MS],
        ["PjitFunction(_fused_multi_round_fn)", 101 * MS, 5 * MS]]}]}
    return [host, dev0, dev1]


def test_busy_is_a_union_of_one_line_of_one_plane():
    busy, window = tr.busy_and_window(synthetic(), "tpu", chips=1)
    assert window == pytest.approx(1.0)
    assert busy == pytest.approx(0.550)          # not 0.700 (ops summed),
    assert 0 < busy <= window                    # not 1.55+ (lines summed)


def test_two_planes_are_averaged_never_summed():
    busy, window = tr.busy_and_window(synthetic(), "tpu", chips=2)
    assert busy == pytest.approx((0.550 + 0.900) / 2)
    assert busy <= window
    with pytest.raises(ValueError):
        tr.busy_and_window(synthetic(), "tpu", chips=4)


def test_programs_are_read_by_name_and_clipped():
    progs = tr.program_seconds(synthetic(), "tpu")
    assert progs == pytest.approx({"jit__fused_multi_round_fn": 0.450,
                                   "jit__eval_partials_fn": 0.050})
    assert tr.matching_seconds(progs, ["_fused_multi_round_fn",
                                       "_fused_round_fn"]) == \
        pytest.approx(0.450)


def test_breakdown_names_ops_and_gaps():
    ops = tr.top_device_ops(synthetic(), "tpu", 10)
    assert ops[0][0] == "custom-call.4" and ops[0][1] == pytest.approx(0.3)
    assert ["sort.1", pytest.approx(0.05)] in ops       # clipped
    gaps = dict(tr.idle_gaps(synthetic(), "tpu", 10))
    assert sum(gaps.values()) == pytest.approx(1.0 - 0.550)
    assert set(gaps) == {"bench.call"}


def test_no_window_annotation_is_an_error():
    planes = [p for p in synthetic() if p["name"] != "/host:CPU"]
    with pytest.raises(ValueError):
        tr.traced_interval(planes)


def test_union_seconds():
    assert tr.union_seconds([(0, 10), (5, 20), (30, 40)], 0, 100) == \
        pytest.approx(30e-9)
    assert tr.union_seconds([(0, 10)], 20, 30) == 0.0
    assert tr.union_seconds([], 0, 1) == 0.0


# ---- the recorded fixtures: cut-down copies of the traces of PR 26's first
# chip call (TPU v5 lite, higgs-11m, one call of 4 rounds each)

@pytest.mark.parametrize("name, busy, window, programs, counts", [
    ("tpu-v5e-train.json.gz", 26.695255387, 26.698691207,
     {"jit__fused_multi_round_fn": 26.695255},
     {"jit__fused_multi_round_fn": 1}),
    ("tpu-v5e-train-eval.json.gz", None, 27.670321265,
     {"jit__fused_round_fn": 26.7796, "jit__predict_margin_binned": 0.8431},
     {"jit__fused_round_fn": 4, "jit__eval_partials_fn": 4}),
])
def test_recorded_tpu_trace(name, busy, window, programs, counts):
    planes = tr.load_fixture(os.path.join(BENCH_DIR, "fixtures", name))
    assert tr.device_planes(planes, "tpu") == ["/device:TPU:0"]
    got_busy, got_window = tr.busy_and_window(planes, "tpu", 1)
    assert got_window == pytest.approx(window, rel=1e-9)
    assert 0 < got_busy <= got_window
    if busy is not None:       # the cut keeps the while op that spans the call
                               # (the whole trace read 26.695369149: the cut
                               # lost the ops after it)
        assert got_busy == pytest.approx(busy, rel=1e-9)
    progs = tr.program_seconds(planes, "tpu")
    for prog, sec in programs.items():
        assert progs[prog] == pytest.approx(sec, rel=1e-4)
    got_counts = tr.program_counts(planes, "tpu")
    for prog, n in counts.items():
        assert got_counts[prog] == n        # rounds counted from the trace
    self_s = tr.op_self_seconds(planes, "tpu")
    assert sum(self_s.values()) == pytest.approx(got_busy, rel=1e-9)
    assert any('custom_call_target="tpu_custom_call"' in k for k in self_s)
    ops = tr.top_device_ops(planes, "tpu", 10)
    assert len(ops) == 10 and all(len(n) <= 100 for n, _s in ops)
    assert tr.idle_gaps(planes, "tpu", 10)


def test_short_op_name():
    full = ('%fusion.8 = u8[10502144,28]{0,1:T(8,128)(4,1)} fusion(u8[28,'
            '10500000]{1,0} %bitcast.9), kind=kCustom, calls=%fused.8')
    assert tr.short_op_name(full) == "fusion.8 fusion u8[10502144,28]"
    call = ('%custom-call.4 = s32[129,28,256,4]{3,2,1,0} custom-call(u8[28,8]'
            ' %x), custom_call_target="tpu_custom_call"')
    assert tr.short_op_name(call).startswith("custom-call.4 tpu_custom_call")
