"""``lib/program_trace.py``: device time by innermost ``xtpu.<stage>`` scope
and idle time by program span, on synthetic planes; scopes of other source
refused; both readers of the ``.xplane.pb`` against
``jax.profiler.ProfileData`` on a trace made here; and the whole on a
cut-down copy of a chip trace of this PR's change."""

import glob
import os

import pytest

from conftest import BENCH_DIR
from lib import manifest as mf
from lib import program_trace as pt
from lib import trace_reduce as tr

MS = 1e6   # ns
ROUND_PROGRAMS = ["_fused_multi_round_fn", "_fused_round_fn"]
GROW = "jit(_fused_round_fn)/xtpu.grow/jit(_grow)/while/body/closed_call/"


def synthetic(spans=True):
    """Window 100..1100 ms. Two executions of the round program (150+200,
    600+250) and one of the eval walk (850+50). Ops: a ``while`` 150+200
    directly under ``xtpu.grow`` whose body holds a permute gather (300+50);
    a kernel 600+200 and an unscoped copy 800+50 in the second round; the
    eval walk's op 850+50 (not a round program's); and an op that began
    before the window inside no program (busy until 110). Idle: 110..150,
    350..600, 900..1100."""
    ops = [
        ["%sort.1", 50 * MS, 60 * MS, ""],
        ["%while.2", 150 * MS, 200 * MS, "jit(_fused_round_fn)/xtpu.grow/while"],
        ["%fusion.3", 300 * MS, 50 * MS,
         GROW + "xtpu.sort/xtpu.permute/jit(_take)/gather"],
        ["%scan_hist.4", 600 * MS, 200 * MS,
         GROW + "xtpu.sort/xtpu.kernel.scan_hist/pallas_call"],
        ["%copy.5", 800 * MS, 50 * MS, ""],
        ["%fusion.6", 850 * MS, 50 * MS, "jit(_predict_margin_binned)/walk"],
    ]
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit__fused_round_fn(1)", 150 * MS, 200 * MS],
            ["jit__fused_round_fn(1)", 600 * MS, 250 * MS],
            ["jit__predict_margin_binned(2)", 850 * MS, 50 * MS]],
         "stats": [{}, {}, {}]},
        {"name": "XLA Ops", "events": [o[:3] for o in ops],
         "stats": [{"tf_op": o[3]} if o[3] else {} for o in ops]}]}
    host_events = [[tr.WINDOW_SPAN, 100 * MS, 1000 * MS]]
    if spans:
        host_events += [
            ["train/call", 100 * MS, 980 * MS],
            ["round", 110 * MS, 440 * MS],
            ["round/fused", 120 * MS, 10 * MS],
            ["round/guard", 130 * MS, 410 * MS],
            ["round", 550 * MS, 500 * MS],
            ["round/eval", 880 * MS, 150 * MS],
            ["round/eval/pull", 950 * MS, 70 * MS],
            ["PjitFunction(_fused_round_fn)", 121 * MS, 5 * MS]]
    host = {"name": "/host:CPU", "lines": [{
        "name": "python3", "events": host_events,
        "stats": [{"iteration": 0}] * len(host_events)}]}
    return [host, dev]


def test_innermost_scope_wins_and_a_while_keeps_its_self_time():
    got = pt.stage_self_seconds(synthetic(), "tpu", ROUND_PROGRAMS)
    assert got == pytest.approx({
        "grow": 0.150,              # the while less its body
        "permute": 0.050,           # not sort, not grow: the innermost
        "kernel.scan_hist": 0.200,
        "": 0.050})                 # the unscoped copy of the second round
    # what the round programs ran, and no more: the eval walk's op and the
    # op that began outside any program are left out
    progs = tr.program_seconds(synthetic(), "tpu")
    assert sum(got.values()) == pytest.approx(
        tr.matching_seconds(progs, ROUND_PROGRAMS))


def test_stage_of_and_groups():
    assert pt.stage_of(GROW + "xtpu.sort/xtpu.count_sort/jit(argsort)/sort") \
        == "count_sort"
    assert pt.stage_of("jit(f)/while/body/add") == ""
    assert pt.stage_of(None) == ""
    assert pt.group_of("kernel.scan_hist") == "hist"
    assert pt.group_of("sort") == "partition"
    assert pt.group_of("grow") == "objective"
    assert pt.group_of("") == "" and pt.group_of("kernelx") == ""
    # every stage the program may emit has a group
    from xgboost_tpu.obs.trace import STAGES
    assert all(pt.group_of(s) for s in STAGES), \
        [s for s in STAGES if not pt.group_of(s)]


def test_idle_is_split_among_the_innermost_spans():
    idle = pt.idle_by_span(synthetic(), "tpu")
    assert sum(idle.values()) == pytest.approx(0.040 + 0.250 + 0.200)
    assert idle == pytest.approx({
        "round": 0.010 + 0.010 + 0.050 + 0.020,   # 110..120, 540..600,
                                                  # 1030..1050
        "round/fused": 0.010,                     # 120..130
        "round/guard": 0.020 + 0.190,             # 130..150, 350..540
        "round/eval": 0.050 + 0.010,              # 900..950, 1020..1030
        "round/eval/pull": 0.070,                 # 950..1020
        "train/call": 0.030,                      # 1050..1080
        "": 0.020})                               # 1080..1100: no span


def test_no_span_reads_all_idle_as_unattributed():
    idle = pt.idle_by_span(synthetic(spans=False), "tpu")
    assert idle == pytest.approx({"": 0.490})


def test_readers_on_synthetic_planes(monkeypatch):
    facts = {"trace": {"rounds": 2}, "platform": "tpu",
             "round_programs": ROUND_PROGRAMS}
    monkeypatch.setattr(pt, "last_trace", lambda: synthetic())
    # a trace this process did not make: nothing to hold its scopes to
    monkeypatch.setattr(pt, "program_exports", lambda programs: None)
    pt._group_seconds_once.cache_clear()
    read = {m: mf.layer_reader(m).read for m in (
        "stage_partition_ms", "stage_permute_ms", "stage_hist_ms",
        "stage_split_ms", "stage_objective_ms", "stage_unattributed_pct",
        "idle_round_driver_ms", "idle_eval_ms", "idle_unattributed_pct")}
    assert read["stage_permute_ms"](facts) == pytest.approx(25.0)
    assert read["stage_hist_ms"](facts) == pytest.approx(100.0)
    assert read["stage_objective_ms"](facts) == pytest.approx(75.0)
    assert read["stage_partition_ms"](facts) == 0.0     # 0.0, never None
    assert read["stage_split_ms"](facts) == 0.0
    assert read["stage_unattributed_pct"](facts) == pytest.approx(
        100 * 0.05 / 0.45)
    stage_ms = sum(read[m](facts) for m in read if m.endswith("_ms")
                   and m.startswith("stage_"))
    assert stage_ms == pytest.approx(225.0 * (1 - 0.05 / 0.45))
    assert read["idle_round_driver_ms"](facts) == pytest.approx(
        1e3 * (0.090 + 0.010 + 0.210 + 0.030) / 2)
    assert read["idle_eval_ms"](facts) == pytest.approx(1e3 * 0.130 / 2)
    assert read["idle_unattributed_pct"](facts) == pytest.approx(
        100 * 0.020 / 0.490)
    monkeypatch.setattr(pt, "last_trace", lambda: synthetic(spans=False))
    assert read["idle_unattributed_pct"](facts) == 100.0
    assert read["idle_round_driver_ms"](facts) == 0.0
    # an untraced run: nothing to read
    for m, fn in read.items():
        assert fn({"trace": None}) is None, m


EXPORTS = {"roots": {"gradient", "grow", "leaf", "margin"},
           "opened": {"gradient", "grow", "sort", "permute", "exchange",
                      "kernel.scan_hist", "margin"},
           "served": {"_fused_round_fn": "hit"}}


def test_scope_check_tells_proof_from_suspicion():
    paths = [GROW + "xtpu.sort/xtpu.permute/jit(_take)/gather",
             "jit(_fused_round_fn)/xtpu.grow/while", "",
             "xtpu.sort/reduce_sum"]                 # a reducer's cut path
    assert pt.scope_check(paths, EXPORTS) == {
        "foreign": [],
        "absent": ["exchange", "gradient", "kernel.scan_hist", "margin"]}
    # the layout of the source before PR 27: no xtpu.grow around the tree
    old = "jit(_fused_round_fn)/jit(_grow)/while/body/xtpu.sort/gather"
    assert pt.scope_check(paths + [old], EXPORTS)["foreign"] == ["sort"]
    # a stage this process never opened
    gone = GROW + "xtpu.sort/xtpu.rowmove/gather"
    assert pt.scope_check([gone], EXPORTS)["foreign"] == [
        "grow/sort/rowmove"]


@pytest.mark.parametrize("stale", [False, True])
def test_readers_refuse_scopes_of_other_source(monkeypatch, capsys, stale):
    """A round program served by the compile cache with another source's
    scopes in it: every stage reads 0 and all of it unattributed, loudly."""
    planes = synthetic()
    if stale:
        ops = planes[1]["lines"][1]
        ops["stats"][2] = {"tf_op": "jit(_fused_round_fn)/jit(_grow)/while/"
                                    "body/xtpu.sort/jit(_take)/gather"}
    facts = {"trace": {"rounds": 2}, "platform": "tpu",
             "round_programs": ROUND_PROGRAMS}
    monkeypatch.setattr(pt, "last_trace", lambda: planes)
    monkeypatch.setattr(pt, "program_exports", lambda programs: EXPORTS)
    pt._group_seconds_once.cache_clear()
    read = {m: mf.layer_reader(m).read(facts) for m in (
        "stage_partition_ms", "stage_permute_ms", "stage_hist_ms",
        "stage_split_ms", "stage_objective_ms", "stage_unattributed_pct")}
    pt._group_seconds_once.cache_clear()
    err = capsys.readouterr().err
    if stale:
        assert read.pop("stage_unattributed_pct") == 100.0
        assert set(read.values()) == {0.0}
        assert "STALE SCOPES (_fused_round_fn cache=hit)" in err
    else:
        assert read["stage_permute_ms"] == pytest.approx(25.0)
        assert read["stage_unattributed_pct"] == pytest.approx(100 / 9)
        assert "scopes: _fused_round_fn cache=hit; opened by this source " \
               "and on no op of the trace: ['exchange', 'gradient', " \
               "'margin']" in err


def test_counter_reader_sums_the_round_programs():
    read = mf.layer_reader("round_program_trace_lower_s").read
    assert read({"trace": None}) is None          # no round_programs given
    from xgboost_tpu.obs import metrics as om
    reg = om.get_registry()
    before = read({"round_programs": ROUND_PROGRAMS})
    reg.inc("xtpu_program_trace_lower_seconds_total", by=1.5,
            labels=(("program", "_fused_round_fn"),))
    reg.inc("xtpu_program_trace_lower_seconds_total", by=2.0,
            labels=(("program", "_predict_margin_binned"),))
    assert read({"round_programs": ROUND_PROGRAMS}) - before == \
        pytest.approx(1.5)


def test_readers_agree_with_profile_data(tmp_path, monkeypatch):
    """``load`` reads what ``jax.profiler.ProfileData`` reads (names, starts,
    lengths of every event of every line), plus a span's args; and the
    reader of the wire format reads what ``xplane_pb2`` reads, stats too."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("xtpu.permute"):
            return jnp.take(x, jnp.arange(x.shape[0])[::-1])

    f(jnp.arange(64.0)).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.StepTraceAnnotation("round", step_num=7, rounds=4):
            with jax.profiler.TraceAnnotation("round/batch", iteration=7):
                f(jnp.arange(64.0)).block_until_ready()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    mine, theirs = pt.load(path), tr.load_xplane(path)
    with open(path, "rb") as fh:
        space = fh.read()
    if pt.xplane_pb2() is not None:      # else ``mine`` is the wire reader's
        assert mine == pt._load_pb2(pt.xplane_pb2(), space) \
            == pt._load_wire(space)
    # a machine without tensorflow: the search finds nothing, ``load`` reads
    # the wire format
    pt.xplane_pb2.cache_clear()
    monkeypatch.setattr(pt.importlib.util, "find_spec", lambda name: None)
    assert pt.xplane_pb2() is None and pt.load(path) == mine
    monkeypatch.undo()
    pt.xplane_pb2.cache_clear()
    assert [p["name"] for p in mine] == [p["name"] for p in theirs]
    n = 0
    for pm, pr in zip(mine, theirs):
        assert [ln["name"] for ln in pm["lines"]] == \
            [ln["name"] for ln in pr["lines"]]
        for lm, lr in zip(pm["lines"], pr["lines"]):
            assert len(lm["events"]) == len(lm["stats"]) == len(lr["events"])
            for em, er in zip(lm["events"], lr["events"]):
                assert em[0] == er[0]
                assert em[1] == pytest.approx(er[1], abs=1.0)
                assert em[2] == pytest.approx(er[2], abs=1.0)
                n += 1
    assert n > 5
    assert tr.traced_interval(mine) == pytest.approx(
        tr.traced_interval(theirs))
    spans = {e[0]: st for p in mine for ln in p["lines"]
             for e, st in zip(ln["events"], ln["stats"]) if pt.SPAN.match(e[0])}
    assert spans["round"]["step_num"] == 7 and spans["round"]["rounds"] == 4
    assert spans["round/batch"]["iteration"] == 7
    # the rehearsal's rule: the host pool is the device, spans still split
    # its idle time
    idle = pt.idle_by_span(mine, "cpu")
    assert set(idle) <= {"", "round", "round/batch"} and idle


# ---- the recorded fixture: the first 2 rounds of a traced chip run of
# higgs-11m.train-eval on this PR's change (TPU v5 lite, seed 2147485101),
# cut by ``program_trace.py OUT _fused_round_fn 2``

FIXTURE = os.path.join(BENCH_DIR, "fixtures",
                       "tpu-v5e-train-eval-stages.json.gz")


def test_recorded_chip_trace_reads_in_stages_and_spans():
    planes = tr.load_fixture(FIXTURE)
    progs = tr.program_seconds(planes, "tpu")
    counts = tr.program_counts(planes, "tpu")
    assert counts["jit__fused_round_fn"] == 2
    assert counts["jit__predict_margin_binned"] == 2
    rounds_s = tr.matching_seconds(progs, ROUND_PROGRAMS)
    assert rounds_s == pytest.approx(13.387723, rel=1e-6)

    stages = pt.stage_self_seconds(planes, "tpu", ROUND_PROGRAMS)
    # every op of the round programs and nothing of the eval walk
    assert sum(stages.values()) == pytest.approx(rounds_s, rel=1e-5)
    for stage, sec in {"count_sort": 5.545290, "permute": 5.497932,
                       "advance": 1.248427, "kernel.scan_hist": 0.872733,
                       "leaf": 0.172613, "fold": 0.018761,
                       "": 0.015741}.items():
        assert stages[stage] == pytest.approx(sec, rel=1e-4), stage
    assert 100 * stages[""] / rounds_s < 0.2
    from xgboost_tpu.obs.trace import STAGES
    assert set(stages) - {""} <= set(STAGES)
    # the kernel's scope and its custom-call mark are the same ops
    mosaic = sum(s for name, s in tr.op_self_seconds(planes, "tpu").items()
                 if 'custom_call_target="tpu_custom_call"' in name)
    assert stages["kernel.scan_hist"] == pytest.approx(mosaic, rel=1e-9)
    assert any(name.startswith("%scan_hist") for name in
               tr.op_self_seconds(planes, "tpu"))     # the kernel's name=

    groups = {g: 0.0 for g in list(pt.GROUPS) + [""]}
    for stage, sec in stages.items():
        groups[pt.group_of(stage)] += sec
    assert groups["partition"] == pytest.approx(6.794320, rel=1e-4)
    assert groups["hist"] == pytest.approx(0.892208, rel=1e-4)

    idle = pt.idle_by_span(planes, "tpu")
    busy, window = tr.busy_and_window(planes, "tpu", 1)
    assert sum(idle.values()) == pytest.approx(window - busy, rel=1e-6)
    assert max(idle, key=idle.get) == "round/eval"
    assert idle["round/eval"] == pytest.approx(0.012730, rel=1e-3)
    assert idle["round/flush"] == pytest.approx(0.004336, rel=1e-3)
    assert 100 * idle[""] / sum(idle.values()) < 1.0
    # the largest gap is named by a span of the program, not by the harness
    assert tr.idle_gaps(planes, "tpu", 10)[0][0].startswith("round")
    # spans carry the round they belong to
    spans = [(e[0], st) for p in planes if p["name"] == "/host:CPU"
             for ln in p["lines"] for e, st in zip(ln["events"], ln["stats"])
             if pt.SPAN.match(e[0])]
    assert ("train/call", {"iteration": 4, "rounds": 4}) in spans
    assert ("round", {"step_num": 4, "iteration": 4, "rounds": 1}) in spans
    assert ("round/flush", {"iteration": 5, "trees": 1}) in spans
    assert all("iteration" in st for _name, st in spans)
    # and its scopes are this source's: nothing foreign, all but the
    # mesh's exchange on some op
    from xgboost_tpu.obs.trace import ROUND_ROOTS
    paths = [tf_op for _s, _e, inside, tf_op in
             pt.round_ops(planes, "tpu", ROUND_PROGRAMS) if inside]
    opened = set(stages) - {""} | {"exchange"}
    assert pt.scope_check(paths, {"roots": set(ROUND_ROOTS),
                                  "opened": opened}) == {
        "foreign": [], "absent": ["exchange"]}
