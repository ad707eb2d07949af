"""The ``setup_*`` per-layer metrics: each reader against a hand-made start-up
report, against a program that has none, and in a traced rehearsal of every
one-chip cell (the four-chip cell cannot rehearse traced on the CPU: PERF.md
section 7; its reader is checked on the chip)."""

import json
import os
import subprocess
import sys

import pytest

import check_line
from conftest import ROOT
from lib import manifest as mf
from lib import startup

REPORT = {"before_import": 11.0, "import": 5.25, "caller": 1.5,
          "ingest/sketch": 4.0, "ingest/bin": 1.0, "ingest/upload": 0.25,
          "ingest/next": 24.0, "train/state": 0.5, "rank/layout": 0.125,
          "round": 0.75, "program/trace_lower": 16.0, "program/compile": 6.0,
          "native/build": 9.0, "unattributed": 0.625, "total": 80.0}

WANT = {"setup_before_import_s": 11.0, "setup_import_s": 5.25,
        "setup_caller_s": 1.5, "setup_sketch_s": 4.0,
        "setup_bin_upload_s": 1.25, "setup_data_wait_s": 24.0,
        "setup_state_s": 0.625, "setup_first_rounds_s": 0.75,
        "setup_unattributed_pct": 100 * 0.625 / 80.0}

COUNTS = {"_fused_multi_round_fn": {"compiles": 1, "compile_s": 6.0,
                                    "cache_hits": 0, "cache_misses": 1,
                                    "trace_lower_s": 15.0},
          "convert_element_type": {"compiles": 3, "compile_s": 0.1,
                                   "cache_hits": 0, "cache_misses": 0,
                                   "trace_lower_s": 0.01},
          "_grow": {"compiles": 2, "compile_s": 9.0, "cache_hits": 1,
                    "cache_misses": 1, "trace_lower_s": 1.0}}

ONE_CHIP = [w["name"] for w in mf.load()["workloads"] if w["chips"] == 1]


def _setup_metrics(manifest, cell):
    return [m["name"] for m in mf.metrics_of(manifest, "per_layer", cell)
            if m["name"].startswith("setup_")]


@pytest.fixture
def program(monkeypatch):
    """The program's two sources, with hand-made contents."""
    from xgboost_tpu.obs import metrics

    def put(report, counts=COUNTS):
        monkeypatch.setattr(metrics, "startup_report",
                            lambda: None if report is None else dict(report),
                            raising=False)
        monkeypatch.setattr(metrics, "program_compile_counts",
                            lambda: {k: dict(v) for k, v in counts.items()})
    return put


def test_the_manifest_lists_the_ten_with_their_layers_and_cells():
    manifest = mf.load()
    new = {m["name"]: m for m in manifest["per_layer"]
           if m["name"].startswith("setup_")}
    assert set(new) == set(WANT) | {"setup_compiled_programs"}
    for m in new.values():
        assert m["moves"] == "setup_s" and m["better"] == "lower"
    assert {n for n, m in new.items() if m["layer"] == "process"} == {
        "setup_before_import_s", "setup_import_s", "setup_caller_s",
        "setup_unattributed_pct"}
    assert {n for n, m in new.items() if m["layer"] == "ingest"} == {
        "setup_sketch_s", "setup_bin_upload_s", "setup_data_wait_s"}
    assert new["setup_data_wait_s"]["workloads"] == ["criteo-ctr.mesh-train"]
    assert all("workloads" not in m for n, m in new.items()
               if n != "setup_data_wait_s")
    for cell in ONE_CHIP:
        assert len(_setup_metrics(manifest, cell)) == 9
    assert len(_setup_metrics(manifest, "criteo-ctr.mesh-train")) == 10


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_against_a_hand_made_report(program, name):
    program(REPORT)
    value = mf.layer_reader(name).read({"round_programs": ["_fused"]})
    assert value == pytest.approx(WANT[name], abs=1e-12)


@pytest.mark.parametrize("name", sorted(WANT) + ["setup_compiled_programs"])
@pytest.mark.parametrize("how", ["not_frozen", "no_such_function"])
def test_reader_against_a_program_without_a_report(program, monkeypatch,
                                                   name, how):
    from xgboost_tpu.obs import metrics

    program(None, counts={k: {f: v for f, v in c.items()
                              if f != "cache_misses"}
                          for k, c in COUNTS.items()})
    if how == "no_such_function":            # the parent: an ImportError
        monkeypatch.delattr(metrics, "startup_report")
        monkeypatch.delattr(metrics, "program_compile_counts")
    assert mf.layer_reader(name).read({"round_programs": ["x"]}) is None


def test_a_phase_the_run_never_opened_reads_zero(program):
    program({"import": 5.0, "caller": 1.0, "unattributed": 0.0,
             "total": 6.0})
    assert mf.layer_reader("setup_data_wait_s").read({}) == 0.0
    assert mf.layer_reader("setup_state_s").read({}) == 0.0
    # but an unknown start of the process is left out, not put at zero
    assert mf.layer_reader("setup_before_import_s").read({}) is None


def test_compiled_programs_counts_what_the_cache_was_handed(program):
    program(REPORT)
    reader = mf.layer_reader("setup_compiled_programs")
    assert reader.read({}) == 2.0
    served = {k: dict(c, cache_hits=c["compiles"], cache_misses=0)
              for k, c in COUNTS.items() if c["compile_s"] >= 1}
    served["convert_element_type"] = COUNTS["convert_element_type"]
    program(REPORT, counts=served)           # small programs still compile
    assert reader.read({}) == 0.0


def test_the_report_goes_to_stderr_as_one_line(program, capsys):
    program(REPORT)
    mf.layer_reader("setup_unattributed_pct").read(
        {"round_programs": ["_fused_multi_round_fn"]})
    lines = [ln for ln in capsys.readouterr().err.splitlines() if ln]
    assert len(lines) == 1 and lines[0].startswith(
        "[bench] start-up report: total 80.00: ingest/next 24.00, "
        "program/trace_lower 16.00, before_import 11.00, native/build 9.00")
    assert "round programs trace+lower 15.00, compile or load 6.00" \
        in lines[0]
    assert "programs compiled 6, served by the cache 1, written to it 2" \
        in lines[0]
    assert startup.line({"total": 1.0, "caller": 1.0}).startswith(
        "total 1.00: caller 1.00")


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_traced_rehearsal_prints_every_setup_metric(tmp_path, cell):
    manifest = mf.load()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    run = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(2 ** 31 + 4321), "--seconds", "1",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=900, cwd=ROOT)
    assert run.returncode == 0, run.stderr[-3000:]
    line = run.stdout.strip().splitlines()[-1]
    # (a CPU traces no Mosaic kernel: the sparse cell's kernel metrics are
    # missing from a rehearsal, as they were before these)
    assert [p for p in check_line.problems(line, manifest, cell, True)
            if "setup_" in p] == []
    metrics = json.loads(line)["metrics"]
    names = _setup_metrics(manifest, cell)
    assert "setup_data_wait_s" not in names
    values = {n: metrics[n]["value"] for n in names}     # every one is there
    assert all(v >= 0 for v in values.values()), values
    # (at a rehearsal's size a round program may compile in under jax's 1 s
    # and never be written: "over 0 on an empty cache" is the chip's to show)
    assert values["setup_unattributed_pct"] < 5
    assert "[bench] start-up report: total " in run.stderr
    # the parts the metrics name, the compile path and the rest add up to
    # the report's total, which is the run's own setup_s to the
    # interpreter's start and the driver's last block_until_ready
    report = next(ln for ln in run.stderr.splitlines()
                  if "start-up report" in ln)
    total = float(report.split("total ")[1].split(":")[0])
    assert abs(total - metrics["setup_s"]["value"]) < 1.0, report
