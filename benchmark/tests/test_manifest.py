"""``BENCHMARK.json`` against the files it names, and the proof that a cell
and a per-layer metric are added as new files only."""

import hashlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import check_line
from conftest import BENCH_DIR, ROOT
from lib import manifest as mf

NAME, UNIT = check_line.NAME, check_line.UNIT
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return mf.load()


def test_keys_and_sizes(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert manifest["paths"] == ["benchmark"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"]:
        assert 0 < m["bound"] <= 0.1
        assert m["source"] in {"host_clock", "device_trace"}
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 4)


def test_every_workload_resolves(manifest):
    used = set()
    for w in manifest["workloads"]:
        cell = mf.cell(manifest, w["name"])
        used.add(w["config"])
        assert cell["config"]["params"]["max_depth"] > 0
        assert cell["traffic"]["driver"]
        assert os.path.exists(os.path.join(
            BENCH_DIR, "drivers", cell["traffic"]["driver"] + ".py"))
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert mf.metrics_of(manifest, "end_to_end", w["name"])
        assert mf.metrics_of(manifest, "per_layer", w["name"])
    assert used == {c["name"] for c in manifest["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_configs(manifest):
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmark/")
        assert 1 <= len(c["source"]) <= 200 and "\n" not in c["source"]
        body = json.load(open(os.path.join(ROOT, c["file"])))
        assert set(c["reduced"]) == set(body.get("reduced", {}))
        assert len(c["reduced"]) <= 16


def test_names_units_and_layers(manifest):
    names = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    names += [w["name"] for w in manifest["workloads"]]
    names += [c["name"] for c in manifest["configs"]]
    names += [w["traffic"] for w in manifest["workloads"]]
    assert all(NAME.match(n) for n in names), names
    metric_names = [m["name"]
                    for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_per_layer_metric_files_agree(manifest):
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    cells = {w["name"] for w in manifest["workloads"]}
    for m in manifest["per_layer"]:
        mod = mf.layer_reader(m["name"])
        assert m["moves"] in e2e
        # each of its cells reports the end-to-end metric it moves
        for name in m.get("workloads", cells):
            assert name in cells
            assert m["moves"] in {x["name"] for x in mf.metrics_of(
                manifest, "end_to_end", name)}
        assert mod.read({"trace": None}) is None or m["source"] != \
            "device_trace"
    on_disk = {f[:-3] for f in os.listdir(
        os.path.join(BENCH_DIR, "layer_metrics")) if f.endswith(".py")}
    assert on_disk == {m["name"] for m in manifest["per_layer"]}
    # a roofline share is named <kernel>_roofline with unit %, and the whole
    # step's share of the peak carries mfu as a part of its name
    assert any("mfu" in re.split(r"[_.\-]", m["name"])
               for m in manifest["per_layer"])
    for m in manifest["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


def _digest(top):
    out = {}
    for base, _dirs, files in os.walk(top):
        if "__pycache__" in base:
            continue
        for f in files:
            p = os.path.join(base, f)
            out[os.path.relpath(p, top)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_new_cell_and_metric_are_new_files_only(tmp_path, manifest):
    """A dummy configuration + traffic mix + limits + per-layer metric, added
    as files and ``BENCHMARK.json`` entries in a copy, make a runnable
    rehearsal cell that reports the new metric; no file that was there
    changes."""
    top = tmp_path / "checkout"
    shutil.copytree(BENCH_DIR, top / "benchmark", ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = _digest(top / "benchmark")

    (top / "benchmark/configs/dummy.v1.json").write_text(json.dumps({
        "name": "dummy.v1", "rows": 4000, "held_out_rows": 0, "features": 9,
        "params": {"objective": "binary:logistic", "max_depth": 3,
                   "eta": 0.3, "max_bin": 64, "tree_method": "hist"},
        "reduced": {}, "data": {"generator": "higgs_like"},
        "program_env": {"XTPU_SKETCH_SAMPLE_ROWS": "123"}}))
    (top / "benchmark/traffic/two-rounds.json").write_text(json.dumps({
        "driver": "train_loop", "rounds_per_call": 2, "evals": None,
        "trace_calls": 1, "follow_rounds": 2,
        "round_programs": ["_fused_multi_round_fn", "_fused_round_fn"]}))
    (top / "benchmark/limits/dummy.v1.two-rounds.json").write_text(json.dumps({
        "loss_gap": 1e-2, "grad_gap": 1e-3, "update_gap": 5e-2,
        "margin_gap": 1e-4, "rounds_gap": 0}))
    (top / "benchmark/layer_metrics/calls_per_window.py").write_text(
        'def read(facts):\n'
        '    from xgboost_tpu.data import quantile    # program_env reached\n'
        '    assert quantile.SKETCH_SAMPLE_ROWS == 123    # the program\n'
        '    return float(len(facts["call_s"]))\n')
    new = json.loads(json.dumps(manifest))
    new["configs"].append({"name": "dummy.v1", "source": "a test",
                           "file": "benchmark/configs/dummy.v1.json",
                           "reduced": [], "why": "a test"})
    new["workloads"].append({"name": "dummy.v1.two-rounds",
                             "config": "dummy.v1", "traffic": "two-rounds",
                             "chips": 1, "why": "a test"})
    new["per_layer"].append({
        "name": "calls_per_window", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "entry / round driver",
        "moves": "train_rounds_per_s", "workloads": ["dummy.v1.two-rounds"]})
    (top / "BENCHMARK.json").write_text(json.dumps(new))

    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    run = subprocess.run(
        [sys.executable, str(top / "benchmark/run.py"), "--workload",
         "dummy.v1.two-rounds", "--seed", str(2 ** 31 + 12345), "--seconds",
         "1", "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=600)
    assert run.returncode == 0, run.stderr[-3000:]
    line = run.stdout.strip().splitlines()[-1]
    assert check_line.problems(line, new, "dummy.v1.two-rounds", True) == []
    result = json.loads(line)
    assert result["device"]["platform"] == "cpu"      # never a chip line
    assert result["correct"] is True, result["compared"]
    assert result["metrics"]["calls_per_window"]["value"] >= 1
    assert "eval_program_share_pct" not in result["metrics"]
    after = _digest(top / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before
