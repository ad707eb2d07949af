"""Reads ``BENCHMARK.json`` and finds a cell's files by the names in it. A
later PR adds a cell by adding a configuration file, a traffic file, a limits
file and entries in ``BENCHMARK.json``, and a per-layer metric by adding one
file under ``layer_metrics/``; nothing that is there needs an edit."""

from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def cell(manifest: dict, name: str, root: str = ROOT) -> dict:
    """The cell's entry with its configuration, traffic mix and limits read
    in. The cell's name is not parsed: ``config`` and ``traffic`` name the
    files."""
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if len(entries) != 1:
        raise SystemExit(f"BENCHMARK.json has no workload {name!r} (it has "
                         f"{[w['name'] for w in manifest['workloads']]})")
    entry = entries[0]
    conf = [c for c in manifest["configs"] if c["name"] == entry["config"]]
    if len(conf) != 1:
        raise SystemExit(f"workload {name!r} names configuration "
                         f"{entry['config']!r}, which BENCHMARK.json lacks")
    bench = os.path.join(root, "benchmark")
    return {
        "entry": entry,
        "config": _read_json(os.path.join(root, conf[0]["file"])),
        "traffic": _read_json(os.path.join(
            bench, "traffic", entry["traffic"] + ".json")),
        "limits": _read_json(os.path.join(bench, "limits", name + ".json")),
    }


def metrics_of(manifest: dict, kind: str, name: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics that cell ``name`` reports:
    those that list it under ``workloads``, and those that list none (for a
    per-layer metric: when the cell reports the metric it moves)."""
    cell_e2e = {m["name"] for m in manifest["end_to_end"]
                if name in m.get("workloads", [name])}
    out = []
    for m in manifest[kind]:
        if "workloads" in m:
            if name in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in cell_e2e:
            out.append(m)
    return out


def layer_reader(name: str, bench_dir: str = BENCH_DIR):
    """The module ``layer_metrics/<name>.py``: ``read(facts) -> float |
    None``. Unit, layer and the rest are ``BENCHMARK.json``'s alone."""
    path = os.path.join(bench_dir, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
