#!/usr/bin/env python3
"""Checks a run's last stdout line against the benchmark's output contract.

    python3 benchmark/run.py ... | python3 benchmark/check_line.py --workload <cell> --trace <0|1>
    python3 benchmark/check_line.py --workload <cell> --trace 1 --file run.stdout

The last line has to be one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device``. ``metrics`` gives every metric the
cell reports in that kind of run as ``{"value", "unit"}`` with the unit
``BENCHMARK.json`` states: the end-to-end metrics always, the per-layer
metrics too in a traced run. ``device`` gives ``platform``, ``kind``,
``count``, ``memory_peak_bytes`` and, traced, ``0 < busy_s <= window_s``. No
number anywhere is non-finite. PR 22 was refused for a traced line that broke
one of these; this is the check it lacked.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import manifest as mf          # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _numbers(obj, path="$"):
    if isinstance(obj, bool):
        return
    if isinstance(obj, (int, float)):
        yield path, obj
    elif isinstance(obj, dict):
        for k, v in obj.items():
            yield from _numbers(v, f"{path}.{k}")
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            yield from _numbers(v, f"{path}[{i}]")


def problems(line: str, manifest: dict, workload: str, traced: bool) -> list:
    """Everything wrong with the line; empty when it keeps the contract."""
    try:
        obj = json.loads(line)
    except ValueError as e:
        return [f"not JSON: {e}"]
    if not isinstance(obj, dict):
        return ["not a JSON object"]
    out = []
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in obj:
            out.append(f"key {key!r} missing")
    if out:
        return out
    if not isinstance(obj["correct"], bool):
        out.append("correct is not true/false")
    for key in ("attempted", "failed"):
        if isinstance(obj[key], bool) or not isinstance(obj[key], int) \
                or obj[key] < 0:
            out.append(f"{key} is not a count")
    for path, v in _numbers(obj):
        if isinstance(v, float) and not math.isfinite(v):
            out.append(f"non-finite number at {path}")

    want = {m["name"]: m["unit"]
            for m in mf.metrics_of(manifest, "end_to_end", workload)}
    if traced:
        # a sandbox rehearsal (platform "cpu") has no device trace worth the
        # name: there a reader of one may find nothing to read
        on_chip = obj["device"].get("platform") == "tpu" \
            if isinstance(obj["device"], dict) else True
        want.update({m["name"]: m["unit"]
                     for m in mf.metrics_of(manifest, "per_layer", workload)
                     if on_chip or m["source"] != "device_trace"})
    metrics = obj["metrics"]
    if not isinstance(metrics, dict):
        return out + ["metrics is not an object"]
    for name, unit in want.items():
        if name not in metrics:
            out.append(f"metric {name!r} of {workload} missing"
                       + (" in the traced run" if traced else ""))
    known = {m["name"]: m["unit"]
             for m in manifest["end_to_end"] + manifest["per_layer"]}
    for name, m in metrics.items():
        if not NAME.match(name):
            out.append(f"metric name {name!r} has characters outside "
                       "letters, digits, _ . -")
        if name not in known:
            out.append(f"metric {name!r} is not in BENCHMARK.json")
        if not isinstance(m, dict) or set(m) != {"value", "unit"}:
            out.append(f"metric {name!r} is not {{value, unit}}")
            continue
        if isinstance(m["value"], bool) or not isinstance(
                m["value"], (int, float)):
            out.append(f"metric {name!r}: value is not a number")
        if not isinstance(m["unit"], str) or not UNIT.match(m["unit"]):
            out.append(f"metric {name!r}: unit {m['unit']!r} not 1-16 of "
                       "letters, digits, _ / % . -")
        elif name in known and m["unit"] != known[name]:
            out.append(f"metric {name!r}: unit {m['unit']!r}, BENCHMARK.json "
                       f"says {known[name]!r}")

    dev = obj["device"]
    if not isinstance(dev, dict):
        return out + ["device is not an object"]
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if key not in dev:
            out.append(f"device.{key} missing")
    if isinstance(dev.get("memory_peak_bytes"), (int, float)) \
            and dev["memory_peak_bytes"] <= 0:
        out.append("device.memory_peak_bytes is not above 0")
    if traced:
        busy, window = dev.get("busy_s"), dev.get("window_s")
        if not isinstance(busy, (int, float)) \
                or not isinstance(window, (int, float)):
            out.append("device.busy_s / device.window_s missing in the "
                       "traced run")
        elif not busy > 0:
            out.append(f"device.busy_s is {busy}, not above 0")
        elif busy > window:
            out.append(f"device.busy_s {busy} exceeds device.window_s "
                       f"{window}")
        bd = obj.get("breakdown")
        if bd is not None:
            for key in ("device_ops", "idle_gaps"):
                rows = bd.get(key) if isinstance(bd, dict) else None
                if not isinstance(rows, list) or len(rows) > 10 or any(
                        not (isinstance(r, list) and len(r) == 2
                             and isinstance(r[0], str)) for r in rows):
                    out.append(f"breakdown.{key} is not a list of at most "
                               "10 [name, seconds]")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--file", default=None, help="stdout of a run; else stdin")
    args = ap.parse_args(argv)
    if args.file:
        with open(args.file) as fh:
            text = fh.read()
    else:
        text = sys.stdin.read()
    lines = [ln for ln in text.splitlines() if ln.strip()]
    if not lines:
        print("check_line: no output at all", file=sys.stderr)
        return 1
    found = problems(lines[-1], mf.load(), args.workload, bool(args.trace))
    for p in found:
        print(f"check_line: {p}", file=sys.stderr)
    if not found:
        print(f"check_line: ok ({args.workload}, trace {args.trace})",
              file=sys.stderr)
    return 1 if found else 0


if __name__ == "__main__":
    sys.exit(main())
