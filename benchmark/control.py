#!/usr/bin/env python3
"""Reads a cell's control and planted faults at the cell's own size, with the
plain reference put in the program's place (host only: no chip is touched).

    python3 benchmark/control.py --workload <cell> --seeds 1 2 3

Prints one JSON object a seed: ``{case: {number: value}}`` and, per case,
whether the cell's limits would call it correct. The control (``control_bf16``)
and every fault have to come out not correct; ``benchmark/tests`` keeps that as
a test at a small size.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import compare, manifest as mf          # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = mf.cell(mf.load(), args.workload)
    config = cell["config"]
    driver = importlib.import_module("drivers." + cell["traffic"]["driver"])
    for seed in args.seeds:
        t = time.perf_counter()
        readings = driver.control_readings(config, cell["traffic"], seed)
        verdict = {case: compare.judge(vals, cell["limits"])[0]
                   for case, vals in readings.items()}
        print(json.dumps({"seed": seed, "rows": config["rows"],
                          "seconds": round(time.perf_counter() - t, 1),
                          "correct": verdict, "readings": readings}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
