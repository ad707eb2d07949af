#!/usr/bin/env python3
"""One chip's twin of the four-chip cell ``criteo-ctr.mesh-train``: the
rows ONE chip holds there (25M x 67 by default), through the same iterator,
trained twice on that chip in one process:

    mesh   ``params["mesh"]`` = a one-device mesh: the general round path
           with the ``shard_map`` grow program, as each of the four chips
           runs it (its collectives are over one device)
    fused  no mesh: the fused, batched round programs

    chiprun --chips 1 --timeout 1500 -- python3 tools/mesh_twin.py

Prints one JSON line a mode (rounds/s over ``--seconds`` after a warm-up
call, calls of 4 rounds) and the device's memory peak. The four-chip cell's
rate over the ``mesh`` rate here is the weak-scaling ratio; ``mesh`` against
``fused`` is what the general path costs (PERF.md section 5). Smoke timings,
not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=25_000_000)
    ap.add_argument("--depth", type=int, default=8)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=20260803)
    ap.add_argument("--modes", default="mesh,fused")
    args = ap.parse_args(argv)

    import jax

    import xgboost_tpu as xgb
    from drivers.mesh_loop import block_iter
    from xgboost_tpu.obs.metrics import (degrade_counts, grow_epilogue_counts,
                                         grow_schedule_counts, mesh_counts)

    def say(msg):
        print(f"[twin] {msg}", file=sys.stderr, flush=True)

    dev = jax.devices()[0]
    say(f"{dev.platform} {dev.device_kind} x {len(jax.devices())}")
    t = time.perf_counter()
    it = block_iter(xgb, args.seed, args.rows, 8)
    dtrain = xgb.QuantileDMatrix(it, max_bin=256)
    it.pool.shutdown()
    say(f"ingest {time.perf_counter() - t:.1f}s, of it waiting for data "
        f"{it.wait_s:.1f}s")
    base = {"objective": "binary:logistic", "max_depth": args.depth,
            "eta": 0.1, "max_bin": 256, "tree_method": "hist",
            "seed": args.seed % (2 ** 31 - 1)}
    for mode in args.modes.split(","):
        params = dict(base)
        if mode == "mesh":
            params["mesh"] = xgb.make_data_mesh(1)
        bst = None

        def call(bst):
            kw = {"xgb_model": bst} if bst is not None else {}
            bst = xgb.train(params, dtrain, num_boost_round=4,
                            verbose_eval=False, **kw)
            jax.block_until_ready(
                bst._state_of(dtrain, is_train=True)["margin"])
            return bst

        t = time.perf_counter()
        bst = call(None)
        warm_s = time.perf_counter() - t
        r0, w0, calls = bst.num_boosted_rounds(), time.perf_counter(), []
        while time.perf_counter() - w0 < args.seconds:
            c0 = time.perf_counter()
            bst = call(bst)
            calls.append(round(time.perf_counter() - c0, 3))
        window_s = time.perf_counter() - w0
        stats = dev.memory_stats() or {}
        print(json.dumps({
            "mode": mode, "rows": args.rows, "depth": args.depth,
            "warm_s": round(warm_s, 2),
            "rounds_per_s": (bst.num_boosted_rounds() - r0) / window_s,
            "calls_s": calls,
            "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)),
            "schedule": grow_schedule_counts(),
            "epilogue": grow_epilogue_counts(), "mesh": mesh_counts(),
            "degrades": degrade_counts(),
            "fused_blocked": bool(bst._fused_blocked)}), flush=True)
        del bst
    return 0


if __name__ == "__main__":
    sys.exit(main())
