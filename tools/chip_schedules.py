"""Train a few rounds under each pinned histogram schedule on the chip.

The quickest check that every value of ``hist_method`` the growers accept
still compiles and runs on the attached TPU (``chip_smoke.py`` covers only
what ``auto`` resolves to: ``fused`` at its shape). Prints one line per
schedule with a cold (compile included) and a warm wall time for the same
rounds. These are smoke timings, not benchmark numbers: no repeats, no
spread.

    python tools/chip_schedules.py                       # 1M x 28, all four
    python tools/chip_schedules.py --rows 10500000 --depth 8 \
        --rounds 4                                       # the cells' shape
    python tools/chip_schedules.py --grow-policy lossguide --max-leaves 64 \
        --rounds 4 --methods fused,coarse,auto
    python tools/chip_schedules.py --rows 10500000 --depth 8 --rounds 4 \
        --methods auto --eval-rows 500000     # an eval set every round

Chain several in one chip-tool command so they share the compile cache.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

METHODS = ("auto", "fused", "coarse", "pallas")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--methods", default=",".join(METHODS))
    ap.add_argument("--depth", type=int, default=6)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--grow-policy", default="depthwise",
                    choices=("depthwise", "lossguide"))
    ap.add_argument("--max-leaves", type=int, default=0)
    ap.add_argument("--eval-rows", type=int, default=0,
                    help="evaluate this many held-out rows every round "
                         "(the per-round driver and the eval walk)")
    args = ap.parse_args()

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_schedules: no TPU: jax.default_backend() is "
              f"{jax.default_backend()!r}", file=sys.stderr)
        return 2
    import numpy as np

    import xgboost_tpu as xgb
    from chip_smoke import MAX_BIN, SEED, make_data, train_params
    from xgboost_tpu.metric.auc import binary_roc_auc
    from xgboost_tpu.obs.metrics import (eval_walk_counts, get_registry,
                                         grow_epilogue_counts,
                                         grow_schedule_counts,
                                         hist_dot_counts, hist_dot_rows)
    from xgboost_tpu.tree.grow import resolve_schedule

    dev = jax.devices()[0]
    X, y = make_data(args.rows, SEED)
    Xh, yh = make_data(100_000, SEED + 100)
    dtrain, dhold = xgb.DMatrix(X, label=y), xgb.DMatrix(Xh)
    binned = dtrain.binned(MAX_BIN)
    evals = []
    if args.eval_rows:
        Xe, ye = make_data(args.eval_rows, SEED + 200)
        evals = [(xgb.DMatrix(Xe, label=ye), "eval")]
    rows = []
    lossguide = args.grow_policy == "lossguide"
    for method in args.methods.split(","):
        params = {**train_params(args.depth), "hist_method": method,
                  "grow_policy": args.grow_policy,
                  "max_leaves": args.max_leaves}
        # the lossguide grower resolves "auto" itself (tree/lossguide.py)
        runs_as = method if lossguide else resolve_schedule(
            method, args.rows, binned.max_nbins, binned.has_missing,
            numeric=True).name
        walls, dots = [], hist_dot_counts()
        for _ in ("cold", "warm"):
            t0 = time.perf_counter()
            bst = xgb.train(params, dtrain, args.rounds, evals=evals,
                            verbose_eval=False)
            pred = bst.predict(dhold)     # host copy: the rounds finished
            walls.append(time.perf_counter() - t0)
        auc = binary_roc_auc(yh.astype(np.float64), pred.astype(np.float64),
                             np.ones(len(yh)))
        if not (np.isfinite(pred).all() and auc > 0.7):
            raise AssertionError(
                f"{method}: AUC {auc} after {args.rounds} rounds")
        rows.append({"hist_method": method, "runs_as": runs_as,
                     "grow_policy": args.grow_policy, "rows": args.rows,
                     "depth": args.depth, "rounds": args.rounds,
                     "auc": round(auc, 4),
                     "cold_s": round(walls[0], 2),
                     "warm_s": round(walls[1], 2),
                     # the histogram kernels this method traced, by dot form
                     "hist_dot_total": {
                         k: v - dots.get(k, 0)
                         for k, v in hist_dot_counts().items()}})
        print(f"[chip_schedules] {rows[-1]}", flush=True)
    print(json.dumps({
        "ok": True, "smoke_timings": rows,
        "grow_schedule_total": grow_schedule_counts(),
        "grow_epilogue_total": grow_epilogue_counts(),
        "eval_walk_total": eval_walk_counts(),
        "hist_dot_rows": hist_dot_rows(),
        "tree_flushes_total": int(get_registry().get(
            "xtpu_tree_flushes_total", ())),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
