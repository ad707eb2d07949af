"""Promotion gate for hist_method='scan' vs the fused one-dispatch path.

Round 12 mirrored the round-6 promotion protocol (tools/validate_fused.py)
before 'auto' was routed to the segmented-scan build (since PR 28 'auto'
stays on fused and scan is explicit; the gate stays): the SAME 3-task x
3-seed grid — widened by a tier axis (depthwise / lossguide / paged) and
a max_bin axis (256 / 128) — trains both schedules and checks quality.
The scan scheme REORDERS the rows feeding the very same per-(node, bin)
sums (ops/histogram.py build_hist_scan: stable counting sort + segment
sums; ops/partition.py counting_sort_by_node pins why stability makes
the reorder bitwise-free), so as in round 6 the bar is strict EQUALITY:
per-round eval metrics must be bit-identical. Any nonzero gap printed
below is a correctness bug, not a quality trade.

Run from the repo root: ``python tools/validate_scan.py``.
Shrink for a smoke run: ``--scale 0.25`` (fraction of rows; also accepts
VALIDATE_SCAN_SCALE for parity with the older gates' env knob) and
``--seeds 1`` (first N of the seed axis — bit-parity is a structural
property, so one seed per cell already falsifies it; the full 3-seed
sweep is the pre-promotion record).

The bf16 split accumulators (XTPU_SCAN_ACC=bf16) are deliberately NOT on
this grid: they are opt-in and not bit-compatible by construction
(docs/performance.md round 12); tests/test_scan_hist.py bounds their
error instead.
"""

import argparse
import json
import os
import sys
import tempfile

import numpy as np

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_here))  # repo root (xgboost_tpu)
sys.path.insert(0, _here)                   # tools/ (validate_coarse)

from validate_coarse import SHAPES  # noqa: E402

SEEDS = (0, 1, 2)

# (tier, extra params) — paged runs one shape only (binary) to keep the
# gate's wall clock sane; the paged scan path maps onto the page-major
# two-level schedule (tree/paged.py), so one cell pins the routing
TIERS = [
    ("depthwise", {}),
    ("lossguide", {"grow_policy": "lossguide", "max_leaves": 48}),
]


def run_cell(maker, params, rounds, metric, seed, hist_method, scale,
             paged=False):
    import xgboost_tpu as xgb

    (Xtr, ytr, qtr), (Xev, yev, qev) = maker(seed)
    if scale < 1.0:
        ktr, kev = int(len(ytr) * scale), int(len(yev) * scale)
        Xtr, ytr = Xtr[:ktr], ytr[:ktr]
        Xev, yev = Xev[:kev], yev[:kev]
        qtr = None if qtr is None else qtr[:ktr]
        qev = None if qev is None else qev[:kev]
    p = {**params, "seed": seed, "hist_method": hist_method}
    res = {}
    if paged:
        from xgboost_tpu.data.dmatrix import DataIter

        class It(DataIter):
            def __init__(self):
                super().__init__()
                self.parts = np.array_split(np.arange(len(ytr)), 4)
                self.i = 0

            def next(self, input_data):
                if self.i >= len(self.parts):
                    return 0
                idx = self.parts[self.i]
                input_data(data=Xtr[idx], label=ytr[idx])
                self.i += 1
                return 1

            def reset(self):
                self.i = 0

        with tempfile.TemporaryDirectory() as tmp:
            old = {k: os.environ.get(k)
                   for k in ("XTPU_PAGE_ROWS", "XTPU_PAGED_COLLAPSE")}
            os.environ["XTPU_PAGE_ROWS"] = "1024"
            os.environ["XTPU_PAGED_COLLAPSE"] = "0"  # stay on page kernels
            try:
                it = It()
                it.cache_prefix = os.path.join(tmp, "pc")
                dtr = xgb.QuantileDMatrix(it, max_bin=p["max_bin"])
                dev = xgb.DMatrix(Xev, label=yev, qid=qev)
                xgb.train(p, dtr, rounds, evals=[(dev, "eval")],
                          evals_result=res, verbose_eval=False)
            finally:
                for k, v in old.items():
                    os.environ.pop(k, None) if v is None \
                        else os.environ.__setitem__(k, v)
    else:
        dtr = xgb.DMatrix(Xtr, label=ytr, qid=qtr)
        dev = xgb.DMatrix(Xev, label=yev, qid=qev)
        xgb.train(p, dtr, rounds, evals=[(dev, "eval")], evals_result=res,
                  verbose_eval=False)
    return [float(v) for v in res["eval"][metric]]


def cells(scale):
    """Yield (label, maker, params, rounds, metric, paged) grid cells."""
    for name, maker, params, rounds, metric, _ in SHAPES:
        rounds = max(2, int(rounds * (scale if scale < 1 else 1)))
        for tier, extra in TIERS:
            for max_bin in (params["max_bin"], 128):
                p = {**params, **extra, "max_bin": max_bin}
                yield (f"{name}/{tier}/b{max_bin}", maker, p, rounds,
                       metric, False)
    # one paged cell: binary shape, depthwise, default bins
    name, maker, params, rounds, metric, _ = SHAPES[0]
    rounds = max(2, int(rounds * (scale if scale < 1 else 1)))
    yield (f"{name}/paged/b{params['max_bin']}", maker, params, rounds,
           metric, True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float,
                    default=float(os.environ.get("VALIDATE_SCAN_SCALE",
                                                 "1.0")),
                    help="fraction of rows/rounds (smoke runs: 0.25)")
    ap.add_argument("--seeds", type=int, default=len(SEEDS),
                    help="use the first N seeds of the grid (smoke: 1)")
    args = ap.parse_args(argv)

    seeds = SEEDS[:max(1, args.seeds)]
    rows = []
    exact_parity = True
    for label, maker, params, rounds, metric, paged in cells(args.scale):
        for seed in seeds:
            fused = run_cell(maker, params, rounds, metric, seed, "fused",
                             args.scale, paged)
            scan = run_cell(maker, params, rounds, metric, seed, "scan",
                            args.scale, paged)
            gaps = [abs(s - f) for s, f in zip(scan, fused)]
            worst = max(gaps)
            exact_parity &= worst == 0.0
            rows.append({"cell": label, "seed": seed, "metric": metric,
                         "rounds": rounds,
                         "fused_final": round(fused[-1], 6),
                         "scan_final": round(scan[-1], 6),
                         "worst_round_gap": worst})
            r = rows[-1]
            print(f"{label} seed={seed} {metric}: fused={r['fused_final']}"
                  f" scan={r['scan_final']} worst_gap={worst:g}",
                  flush=True)

    print("\n| cell | metric | seed | fused (final) | scan (final) | "
          "worst per-round gap |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['cell']} | {r['metric']} | {r['seed']} | "
              f"{r['fused_final']:.6f} | {r['scan_final']:.6f} | "
              f"{r['worst_round_gap']:g} |")
    verdict = "PASS — bit-identical" \
        if exact_parity else "FAIL — scan diverges from fused (bug)"
    print(f"\n{verdict}")
    print(json.dumps({"cells": rows, "exact_parity": exact_parity}))
    if not exact_parity:
        sys.exit(1)


if __name__ == "__main__":
    main()
