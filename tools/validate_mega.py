"""Promotion gate for hist_method='mega' vs the scan formulation.

Round 14 mirrored the round-12 promotion protocol (tools/validate_scan.py)
before 'auto' was routed into the single compiled megakernel program
(since PR 28 'auto' stays on fused and mega is explicit; the gate
stays): the SAME 3-task x 3-seed grid — widened by
the tier axis (depthwise / lossguide / paged) and the max_bin axis
(256 / 128), plus mesh row- and column-split cells — trains both
schedules and checks quality. The megakernel reorders NOTHING: it runs
the very same per-level stage ops with traced (lo, n_level) carries and
sentinel-padded writes (tree/grow.py _mega_body docstring pins why every
padded lane is write-dropped), and the lossguide greedy loop replays the
host heapq order in-trace (tree/lossguide.py _mega_greedy_loop), so as
in rounds 6/12 the bar is strict EQUALITY — per-round eval metrics must
match bit-for-bit AND ``save_raw`` must be byte-identical after
normalising the stored hist_method param string. Any nonzero gap below
is a correctness bug, not a quality trade.

Run from the repo root: ``python tools/validate_mega.py``.
Shrink for a smoke run: ``--scale 0.25`` (fraction of rows; also accepts
VALIDATE_MEGA_SCALE) and ``--seeds 1`` (bit-parity is structural, one
seed per cell already falsifies it).

The mesh cells force 8 virtual CPU devices when the process has fewer
(same trick as tests/conftest.py), exercising the in-loop psum +
check-waiver path of both growers' shard_map twins.
"""

import argparse
import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import numpy as np

_here = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_here))  # repo root (xgboost_tpu)
sys.path.insert(0, _here)                   # tools/ (validate_coarse)

from validate_coarse import SHAPES  # noqa: E402

SEEDS = (0, 1, 2)

TIERS = [
    ("depthwise", {}),
    ("lossguide", {"grow_policy": "lossguide", "max_leaves": 48}),
]


def _norm_raw(raw: bytes) -> bytes:
    """save_raw stores the hist_method param string; the tree bytes are
    the parity surface, so normalise the label before comparing."""
    return bytes(raw).replace(b"i\x04mega", b"i\x04scan")


def run_cell(maker, params, rounds, metric, seed, hist_method, scale,
             paged=False, mesh=None):
    import xgboost_tpu as xgb

    (Xtr, ytr, qtr), (Xev, yev, qev) = maker(seed)
    if scale < 1.0:
        ktr, kev = int(len(ytr) * scale), int(len(yev) * scale)
        Xtr, ytr = Xtr[:ktr], ytr[:ktr]
        Xev, yev = Xev[:kev], yev[:kev]
        qtr = None if qtr is None else qtr[:ktr]
        qev = None if qev is None else qev[:kev]
    p = {**params, "seed": seed, "hist_method": hist_method}
    if mesh is not None:
        p["mesh"] = xgb.make_data_mesh()
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
                bst = xgb.train(p, dtr, rounds, evals=[(dev, "eval")],
                                evals_result=res, verbose_eval=False)
            finally:
                for k, v in old.items():
                    os.environ.pop(k, None) if v is None \
                        else os.environ.__setitem__(k, v)
    else:
        dtr = xgb.DMatrix(Xtr, label=ytr, qid=qtr)
        dev = xgb.DMatrix(Xev, label=yev, qid=qev)
        bst = xgb.train(p, dtr, rounds, evals=[(dev, "eval")],
                        evals_result=res, verbose_eval=False)
    return ([float(v) for v in res["eval"][metric]],
            _norm_raw(bst.save_raw()))


def cells(scale, smoke=False):
    """Yield (label, maker, params, rounds, metric, paged, mesh) cells.

    ``smoke`` prunes to one representative cell per lowering tier
    (binary shape only, one max_bin, one mesh cell per grower) — the
    ci_checks.sh budget; the full grid is the promotion run."""
    shapes = SHAPES[:1] if smoke else SHAPES
    for name, maker, params, rounds, metric, _ in shapes:
        rounds = max(2, int(rounds * (scale if scale < 1 else 1)))
        for tier, extra in TIERS:
            bins = (params["max_bin"],) if smoke \
                else (params["max_bin"], 128)
            for max_bin in bins:
                p = {**params, **extra, "max_bin": max_bin}
                yield (f"{name}/{tier}/b{max_bin}", maker, p, rounds,
                       metric, False, None)
    name, maker, params, rounds, metric, _ = SHAPES[0]
    rounds = max(2, int(rounds * (scale if scale < 1 else 1)))
    # one paged cell (mega lowers to the page-major schedule there) and
    # the mesh cells: both split modes x both growers, binary shape
    # (smoke keeps one cell per grower, opposite split modes)
    yield (f"{name}/paged/b{params['max_bin']}", maker, params, rounds,
           metric, True, None)
    for split in ("row", "col"):
        mp = {**params, "data_split_mode": split}
        if not smoke or split == "row":
            yield (f"{name}/mesh-{split}/depthwise", maker, mp, rounds,
                   metric, False, split)
        if not smoke or split == "col":
            yield (f"{name}/mesh-{split}/lossguide",
                   maker,
                   {**mp, "grow_policy": "lossguide", "max_leaves": 24},
                   rounds, metric, False, split)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float,
                    default=float(os.environ.get("VALIDATE_MEGA_SCALE",
                                                 "1.0")),
                    help="fraction of rows/rounds (smoke runs: 0.25)")
    ap.add_argument("--seeds", type=int, default=len(SEEDS),
                    help="use the first N seeds of the grid (smoke: 1)")
    ap.add_argument("--smoke", action="store_true",
                    help="one cell per lowering tier (ci_checks budget)")
    args = ap.parse_args(argv)

    seeds = SEEDS[:max(1, args.seeds)]
    rows = []
    exact_parity = True
    for label, maker, params, rounds, metric, paged, mesh in \
            cells(args.scale, smoke=args.smoke):
        for seed in seeds:
            scan, raw_s = run_cell(maker, params, rounds, metric, seed,
                                   "scan", args.scale, paged, mesh)
            mega, raw_m = run_cell(maker, params, rounds, metric, seed,
                                   "mega", args.scale, paged, mesh)
            gaps = [abs(m - s) for m, s in zip(mega, scan)]
            worst = max(gaps)
            raw_eq = raw_s == raw_m
            exact_parity &= worst == 0.0 and raw_eq
            rows.append({"cell": label, "seed": seed, "metric": metric,
                         "rounds": rounds,
                         "scan_final": round(scan[-1], 6),
                         "mega_final": round(mega[-1], 6),
                         "worst_round_gap": worst,
                         "raw_identical": raw_eq})
            r = rows[-1]
            print(f"{label} seed={seed} {metric}: scan={r['scan_final']}"
                  f" mega={r['mega_final']} worst_gap={worst:g}"
                  f" raw={'==' if raw_eq else 'DIFF'}", flush=True)

    print("\n| cell | metric | seed | scan (final) | mega (final) | "
          "worst per-round gap | save_raw |")
    print("|---|---|---|---|---|---|---|")
    for r in rows:
        print(f"| {r['cell']} | {r['metric']} | {r['seed']} | "
              f"{r['scan_final']:.6f} | {r['mega_final']:.6f} | "
              f"{r['worst_round_gap']:g} | "
              f"{'identical' if r['raw_identical'] else 'DIFFERS'} |")
    verdict = "PASS — bit-identical" \
        if exact_parity else "FAIL — mega diverges from scan (bug)"
    print(f"\n{verdict}")
    print(json.dumps({"cells": rows, "exact_parity": exact_parity}))
    if not exact_parity:
        sys.exit(1)


if __name__ == "__main__":
    main()
