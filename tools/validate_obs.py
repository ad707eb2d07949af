"""Observability promotion gate: tracing must be invisible to training.

xtpuobs instruments the hot paths in-line (host spans in the round,
paged and lossguide drivers, ``obs.trace.stage`` scopes inside the fused
dispatch), so the load-bearing contract is that NONE of it perturbs
numerics: training
with ``XTPU_TRACE=1`` must produce **byte-identical** ``save_raw``
artifacts to an untraced run, in every tier whose driver the tracer
touches. This gate trains each cell twice — tracing off, then on — and
diffs the bytes:

    resident depthwise | lossguide | paged (streamed) | mesh row-split

Each traced cell must also actually RECORD the spans it claims to (an
empty ring would make byte-equality vacuous). Two extra cells re-run
resident and paged with the FULL xtpuflight stack armed (memory
monitor, rank identity, black box) and additionally require a round of
memory samples plus a CRC-valid postmortem bundle. Four more cells
re-run resident (with an eval set), pinned fused, paged and mesh with
xtpuinsight armed (``XTPU_INSIGHT=1`` + in-carry eval): per-round
telemetry and the eval fold must leave the model bytes untouched while
actually recording a :class:`~xgboost_tpu.obs.insight.TrainingLog`.

The second half lints the one-registry Prometheus exposition
(``obs.metrics.get_registry().render_prometheus()``) after exercising
the serve and collective collectors: every sample line parses, belongs
to a family with ``# HELP``/``# TYPE`` headers, counters end in
``_total``, and histogram ``_bucket`` series are monotone cumulative,
end at ``le="+Inf"``, and agree with ``_count``.

Run from the repo root: ``python tools/validate_obs.py``; shrink with
``--rows``/``--rounds``. Wired into ``tools/ci_checks.sh``.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import tempfile
from typing import Callable, Dict, List, Tuple

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the mesh cell needs the virtual 8-device mesh (same trick as conftest)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import numpy as np  # noqa: E402

import xgboost_tpu as xgb  # noqa: E402
from xgboost_tpu.obs import trace as tr  # noqa: E402
from xgboost_tpu.obs.metrics import get_registry  # noqa: E402

BASE = {"objective": "binary:logistic", "eta": 0.3, "max_bin": 64,
        "seed": 7}


def _data(rows: int, features: int = 10, seed: int = 0):
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, features).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] - 0.25 * X[:, 2] > 0).astype(np.float32)
    return X, y


def _cell_resident(X, y, rounds):
    p = {**BASE, "max_depth": 4}
    return xgb.train(p, xgb.DMatrix(X, label=y), rounds,
                     verbose_eval=False).save_raw()


def _cell_lossguide(X, y, rounds):
    p = {**BASE, "max_depth": 6, "grow_policy": "lossguide",
         "max_leaves": 16}
    return xgb.train(p, xgb.DMatrix(X, label=y), rounds,
                     verbose_eval=False).save_raw()


def _train_paged(X, y, rounds):
    """Genuinely streamed paged training: iterator + cache prefix, page
    cache off, collapse off — the driver that carries the per-level
    ``paged/*`` stage spans is exactly the one under test here."""
    from xgboost_tpu.data.dmatrix import DataIter

    n_pages = 3
    parts = np.array_split(np.arange(len(y)), n_pages)

    class _It(DataIter):
        def __init__(self):
            super().__init__()
            self.i = 0

        def next(self, input_data):
            if self.i >= n_pages:
                return 0
            idx = parts[self.i]
            input_data(data=X[idx], label=y[idx])
            self.i += 1
            return 1

        def reset(self):
            self.i = 0

    keep = {k: os.environ.get(k) for k in
            ("XTPU_PAGE_ROWS", "XTPU_PAGED_COLLAPSE",
             "XTPU_PAGE_CACHE_BYTES")}
    os.environ["XTPU_PAGE_ROWS"] = str(max(len(y) // n_pages, 1))
    os.environ["XTPU_PAGED_COLLAPSE"] = "0"
    os.environ["XTPU_PAGE_CACHE_BYTES"] = "0"
    tmp = tempfile.TemporaryDirectory(prefix="xtpu_validate_obs_")
    try:
        it = _It()
        it.cache_prefix = os.path.join(tmp.name, "pc")
        dm = xgb.QuantileDMatrix(it, max_bin=BASE["max_bin"])
        p = {**BASE, "max_depth": 4}
        return xgb.train(p, dm, rounds, verbose_eval=False)
    finally:
        for k, v in keep.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        tmp.cleanup()


def _cell_paged(X, y, rounds):
    return _train_paged(X, y, rounds).save_raw()


def _train_mesh(X, y, rounds):
    p = {**BASE, "max_depth": 4, "mesh": xgb.make_data_mesh()}
    return xgb.train(p, xgb.DMatrix(X, label=y), rounds,
                     verbose_eval=False)


def _cell_mesh(X, y, rounds):
    return _train_mesh(X, y, rounds).save_raw()


# (name, trainer, span prefixes at least one of which must be recorded)
CELLS: List[Tuple[str, Callable, Tuple[str, ...]]] = [
    ("resident", _cell_resident, ("round/", "Booster.")),
    ("lossguide", _cell_lossguide, ("lossguide/",)),
    ("paged", _cell_paged, ("paged/",)),
    ("mesh", _cell_mesh, ("round/", "Booster.")),
]


def run_cells(rows: int, rounds: int):
    X, y = _data(rows)
    results = []
    for name, fn, prefixes in CELLS:
        tr.disable()
        raw_plain = fn(X, y, rounds)
        t = tr.enable()
        try:
            raw_traced = fn(X, y, rounds)
            names = {s.name for s in t.spans()}
        finally:
            tr.disable()
        seen = any(n.startswith(p) for n in names for p in prefixes)
        results.append({
            "cell": name,
            "identical": raw_traced == raw_plain,
            "spans": len(names),
            "covered": seen,
            "ok": raw_traced == raw_plain and seen,
        })
    return results


def run_flight_cells(rows: int, rounds: int):
    """Byte-equality with the FULL flight recorder armed, not just the
    bare tracer: memory monitor sampling every round and page level,
    rank identity on the ring, black box armed. xtpuflight must be as
    invisible to numerics as xtpuobs — and still leave a CRC-valid
    postmortem bundle on demand."""
    from xgboost_tpu.obs import flight, memory

    X, y = _data(rows)
    results = []
    for name, fn, prefixes in CELLS:
        if name not in ("resident", "paged"):
            continue  # the cells with memory-accounting call sites
        tr.disable()
        raw_plain = fn(X, y, rounds)
        tmp = tempfile.TemporaryDirectory(prefix="xtpu_flight_gate_")
        t = tr.enable()
        tr.set_identity(0, 1)
        mon = memory.enable()
        box = flight.arm(directory=tmp.name, rank=0, world=1,
                         install_hooks=False)
        try:
            raw_flight = fn(X, y, rounds)
            names = {s.name for s in t.spans()}
            sampled = mon.snapshot()["samples"] > 0
            bundle = box.write("validate-obs-flight")
            bundle_ok = False
            if bundle is not None:
                try:
                    flight.verify_bundle(bundle)
                    bundle_ok = True
                except flight.BundleCorrupt:
                    pass
        finally:
            flight.disarm()
            memory.disable()
            tr.disable()
            tmp.cleanup()
        seen = any(n.startswith(p) for n in names for p in prefixes)
        results.append({
            "cell": f"{name}+flight",
            "identical": raw_flight == raw_plain,
            "spans": len(names),
            "covered": seen and sampled and bundle_ok,
            "ok": (raw_flight == raw_plain and seen and sampled
                   and bundle_ok),
        })
    return results


def run_insight_cells(rows: int, rounds: int):
    """Byte-equality with xtpuinsight armed: per-round telemetry (and,
    on the resident tier, the in-carry eval fold) must not move a single
    model byte — resident, pinned fused, paged streamed and virtual-mesh
    tiers. Coverage makes the equality non-vacuous: every armed run must
    actually record per-round telemetry, and the resident cell must land
    in-carry eval history for its eval set."""
    from xgboost_tpu.obs import insight

    X, y = _data(rows)
    Xv, yv = _data(max(rows // 3, 120), seed=1)

    def _resident_eval(armed_unused=None):
        p = {**BASE, "max_depth": 4, "eval_metric": "logloss"}
        return xgb.train(p, xgb.DMatrix(X, label=y), rounds,
                         evals=[(xgb.DMatrix(Xv, label=yv), "val")],
                         verbose_eval=False)

    cells = [
        ("resident+insight", _resident_eval),
        ("fused+insight", lambda: xgb.train(
            {**BASE, "max_depth": 4, "hist_method": "fused"},
            xgb.DMatrix(X, label=y), rounds, verbose_eval=False)),
        ("paged+insight", lambda: _train_paged(X, y, rounds)),
        ("mesh+insight", lambda: _train_mesh(X, y, rounds)),
    ]
    results = []
    for name, fn in cells:
        insight.disable()
        raw_plain = bytes(fn().save_raw())
        insight.enable(eval=True)
        try:
            bst = fn()
            raw_armed = bytes(bst.save_raw())
        finally:
            insight.disable()
        log = bst.training_log
        recorded = bool(log is not None and log.records)
        covered = recorded
        if name == "resident+insight":
            covered = recorded and bool(log and log.get("val"))
        results.append({
            "cell": name,
            "identical": raw_armed == raw_plain,
            "spans": len(log.records) if log is not None else 0,
            "covered": covered,
            "ok": raw_armed == raw_plain and covered,
        })
    return results


# ------------------------------------------------------- exposition lint

_NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*")
_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'               # metric name
    r'(\{(?:[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*",?)*\})?'  # labels
    r' (-?(?:\d+\.?\d*(?:e[+-]?\d+)?|\+Inf|-Inf|NaN))$')


def lint_exposition(text: str) -> List[str]:
    """Prometheus text-format 0.0.4 checks; returns problem strings."""
    problems: List[str] = []
    types: Dict[str, str] = {}
    helps: Dict[str, str] = {}
    # per (family, non-le labels): [(le, cum)], plus _sum/_count values
    buckets: Dict[Tuple[str, str], List[Tuple[float, float]]] = {}
    counts: Dict[Tuple[str, str], float] = {}
    sums: Dict[Tuple[str, str], float] = {}

    def base_of(name: str) -> str:
        for suf in ("_bucket", "_sum", "_count"):
            if name.endswith(suf) and name[:-len(suf)] in types:
                return name[:-len(suf)]
        return name

    for ln in text.splitlines():
        if not ln.strip():
            continue
        if ln.startswith("# HELP "):
            parts = ln.split(" ", 3)
            if len(parts) < 4 or not _NAME_RE.fullmatch(parts[2]):
                problems.append(f"malformed HELP line: {ln!r}")
            else:
                helps[parts[2]] = parts[3]
            continue
        if ln.startswith("# TYPE "):
            parts = ln.split(" ")
            if len(parts) != 4 or parts[3] not in ("counter", "gauge",
                                                   "histogram"):
                problems.append(f"malformed TYPE line: {ln!r}")
            else:
                types[parts[2]] = parts[3]
            continue
        if ln.startswith("#"):
            continue
        m = _SAMPLE_RE.match(ln)
        if not m:
            problems.append(f"unparseable sample line: {ln!r}")
            continue
        name, labels = m.group(1), m.group(2) or ""
        fam = base_of(name)
        if fam not in types:
            problems.append(f"sample {name!r} has no # TYPE header")
            continue
        if fam not in helps:
            problems.append(f"family {fam!r} has no # HELP header")
        kind = types[fam]
        if kind == "counter" and not name.endswith("_total"):
            problems.append(f"counter {name!r} not suffixed _total")
        if kind == "histogram":
            val = float(m.group(3).replace("+Inf", "inf"))
            le = None
            rest = []
            for lm in re.finditer(r'([a-zA-Z_][a-zA-Z0-9_]*)='
                                  r'"((?:[^"\\]|\\.)*)"', labels):
                if lm.group(1) == "le":
                    le = lm.group(2)
                else:
                    rest.append(f'{lm.group(1)}={lm.group(2)}')
            key = (fam, ",".join(rest))
            if name.endswith("_bucket"):
                if le is None:
                    problems.append(f"bucket without le: {ln!r}")
                else:
                    buckets.setdefault(key, []).append(
                        (float(le.replace("+Inf", "inf")), val))
            elif name.endswith("_count"):
                counts[key] = val
            elif name.endswith("_sum"):
                sums[key] = val
            else:
                problems.append(f"bare sample on histogram family: {ln!r}")

    for key, bs in buckets.items():
        fam, labels = key
        where = f"{fam}{{{labels}}}"
        les = [b[0] for b in bs]
        cums = [b[1] for b in bs]
        if les != sorted(les):
            problems.append(f"{where}: le edges not ascending")
        if cums != sorted(cums):
            problems.append(f"{where}: cumulative buckets not monotone")
        if not les or les[-1] != float("inf"):
            problems.append(f"{where}: missing le=\"+Inf\" bucket")
        if key not in counts or key not in sums:
            problems.append(f"{where}: missing _count or _sum")
        elif les and les[-1] == float("inf") and cums[-1] != counts[key]:
            problems.append(
                f"{where}: +Inf bucket {cums[-1]} != _count {counts[key]}")
    return problems


def run_exposition_lint() -> List[str]:
    """Exercise the serve + collective collectors, then lint the full
    registry exposition (pre-declared core counters + direct counters
    + histogram family all flow through the same renderer)."""
    from xgboost_tpu.parallel.collective import NoOpCommunicator
    from xgboost_tpu.parallel.resilience import ResilientCommunicator
    from xgboost_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics()           # registered collector (kept alive below)
    m.inc("requests", 5)
    m.inc("rows", 40)
    m.observe("e2e", 0.012)
    m.observe("compute", 0.004)
    m.hit_bucket(16, padded_rows=3)
    # fleet mode: replica-labeled serve families (every sample of a
    # labeled ServeMetrics carries its replica tag, including stage
    # histograms and bucket hits) must lint and stay distinguishable
    mr = ServeMetrics(labels=(("replica", "lint0"),))
    mr.inc("requests", 2)
    mr.observe("shap", 0.003)
    mr.hit_bucket(8, padded_rows=1)
    rc = ResilientCommunicator(NoOpCommunicator())
    rc.stats["retry"] = 2
    # fleet router collector: aggregate + per-replica families
    from xgboost_tpu.serve.fleet import FleetConfig, FleetRouter

    fleet = FleetRouter(config=FleetConfig(replicas=2, min_replicas=1,
                                           max_replicas=2, replication=1))
    reg = get_registry()
    reg.inc("xtpu_validate_obs_runs_total", help="gate executions")
    text = reg.render_prometheus()
    problems = lint_exposition(text)
    for needle in ("xtpu_serve_requests_total 5",
                   'xtpu_collective_events_total{kind="retry"} 2',
                   "xtpu_serve_stage_latency_seconds_bucket",
                   # fleet families + replica labels
                   'xtpu_serve_requests_total{replica="lint0"} 2',
                   'stage="shap"',
                   'xtpu_serve_bucket_hits_total{replica="lint0",'
                   'bucket="8"} 1',
                   "xtpu_fleet_replicas 2",
                   'xtpu_fleet_replica_up{replica="r0"} 1',
                   'xtpu_fleet_replica_up{replica="r1"} 1',
                   "xtpu_fleet_routed_total",
                   # left behind by run_insight_cells: armed runs stream
                   # telemetry + eval gauges through the same registry
                   "xtpu_insight_round",
                   'xtpu_eval_score{data="val",metric="logloss"}'):
        if needle not in text:
            problems.append(f"expected exposition line missing: {needle}")
    fleet.close(drain=False)
    del m, mr, rc, fleet
    return problems


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=2400)
    ap.add_argument("--rounds", type=int, default=3)
    args = ap.parse_args()

    results = run_cells(args.rows, args.rounds)
    results += run_flight_cells(args.rows, args.rounds)
    results += run_insight_cells(args.rows, args.rounds)
    wid = max(len(r["cell"]) for r in results)
    print(f"traced-vs-untraced byte equality ({args.rows} rows, "
          f"{args.rounds} rounds):")
    for r in results:
        mark = "OK  " if r["ok"] else "FAIL"
        print(f"  {mark} {r['cell']:<{wid}}  identical={r['identical']}  "
              f"span_names={r['spans']}  covered={r['covered']}")

    problems = run_exposition_lint()
    if problems:
        print("exposition lint: FAIL")
        for p in problems:
            print(f"  - {p}")
    else:
        print("exposition lint: OK")

    failed = [r["cell"] for r in results if not r["ok"]]
    if failed or problems:
        print(f"validate_obs: FAILED ({', '.join(failed) or 'lint'})")
        return 1
    print("validate_obs: all cells byte-identical, exposition clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
