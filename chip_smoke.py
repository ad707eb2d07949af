#!/usr/bin/env python3
"""chip_smoke.py — does the default train -> predict -> serve path run on the chip?

One process, the entry points a user calls (``xgb.DMatrix``, ``xgb.train``,
``Booster.predict``, ``xgboost_tpu.serve.Server``), seeded synthetic data at
the HIGGS shape (28 features, 256 bins, depth 6). It fails unless jax's
default backend is a TPU, and it proves — not assumes — that the Pallas
path was the one dispatched. Every timing it prints is a smoke timing, not
a benchmark number.

    python3 chip_smoke.py                 # on the chip (through the chip tool)
    python3 chip_smoke.py --dry-run-cpu   # sandbox rehearsal: tiny shapes,
                                          # interpret-mode kernels, CPU backend

The last line of stdout is the verdict, one JSON object with exactly these
keys: ``{"ok": true, "device": {"platform", "kind", "count"}}``, the device
as jax reports it (a dry run adds ``"dry_run": true``, so a rehearsal can
never pass for a chip result). The line before it is the report: one JSON
object with what each phase found and its smoke timings. Any failed phase
means exit code 1 and neither line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import os
import sys
import tempfile
import time
import traceback

FEATURES = 28
MAX_BIN = 256
SEED = 2026


@dataclasses.dataclass(frozen=True)
class Sizes:
    rows: int            # training rows
    holdout: int         # held-out rows for AUC / predict / serve
    depth: int
    rounds: int          # no-evals call: batched driver, chunks 16 + 4
    eval_rounds: int     # evals call: per-round driver + eval program
    auc_floor: float
    kernel_rows: int
    kernel_feats: int
    kernel_nodes: tuple  # n_nodes per kernel check; node 1 is left empty
    serve_sizes: tuple
    contrib_sizes: tuple
    mesh_shard_rows: int
    col_rows: int


# auc_floor: a CPU run of this script's data (same seed, same held-out
# rows) with hist_method="segment" at 1,000,000 training rows reaches AUC
# 0.8801 after the same 20 rounds (CHANGES.md, PR 21); the floor sits 0.01
# under it.
CHIP = Sizes(rows=11_000_000, holdout=200_000, depth=6, rounds=20,
             eval_rounds=3, auc_floor=0.87, kernel_rows=8192, kernel_feats=28,
             kernel_nodes=(1, 4, 32), serve_sizes=(1, 7, 64, 300, 1000),
             contrib_sizes=(1, 7, 50), mesh_shard_rows=1_000_000,
             col_rows=50_000)
DRY = Sizes(rows=3000, holdout=500, depth=4, rounds=20, eval_rounds=2,
            auc_floor=0.6, kernel_rows=256, kernel_feats=4,
            kernel_nodes=(1, 4), serve_sizes=(1, 5, 33), contrib_sizes=(1, 5),
            mesh_shard_rows=512, col_rows=1024)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def train_params(depth: int) -> dict:
    return {"objective": "binary:logistic", "max_depth": depth,
            "max_bin": MAX_BIN, "eta": 0.3, "seed": SEED}


def make_data(n: int, seed: int):
    """Seeded HIGGS-shaped binary task: 28 standard-normal features, a
    nonlinear score over the first eight, label noise. Prefix-stable: the
    first k rows do not depend on n."""
    import numpy as np

    X = np.random.default_rng(seed).standard_normal(
        (n, FEATURES), dtype=np.float32)
    z = (X[:, 0] * X[:, 1] + 0.8 * np.abs(X[:, 2]) - 0.6 * X[:, 3] ** 2
         + 0.7 * X[:, 4] + 0.5 * np.sin(2.0 * X[:, 5]) + 0.4 * X[:, 6] * X[:, 7])
    noise = np.random.default_rng(seed + 1).standard_normal(
        n, dtype=np.float32)
    y = (z + 0.7 * noise > 0.05).astype(np.float32)
    return X, y


class CompileClock:
    """Sums jax's own compile-time events, so a phase can report how much
    of its wall time was tracing/lowering and backend compilation, and how
    many executables came from the persistent cache."""

    def __init__(self) -> None:
        import jax.monitoring

        self.backend_s = 0.0
        self.trace_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.backend_s += secs
        elif event.endswith(("jaxpr_trace_duration",
                             "jaxpr_to_mlir_module_duration")):
            self.trace_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event.endswith("/cache_hits"):
            self.cache_hits += 1
        elif event.endswith("/cache_misses"):
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.backend_s, "trace_lower_s": self.trace_s,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


class Spy:
    """Stands in for one jitted program of ``xgboost_tpu.core``: counts the
    dispatches and keeps the first call's abstract signature, so the very
    program that ran can be lowered again and its text inspected."""

    def __init__(self, fn) -> None:
        self.fn = fn
        self.calls = 0
        self.first = None

    def __call__(self, *args, **kwargs):
        import jax
        import numpy as np

        if self.first is None:
            def abstract(x):
                x = x if isinstance(x, jax.Array) else np.asarray(x)
                return jax.ShapeDtypeStruct(
                    x.shape, x.dtype, sharding=getattr(x, "sharding", None))

            self.first = (jax.tree.map(abstract, args), kwargs)
        self.calls += 1
        return self.fn(*args, **kwargs)

    def lowered_text(self) -> str:
        args, kwargs = self.first
        return self.fn.lower(*args, **kwargs).as_text()


class WarningTrap(logging.Handler):
    def __init__(self) -> None:
        super().__init__(level=logging.WARNING)
        self.messages = []

    def emit(self, record: logging.LogRecord) -> None:
        self.messages.append(record.getMessage())


# ------------------------------------------------------------------ phases

def phase_device(ctx) -> dict:
    import importlib.metadata as md

    import jax

    import xgboost_tpu as xgb

    info = xgb.build_info()
    if info["native_runtime"] is not True:
        raise RuntimeError("native runtime did not build/load "
                           f"(build_info: {info})")
    out = {"jax": jax.__version__, "jaxlib": md.version("jaxlib"),
           "native_runtime": True,
           "compile_cache_dir": jax.config.jax_compilation_cache_dir}
    try:
        out["libtpu"] = md.version("libtpu")
    except md.PackageNotFoundError:
        out["libtpu"] = None
    say(f"device {ctx['device']}  {out}")
    return out


def phase_kernels(ctx) -> dict:
    """The two Pallas histogram kernels against ``build_hist_segment`` on
    identical inputs, compiled (interpret mode only in the dry run). Bound:
    each row's (g, h) is rounded to 15-bit fixed point with a global
    per-component scale (kernel docstrings: relative error 2^-15 of max|g|
    per element), so a cell holding ``cnt`` rows may differ from the f32
    reference by cnt * max|g| * 2^-15 plus f32 summation noise."""
    import jax.numpy as jnp
    import numpy as np

    from xgboost_tpu.ops.histogram import (build_hist_segment,
                                           fused_advance_coarse)
    from xgboost_tpu.ops.pallas.histogram import (
        build_hist_pallas, fused_advance_coarse_pallas)
    from xgboost_tpu.ops.split import COARSE_B, coarse_bin_ids

    sz, interp = ctx["sizes"], ctx["dry_run"]
    n, F = sz.kernel_rows, sz.kernel_feats
    rng = np.random.default_rng(SEED + 7)
    gpair = jnp.asarray(np.stack(
        [rng.standard_normal(n), rng.uniform(0.05, 0.25, n)],
        axis=1).astype(np.float32))
    max_abs = np.abs(np.asarray(gpair)).max(axis=0)            # [2]
    ones = jnp.ones((n, 2), jnp.float32)
    checked = []

    def close(name, got, ref, cnt):
        got, ref, cnt = np.asarray(got), np.asarray(ref), np.asarray(cnt)
        if got.shape != ref.shape or not np.isfinite(got).all():
            raise AssertionError(f"{name}: shape {got.shape} vs {ref.shape} "
                                 "or non-finite values")
        bound = (cnt * max_abs * 2.0 ** -15
                 + 1e-5 * np.abs(ref) + 1e-6 * max_abs)
        worst = float((np.abs(got - ref) / bound).max())
        if worst > 1.0:
            raise AssertionError(
                f"{name}: {worst:.2f}x the int8x2 quantisation bound")
        checked.append(name)

    # 256 bins in uint8 (SWAR one-hot branch) and a NaN-bearing matrix:
    # 257 slots in uint16, missing at 256 (compare-built one-hot branch)
    for B, dtype in ((256, np.uint8), (257, np.uint16)):
        miss = B - 1 if B == 257 else B      # B: no row carries it
        bins_np = rng.integers(0, 256, (n, F)).astype(dtype)
        if B == 257:
            bins_np[rng.random((n, F)) < 0.1] = 256
        bins = jnp.asarray(bins_np)
        bins_t = bins.T
        cb = coarse_bin_ids(bins.astype(jnp.int32), miss)
        for N in sz.kernel_nodes:
            # node 1 stays empty where the level has one; rel == N: inactive
            rel_np = rng.integers(0, N + 1, n).astype(np.int32)
            if N > 1:
                rel_np[rel_np == 1] = 0
            rel = jnp.asarray(rel_np)
            ref = build_hist_segment(bins, gpair, rel, N, B)
            cnt = build_hist_segment(bins, ones, rel, N, B)
            tag = f"B{B}.N{N}"
            close(f"build_hist_pallas.{tag}",
                  build_hist_pallas(bins_t, gpair, rel, N, B,
                                    interpret=interp), ref, cnt)

        # fused sweep: advance below a 2-node level's splits, then the
        # 4-node level's coarse histogram; reference = its own XLA body
        pos = jnp.asarray(rng.integers(1, 3, n).astype(np.int32))
        splits = (jnp.asarray([1, F - 1], jnp.int32),
                  jnp.asarray([90, 200], jnp.int32),
                  jnp.asarray([True, False]), jnp.asarray([True, True]))
        got_pos, got_h = fused_advance_coarse_pallas(
            bins_t, gpair, pos, *splits, lo_prev=1, n_prev=2, lo=3,
            n_level=4, missing_bin=miss, interpret=interp)
        ref_pos, ref_h = fused_advance_coarse(
            bins, gpair, pos,
            {"kind": "dense", "lo": 1, "n_level": 2, "arrs": splits},
            3, 4, miss, bins_t=bins_t, method="segment")
        if not np.array_equal(np.asarray(got_pos), np.asarray(ref_pos)):
            raise AssertionError(f"fused.B{B}: advanced positions differ")
        rel4 = jnp.where((ref_pos >= 3) & (ref_pos < 7), ref_pos - 3, 4)
        close(f"fused_advance_coarse_pallas.B{B}", got_h, ref_h,
              build_hist_segment(cb, ones, rel4, 4, COARSE_B))

    say(f"kernels: {len(checked)} checks inside the int8x2 bound "
        f"({'INTERPRET mode' if interp else 'compiled'})")
    return {"checks": len(checked), "compiled": not interp}


def phase_train(ctx) -> dict:
    import numpy as np

    import xgboost_tpu as xgb
    from xgboost_tpu import core
    from xgboost_tpu.metric.auc import binary_roc_auc
    from xgboost_tpu.obs.metrics import (degrade_counts, eval_walk_counts,
                                         grow_schedule_counts,
                                         hist_dot_counts, hist_dot_rows)
    from xgboost_tpu.tree.grow import resolve_schedule

    sz = ctx["sizes"]
    t0 = time.perf_counter()
    X, y = make_data(sz.rows, SEED)
    Xh, yh = make_data(sz.holdout, SEED + 100)
    t1 = time.perf_counter()
    dtrain = xgb.DMatrix(X, label=y)
    dhold = xgb.DMatrix(Xh, label=yh)
    binned = dtrain.binned(MAX_BIN)
    np.asarray(binned.bins[:1])                      # upload finished
    t2 = time.perf_counter()
    say(f"train: {sz.rows} x {FEATURES} rows generated in {t1 - t0:.1f}s, "
        f"sketch+bin+upload {t2 - t1:.1f}s (smoke timing)")

    params = train_params(sz.depth)
    sched = resolve_schedule(
        "auto", sz.rows, binned.max_nbins, binned.has_missing, numeric=True)
    say(f"train: hist_method=auto resolves to {sched.name!r} "
        f"(max_nbins={binned.max_nbins}, has_missing={binned.has_missing})")

    spies = {name: Spy(getattr(core, name)) for name in (
        "_fused_multi_round_fn", "_fused_round_fn", "_eval_partials_fn")}
    for name, spy in spies.items():
        setattr(core, name, spy)
    try:
        # driver 1: nothing consumes per-round output -> batched rounds
        t3 = time.perf_counter()
        bst = xgb.train(params, dtrain, sz.rounds, verbose_eval=False)
        pred = bst.predict(dhold)
        t4 = time.perf_counter()
        # driver 2: an eval set -> one fused round + one eval program a round
        res = {}
        bst2 = xgb.train({**params, "eval_metric": "logloss"}, dtrain,
                         sz.eval_rounds, evals=[(dhold, "holdout")],
                         evals_result=res, verbose_eval=False)
        t5 = time.perf_counter()
    finally:
        for name, spy in spies.items():
            setattr(core, name, spy.fn)

    chunks, left = [], sz.rounds     # train()'s pow2 chunks of <= 16 rounds
    while left >= 2:
        chunks.append(1 << (min(16, left).bit_length() - 1))
        left -= chunks[-1]
    want = {"_fused_multi_round_fn": len(chunks),
            "_fused_round_fn": sz.eval_rounds + sz.rounds - sum(chunks),
            "_eval_partials_fn": sz.eval_rounds}
    got = {k: s.calls for k, s in spies.items()}
    if got != want:
        raise AssertionError(f"dispatched programs {got}, expected {want}")
    if bst.num_boosted_rounds() != sz.rounds \
            or bst2.num_boosted_rounds() != sz.eval_rounds:
        raise AssertionError("wrong number of boosted rounds")
    if bst._fused_blocked or bst2._fused_blocked:
        raise AssertionError("the fused round latched off")
    custom_calls = {k: s.lowered_text().count("tpu_custom_call")
                    for k, s in spies.items() if k != "_eval_partials_fn"}
    if not ctx["dry_run"] and min(custom_calls.values()) < 1:
        raise AssertionError(
            f"no tpu_custom_call in the dispatched round program: "
            f"{custom_calls}")
    if any(degrade_counts().values()):
        raise AssertionError(f"a degrade handler fired: {degrade_counts()}")
    traced = grow_schedule_counts()
    if set(traced) != {sched.name}:
        raise AssertionError(
            f"grow programs were traced under {traced}, not under "
            f"{sched.name!r} alone")
    # every evaluated round's new tree walked over the held-out rows from
    # its device heap on a TPU; the CPU keeps the forest walk
    walks = eval_walk_counts()
    want_walks = {"forest" if ctx["dry_run"] else "heap": sz.eval_rounds}
    if walks != want_walks:
        raise AssertionError(
            f"xtpu_eval_walk_total {walks}, expected {want_walks}")

    if pred.shape != (sz.holdout,) or not np.isfinite(pred).all():
        raise AssertionError("predictions: wrong shape or non-finite")
    auc = binary_roc_auc(yh.astype(np.float64), pred.astype(np.float64),
                         np.ones(sz.holdout))
    ll = res["holdout"]["logloss"]
    if not (np.isfinite(ll).all() and ll[-1] < ll[0]):
        raise AssertionError(f"holdout logloss did not fall: {ll}")
    if not auc >= sz.auc_floor:
        raise AssertionError(
            f"held-out AUC {auc:.4f} under the floor {sz.auc_floor}")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        for ext in ("json", "ubj"):
            path = os.path.join(tmp, f"m.{ext}")
            bst.save_model(path)
            again = xgb.Booster(model_file=path).predict(dhold)
            if not np.array_equal(again, pred):
                raise AssertionError(f"{ext} save/load changed predictions")

    say(f"train: {sz.rounds} batched rounds + predict {t4 - t3:.1f}s, "
        f"{sz.eval_rounds} evaluated rounds {t5 - t4:.1f}s, compile included "
        f"(smoke timings); held-out AUC {auc:.4f} (floor {sz.auc_floor}); "
        f"tpu_custom_call per program {custom_calls}; "
        + ", ".join(
            [f'xtpu_grow_schedule_total{{schedule="{k}"}} {v}'
             for k, v in traced.items()]
            + [f'xtpu_eval_walk_total{{kind="{k}"}} {v}'
               for k, v in walks.items()]
            + [f'xtpu_hist_dot_total{{form="{k}"}} {v}'
               for k, v in hist_dot_counts().items()]
            + [f"xtpu_hist_dot_rows {hist_dot_rows()}"]))
    ctx.update(bst=bst, Xh=Xh)
    return {"rows": sz.rows, "schedule": sched.name,
            "grow_schedule_total": traced, "eval_walk_total": walks,
            "hist_dot_total": hist_dot_counts(),
            "hist_dot_rows": hist_dot_rows(),
            "auc": round(auc, 4),
            "auc_floor": sz.auc_floor, "dispatches": got,
            "tpu_custom_call": custom_calls,
            "holdout_logloss": [round(float(v), 5) for v in ll],
            "ingest_s": round(t2 - t1, 2)}


def phase_serve(ctx) -> dict:
    import numpy as np

    import xgboost_tpu as xgb
    from xgboost_tpu.serve import Server

    sz, bst, Xh = ctx["sizes"], ctx["bst"], ctx["Xh"]
    # references first: Booster.predict compiles walk programs of its own,
    # which the server's recompile counter would see after warmup
    want = {n: bst.predict(xgb.DMatrix(Xh[:n])) for n in sz.serve_sizes}
    want_phi = {n: bst.predict(xgb.DMatrix(Xh[:n]), pred_contribs=True)
                for n in sz.contrib_sizes}
    with Server(models={"m": bst}, buckets=(1, 8, 64, 512),
                shap_buckets=(1, 8, 64)) as srv:
        srv.warmup()
        srv.warmup_contribs()
        for n in sz.serve_sizes:
            got = np.asarray(srv.predict(Xh[:n], "m"))
            if not np.array_equal(got, want[n]):
                raise AssertionError(
                    f"serve predict, {n} rows: differs from Booster.predict")
        for n in sz.contrib_sizes:
            phi = np.asarray(srv.contribs(Xh[:n], "m"))
            np.testing.assert_allclose(phi, want_phi[n], rtol=1e-5, atol=1e-5)
        recompiles = srv.recompiles_after_warmup
    if recompiles != 0:
        raise AssertionError(f"{recompiles} recompiles after warmup")
    say(f"serve: {len(sz.serve_sizes)} predict + {len(sz.contrib_sizes)} "
        "contribs requests identical to the booster, 0 recompiles")
    return {"predict_requests": len(sz.serve_sizes),
            "contrib_requests": len(sz.contrib_sizes),
            "recompiles_after_warmup": recompiles}


def phase_mesh(ctx):
    """Row- and column-split training over every visible device against the
    one-device model of the same data. ``tests/test_distributed.py`` asserts
    predictions equal to rtol/atol 1e-5 on the virtual CPU mesh. On hardware
    that holds tree by tree only until a near-tie: the shards' f32 partial
    histograms are psum'd in another order than one device accumulates them,
    so a split between two almost equal gains may go the other way (my chip
    run, PR 21: 0.54% of held-out predictions moved after 3 rounds at
    4 x 1M rows), and every later round inherits it. So the bar here is: the
    FIRST tree agrees on >= 90% of held-out rows (a missing psum, a wrong
    quantisation scale or a misplaced shard moves every leaf value), and the
    full models are the same model by held-out logloss; the exact-agreement
    share of the full model is reported, not asserted."""
    import jax
    import numpy as np

    import xgboost_tpu as xgb
    from xgboost_tpu.metric import get_metric

    n_dev = len(jax.devices())
    if n_dev < 4:
        say(f"mesh: SKIPPED, {n_dev} device(s) visible (needs 4)")
        return f"skipped: {n_dev} device(s)"
    sz = ctx["sizes"]
    mesh = xgb.make_data_mesh()
    params = train_params(sz.depth)
    Xh, yh = make_data(sz.holdout, SEED + 100)
    dhold = xgb.DMatrix(Xh, label=yh)
    logloss = get_metric("logloss")
    problems = []

    def batches(X, y, n_batches=4):
        """The rows through a ``DataIter``: ingest then lands on the shards
        (``DMatrix.place_binned``), where it used to put the whole matrix
        on device 0 first."""
        step = -(-len(y) // n_batches)

        class It(xgb.DataIter):
            def __init__(self):
                super().__init__()
                self.at = 0

            def reset(self):
                self.at = 0

            def next(self, input_data):
                if self.at >= len(y):
                    return 0
                input_data(data=X[self.at:self.at + step],
                           label=y[self.at:self.at + step])
                self.at += step
                return 1
        return xgb.QuantileDMatrix(It(), max_bin=256)

    def whole_copies(shape):
        """Live uint8 arrays of the bin matrix's shape on ONE device."""
        return sum(1 for a in jax.live_arrays()
                   if a.shape == shape and a.dtype == np.uint8
                   and len(a.sharding.device_set) == 1)

    def both(name, rows, extra, rounds, sharded_axis, by_iterator=False):
        X, y = make_data(rows, SEED + 200)
        whole_before = whole_copies(X.shape)
        dm = batches(X, y) if by_iterator else xgb.DMatrix(X, label=y)
        b_mesh = xgb.train({**params, "mesh": mesh, **extra}, dm, rounds,
                           verbose_eval=False)
        bins = b_mesh._caches[id(dm)]["binned"].bins
        shards = bins.addressable_shards
        width = bins.shape[sharded_axis]
        if (len({s.device for s in shards}) != n_dev
                or any(s.data.shape[sharded_axis] * n_dev != width
                       for s in shards)):
            problems.append(
                f"{name}: bins {bins.shape} are not split over {n_dev} "
                f"devices: {[(str(s.device), s.data.shape) for s in shards]}")
        if by_iterator:
            # no device holds more of the bin matrix than its share: the
            # whole matrix alive on one device is the fault ingest had (an
            # in-memory DMatrix keeps its own whole copy, by design)
            whole = whole_copies(bins.shape) - whole_before
            if whole > 0 or any(s.data.shape[0] * n_dev > rows
                                for s in shards):
                problems.append(
                    f"{name}: a device holds more than rows / chips of the "
                    f"bin matrix {bins.shape}: {whole} new whole copies on "
                    f"one device, shards {[s.data.shape for s in shards]}")
            dm = xgb.DMatrix(X, label=y)      # the one-chip twin's matrix
        b_one = xgb.train(params, dm, rounds, verbose_eval=False)

        def same(a, b):
            return round(float(np.isclose(a, b, rtol=1e-5,
                                          atol=1e-5).mean()), 4)

        first = [b.predict(dhold, iteration_range=(0, 1))
                 for b in (b_mesh, b_one)]
        full = [b.predict(dhold) for b in (b_mesh, b_one)]
        out = {"rows": rows, "rounds": rounds,
               "shard": [int(d) for d in shards[0].data.shape],
               "first_tree_same": same(*first),
               "all_rounds_same": same(*full),
               "logloss_delta": round(abs(
                   logloss(full[0], dhold.info)
                   - logloss(full[1], dhold.info)), 6)}
        if out["first_tree_same"] < 0.9 or out["logloss_delta"] > 2e-3:
            problems.append(f"{name}: mesh model != one-device model: {out}")
        return out

    row = both("row split", n_dev * sz.mesh_shard_rows, {}, 3, 0)
    fed = both("row split by iterator", n_dev * sz.mesh_shard_rows, {}, 3, 0,
               by_iterator=True)
    # col split keeps the one-pass kernel, so its one-chip twin must too:
    # col_rows stays under the 65,536-row promotion threshold of "auto"
    col = both("col split", sz.col_rows, {"data_split_mode": "col"}, 2, 1)
    from xgboost_tpu.obs.metrics import mesh_counts

    counts = mesh_counts()
    say(f"mesh: {n_dev} devices; row split {row}; by iterator {fed}; col "
        f"split {col}; xtpu_mesh_allreduce_total {counts['allreduce']}, "
        f"bytes {counts['bytes']}")
    if not counts["allreduce"].get("hist_psum"):
        problems.append(f"no histogram exchange was counted: {counts}")
    if problems:
        raise AssertionError("; ".join(problems))
    return {"devices": n_dev, "row_split": row, "row_split_by_iterator": fed,
            "col_split": col, "mesh_allreduce_total": counts["allreduce"]}


PHASES = (("device", phase_device, ()), ("kernels", phase_kernels, ()),
          ("train", phase_train, ()), ("serve", phase_serve, ("train",)),
          ("mesh", phase_mesh, ()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="sandbox rehearsal on the CPU backend: tiny shapes, "
                         "interpret-mode kernels; proves nothing about a chip")
    args = ap.parse_args(argv)
    if args.dry_run_cpu:
        say("DRY RUN ON CPU — tiny shapes, interpret-mode kernels; "
            "no device result")
        os.environ["JAX_PLATFORMS"] = "cpu"
        # the level the repo's CPU tests compile at. Serve's bit-identity
        # to Booster.predict holds on XLA:CPU only there: at the default
        # level the two walk programs' f32 tree sums differ in the last ulp
        if "xla_backend_optimization_level" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_backend_optimization_level=0").strip()

    import jax

    if args.dry_run_cpu:
        # no CPU executables in the cache directory a chip run uses
        jax.config.update("jax_enable_compilation_cache", False)
    backend = jax.default_backend()
    if backend != ("cpu" if args.dry_run_cpu else "tpu"):
        print(f"chip_smoke: no TPU: jax.default_backend() is {backend!r} "
              "(run through the chip tool, or pass --dry-run-cpu to "
              "rehearse)", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}

    import xgboost_tpu  # noqa: F401  (places the compile cache)

    clock = CompileClock()
    trap = WarningTrap()
    logging.getLogger("xgboost_tpu").addHandler(trap)
    ctx = {"sizes": DRY if args.dry_run_cpu else CHIP,
           "dry_run": args.dry_run_cpu, "device": device}
    results, timings, failed = {}, {}, []
    for name, fn, needs in PHASES:
        if any(n in failed for n in needs):
            failed.append(name)
            say(f"{name}: NOT RUN, needs {needs}")
            continue
        c0, t0 = clock.snapshot(), time.perf_counter()
        try:
            results[name] = fn(ctx)
        except Exception:
            failed.append(name)
            say(f"{name}: FAILED\n{traceback.format_exc()}")
        timings[name] = {
            "wall_s": round(time.perf_counter() - t0, 2),
            **{k: round(v - c0[k], 2) for k, v in clock.snapshot().items()}}
        say(f"{name}: smoke timing {timings[name]}")

    fallbacks = [m for m in trap.messages if "falling back" in m]
    if fallbacks:
        failed.append("fallback-log")
        say(f"'falling back' was logged: {fallbacks}")
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    report = {"device": device, "dry_run": args.dry_run_cpu,
              **{k: results[k] for k in ("train", "kernels", "serve", "mesh")},
              "versions": results["device"], "fallback_records": 0,
              "warnings_logged": len(trap.messages),
              "smoke_timings": timings}
    verdict = {"ok": True, "device": device}
    if args.dry_run_cpu:
        verdict["dry_run"] = True
    print(json.dumps(report))
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
