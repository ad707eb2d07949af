"""Traffic driver ``cat_loop``: boosting-round continuation calls on one
persistent ``DMatrix`` of a float32 matrix with categorical columns (passed
as ``feature_types`` with ``enable_categorical=True``, as XGBoost 2.0's
categorical tutorial has it) and one ``Booster``, through the public
``xgb.train``.

The window, ``setup_s`` and ``train_rounds_per_s`` are ``train_loop``'s, word
for word: the window opens after one warm-up call of the cell's own shape,
issues continuation calls while fewer than ``--seconds`` have passed, and
closes when the last call has returned and ``block_until_ready`` on the
booster's margin has returned; ``setup_s`` runs from process start to the
start of the window; ``train_rounds_per_s`` is all rounds completed in the
window over its whole length. This driver reads its generator from
``lib/data_airline.py`` and holds the program to ``lib/reference_cat.py
numbers`` over the rows in memory: every node's stated sums against the
rows routed there (a categorical node by its left set, a missing value by
its default side), the stated gains and leaves, an exact search of the top
splits on a sample of blocks, categorical splits included, and the margin
the window carried.

A mix's file gives ``rounds_per_call``, ``trace_calls``, ``follow_rounds``,
``stride_blocks``, ``top_levels``, ``round_programs`` and
``expect_schedule`` (by platform: the histogram schedule
``xtpu_grow_schedule_total`` has to name, and no other). The harness pins no
``hist_method`` and sets no switch of the program.

From the program this file takes ``xgb.DMatrix``, ``xgb.train``, the model as
``Booster.save_raw("json")`` states it (``split_type``, ``categories``,
``default_left`` and ``loss_changes`` too), the booster's training margin,
``_fused_blocked``, ``degrade_counts()``, ``grow_schedule_counts()``,
``kernel_vmem_bytes()``, ``cat_split_counts()`` and the schedule rule
``tree/grow.py resolve_schedule`` with ``two_level_numeric_bins``.
``require_program`` reads those before any data is made: a program that
lacks them, or that would not run the categorical columns on the schedule
the mix expects, is refused at once (exit 4) rather than left to exhaust
the chip's memory at its first compile.

The run's wall time by part goes on stderr as ``check``'s last line: the
whole run, compiling, has a budget of 300 s (PERF.md section 4).
"""

from __future__ import annotations

import gc
import json
import sys
import time

import numpy as np

from drivers import train_loop
from drivers.sparse_loop import _max_rss_gb
from drivers.train_loop import training_margin
from lib import compare, data_airline
from lib import reference_cat as rc

CAT_COLS = data_airline.CAT_COLS


def require_program(config: dict, platform: str, expected: str):
    """The program's categorical counter and schedule rule, and the
    schedule it resolves for this configuration's matrix on this platform:
    refused (exit 4) where it lacks them or resolves another."""
    try:
        from xgboost_tpu.obs.metrics import cat_split_counts
        from xgboost_tpu.ops.split import CatInfo
        from xgboost_tpu.tree.grow import (resolve_schedule,
                                           two_level_numeric_bins)
    except ImportError as e:
        print(f"this program has no categorical split counter or no rule "
              f"for categorical columns on the two-level schedules ({e}): "
              "its default path cannot hold the cell's matrix on one chip; "
              "nothing measured", file=sys.stderr, flush=True)
        raise SystemExit(4)
    slots = max(data_airline.CATEGORIES.values()) + 1   # and the missing one
    one = np.zeros(len(data_airline.COLUMNS), bool)
    cat = CatInfo(is_cat=one, is_onehot=one, cols=CAT_COLS,
                  numeric_bins=int(config["params"]["max_bin"]))
    schedule = resolve_schedule(
        "auto", int(config["rows"]), slots, True, numeric=False,
        numeric_bins=two_level_numeric_bins(cat, slots, True)).name
    if schedule != expected:
        print(f"the program resolves the {schedule!r} schedule for this "
              f"matrix on {platform}, not {expected!r}; nothing measured",
              file=sys.stderr, flush=True)
        raise SystemExit(4)
    return cat_split_counts


def make_inputs(config: dict, seed: int):
    gen = data_airline.GENERATORS[config["data"]["generator"]]
    return gen(int(config["rows"]), int(config["features"]), seed, stream=0)


def model_trees(bst):
    """The model as the program states it, as arrays for the walker: each
    split's stated gain, default side and, at a categorical node, its left
    set."""
    model = json.loads(bytes(bst.save_raw("json")))["learner"]
    trees = []
    for t in model["gradient_booster"]["trees"]:
        n = len(t["left_children"])
        trees.append({
            "left": np.asarray(t["left_children"], np.int64),
            "right": np.asarray(t["right_children"], np.int64),
            "feat": np.asarray(t["split_indices"], np.int64),
            "thr": np.asarray(t["split_conditions"], np.float32),
            "value": np.asarray(t["split_conditions"], np.float32),
            "sum_hess": np.asarray(t["sum_hessian"], np.float64),
            "gain": np.asarray(t["loss_changes"], np.float64),
            "default_left": np.asarray(t["default_left"], bool),
            "is_cat": np.asarray(t.get("split_type", [0] * n), bool),
            "cats": {int(k): v for k, v in t.get("categories", {}).items()}})
    return trees, float(model["learner_model_param"]["base_score"][0])


def source(X, y) -> rc.Source:
    """The rows in memory as the comparison's stream of blocks."""
    return rc.array_source(X, y, data_airline.BLOCK)


def measure(ctx) -> dict:
    """Set-up, then the window. Returns the state ``check`` reads, with the
    program's outputs on the host and the program's objects dropped."""
    config, mix = ctx.config, ctx.traffic
    expected = mix["expect_schedule"][ctx.platform]
    cat_split_counts = require_program(config, ctx.platform, expected)
    import jax

    import xgboost_tpu as xgb
    from xgboost_tpu.obs.metrics import (degrade_counts, grow_schedule_counts,
                                         kernel_vmem_bytes)

    start_s = time.perf_counter() - ctx.t_start
    params = dict(config["params"])
    params["seed"] = ctx.seed % (2 ** 31 - 1)
    rpc = int(mix["rounds_per_call"])

    t = time.perf_counter()
    X, y = make_inputs(config, ctx.seed)
    data_s = time.perf_counter() - t

    t = time.perf_counter()
    dtrain = xgb.DMatrix(X, label=y,
                         feature_types=data_airline.feature_types(),
                         enable_categorical=True)
    binned = dtrain.binned(int(params["max_bin"]))
    np.asarray(binned.bins[:1])                  # upload finished
    ingest_s = time.perf_counter() - t
    ctx.say(f"data {data_s:.2f}s, ingest {ingest_s:.2f}s ({X.shape[0]} x "
            f"{X.shape[1]}, {100.0 * y.mean():.3f}% late, "
            f"{100.0 * np.isnan(X).mean():.3f}% missing); bins "
            f"{binned.bins.dtype} {binned.max_nbins} slots, has_missing "
            f"{binned.has_missing}, categories "
            f"{[int(binned.cuts.n_real_bins()[c]) for c in CAT_COLS]}")

    def call(bst):
        # through the module, so that a test can break it from underneath
        bst = train_loop.train_call(xgb, params, dtrain, rpc, bst, [], {})
        jax.block_until_ready(training_margin(bst, dtrain))
        return bst

    # warm-up: one call of the cell's own shape, on the objects the window uses
    t = time.perf_counter()
    bst = call(None)
    warm_s = time.perf_counter() - t
    # the state the window starts from, for the comparison
    start_margin = np.asarray(training_margin(bst, dtrain),
                              np.float32).reshape(-1)
    clock0 = ctx.compile_clock.snapshot()
    # the warm-up's garbage is collected in set-up (``wide_loop``)
    gc.collect()
    setup_s = time.perf_counter() - ctx.t_start
    ctx.say(f"warm-up call {warm_s:.2f}s, compile {clock0}")
    full_gc = []                 # seconds of each full collection inside it

    def on_gc(phase, info, began=[0.0]):
        if info["generation"] == 2:
            if phase == "start":
                began[0] = time.perf_counter()
            else:
                full_gc.append(time.perf_counter() - began[0])
    gc.callbacks.append(on_gc)

    calls, failed = [], 0
    trace_calls = int(mix.get("trace_calls", 0)) if ctx.trace else 0
    rounds0 = bst.num_boosted_rounds()
    splits0 = cat_split_counts()
    if trace_calls:
        ctx.start_trace()
    w0 = time.perf_counter()
    while time.perf_counter() - w0 < ctx.seconds:
        c0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.call", call=len(calls)):
                bst = call(bst)
        except Exception as e:                   # a failed operation: counted,
            ctx.say(f"call {len(calls)} raised {type(e).__name__}: {e}")
            failed += 1                          # and the run is not correct
            calls.append(time.perf_counter() - c0)
            break
        calls.append(time.perf_counter() - c0)
        if trace_calls and len(calls) == trace_calls:
            ctx.stop_trace(rounds=trace_calls * rpc)
            trace_calls = 0
    if trace_calls:                              # window shorter than asked
        ctx.stop_trace(rounds=len(calls) * rpc)
    jax.block_until_ready(training_margin(bst, dtrain))
    window_s = time.perf_counter() - w0
    gc.callbacks.remove(on_gc)
    claimed = (len(calls) - failed + 1) * rpc    # warm-up call included
    rounds = bst.num_boosted_rounds() - rounds0
    memory_peak = ctx.read_memory_peak()
    clock1 = ctx.compile_clock.snapshot()

    margin = np.asarray(training_margin(bst, dtrain), np.float32).reshape(-1)
    if not np.isfinite(margin).all():
        failed = max(failed, 1)
    degrades = degrade_counts()
    if bst._fused_blocked or any(degrades.values()):
        ctx.say(f"degraded: _fused_blocked={bst._fused_blocked} {degrades}")
        failed = max(failed, 1)
    # the schedule the grow programs were traced under, and no other
    schedules = grow_schedule_counts()
    ctx.say(f"grow schedules traced {schedules} (expected {expected!r}); "
            f"kernel_vmem_bytes {kernel_vmem_bytes()}")
    if not schedules.get(expected) or set(schedules) != {expected}:
        ctx.say(f"the grow programs did not all run the {expected!r} "
                "schedule")
        failed = max(failed, 1)
    t = time.perf_counter()
    trees, base = model_trees(bst)
    flush_s = time.perf_counter() - t
    # the window's splits by kind: the flush above pulled its trees
    splits1 = cat_split_counts()
    splits = {k: splits1.get(k, 0) - splits0.get(k, 0)
              for k in set(splits1) | set(splits0)}
    compiles_in_window = clock1["compiles"] - clock0["compiles"]
    ctx.say(f"window {window_s:.3f}s: {len(calls)} calls, {rounds} rounds, "
            f"calls s {[round(c, 3) for c in calls]}, compilations inside "
            f"the window {compiles_in_window}, full collections inside it "
            f"{[round(x, 3) for x in full_gc]}, tree flush {flush_s:.2f}s, "
            f"splits by kind {splits}, host RSS peak so far "
            f"{_max_rss_gb():.2f} GB")
    del bst, dtrain, binned                      # the program's state goes

    return {
        "end_to_end": {"setup_s": setup_s,
                       "train_rounds_per_s": rounds / window_s},
        "attempted": len(calls), "failed": failed,
        "memory_peak_bytes": memory_peak,
        "facts": {"ingest_s": ingest_s, "data_s": data_s, "warm_s": warm_s,
                  "compile_s": clock0["compile_s"], "setup_clock": clock0,
                  "compiles_in_window": compiles_in_window,
                  "call_s": calls, "window_s": window_s, "rounds": rounds,
                  "rounds_per_call": rpc, "cat": {"splits": splits},
                  "round_programs": list(mix["round_programs"]),
                  "wall": {"start_s": start_s, "flush_s": flush_s,
                           "measured_at": time.perf_counter()}},
        # one chip holds the one copy of every tree: no replica to differ
        "outputs": {"trees": trees, "base_margin": base, "margin": margin,
                    "start_margin": start_margin,
                    "replica_gap": 0.0, "rounds_claimed": claimed,
                    "warm_rounds": rpc},
        "inputs": (X, y, params),
    }


def numbers(outputs: dict, X, y, params: dict, mix: dict,
            detail: dict | None = None) -> dict:
    """``lib/reference_cat.py numbers`` over the rows in memory."""
    return rc.numbers(outputs, source(X, y), params,
                      int(mix["follow_rounds"]), int(mix["stride_blocks"]),
                      int(mix["top_levels"]), CAT_COLS, detail=detail)


def check(ctx, state) -> tuple:
    """The comparison with the blockwise reference -> (correct, table)."""
    X, y, params = state["inputs"]
    t = time.perf_counter()
    detail: dict = {}
    values = numbers(state["outputs"], X, y, params, ctx.traffic, detail)
    ok, table = compare.judge(values, ctx.limits)
    check_s = time.perf_counter() - t
    ctx.say("the comparison in detail: " + json.dumps(detail))
    ctx.say(f"reference and comparison {check_s:.2f}s, host "
            f"RSS peak {_max_rss_gb():.2f} GB")
    facts = state["facts"]
    wall, clock = facts["wall"], facts["setup_clock"]
    ctx.say(f"wall time by part: imports and backend {wall['start_s']:.1f}s, "
            f"data {facts['data_s']:.1f}s, ingest {facts['ingest_s']:.1f}s, "
            f"warm-up call {facts['warm_s']:.1f}s (all set-up's programs "
            f"traced and lowered in {clock['trace_lower_s']:.1f}s, compiled "
            f"in {clock['compile_s']:.1f}s), window {facts['window_s']:.1f}s, "
            f"tree flush {wall['flush_s']:.1f}s, trace read "
            f"{t - wall['measured_at']:.1f}s, reference and comparison "
            f"{check_s:.1f}s; {time.perf_counter() - ctx.t_start:.1f}s since "
            "the process started")
    return ok and state["failed"] == 0, table


# ---- the control and the planted faults ------------------------------------

CASES = ("sound", "control_bf16", "threshold_routed", "partition_reversed",
         "column_left_out", "half_batch", "state_unchanged")


def control_readings(config: dict, mix: dict, seed: int,
                     cases=CASES) -> dict:
    """The control and the planted faults, read with the blockwise reference
    in the program's place on this configuration's data:
    ``{case: {number: value}}``. Every case shares one sound warm-up call
    and differs in the window's first rounds, where the timed path runs:

    sound               the float32 reference itself
    control_bf16        the window's rounds with margin, gradient pairs and
                        leaf values held in bfloat16: the nearest precision
                        below float32
    threshold_routed    a categorical split's rows routed by ``bin <= its
                        split bin`` (a threshold on the code) and not by the
                        left set the tree states
    partition_reversed  the sorted-partition search over categories sorted
                        descending by ``G / (H + lambda)``
    column_left_out     the Origin column left out of the window's
                        histograms
    half_batch          the window's histograms miss the second half of
                        every block
    state_unchanged     the window's first call returned its state as it
                        got it
    """
    X, y = make_inputs(config, seed)
    params = dict(config["params"])
    follow, rpc = int(mix["follow_rounds"]), int(mix["rounds_per_call"])
    src = source(X, y)
    warm = rc.train(src, params, rpc, CAT_COLS)

    def window(**fault):
        run = rc.train(src, params, follow, CAT_COLS,
                       start_margin=warm["margin"], **fault)
        return {"trees": warm["trees"] + run["trees"], "warm_rounds": rpc,
                "base_margin": warm["base_margin"], "margin": run["margin"],
                "start_margin": warm["margin"], "replica_gap": 0.0,
                "rounds_claimed": rpc + follow}

    def first_half(_b, rows):
        return (np.arange(rows) < rows // 2).astype(np.float64)

    origin = data_airline.COLUMNS.index("Origin")
    make = {
        "control_bf16": lambda: window(precision="bfloat16"),
        "threshold_routed": lambda: window(fault="threshold_routed"),
        "partition_reversed": lambda: window(fault="partition_reversed"),
        "column_left_out": lambda: window(left_out=origin),
        "half_batch": lambda: window(row_weight=first_half),
        "state_unchanged": lambda: dict(sound, trees=warm["trees"],
                                        margin=warm["margin"],
                                        rounds_claimed=2 * rpc),
    }
    sound = window()
    return {name: numbers(sound if name == "sound" else make[name](), X, y,
                          params, mix)
            for name in cases}
