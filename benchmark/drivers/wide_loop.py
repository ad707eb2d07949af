"""Traffic driver ``wide_loop``: boosting-round continuation calls on one
persistent ``DMatrix`` of a dense float32 matrix of a few thousand columns,
and one ``Booster``, through the public ``xgb.train``.

The window, ``setup_s`` and ``train_rounds_per_s`` are ``train_loop``'s, word
for word: the window opens after one warm-up call of the cell's own shape,
issues continuation calls while fewer than ``--seconds`` have passed, and
closes when the last call has returned and ``block_until_ready`` on the
booster's margin has returned; ``setup_s`` runs from process start to the
start of the window; ``train_rounds_per_s`` is all rounds completed in the
window over its whole length. This driver reads its generator from
``lib/data_wide.py`` and holds the program to ``lib/reference_blocks.py
numbers``, the dense blockwise comparison ``mesh_loop`` uses, over the rows
in memory: every node's stated sums against the rows routed there, the
stated gains and leaves, an exact search of the top splits on a sample of
blocks, and the margin the window carried.

A mix's file gives ``rounds_per_call``, ``trace_calls``, ``follow_rounds``,
``stride_blocks``, ``top_levels``, ``round_programs`` and
``expect_schedule`` (by platform: the histogram schedule
``xtpu_grow_schedule_total`` has to name, and no other). The harness pins no
``hist_method`` and sets no switch of the program.

From the program this file takes ``xgb.DMatrix``, ``xgb.train``, the model as
``Booster.save_raw("json")`` states it (``loss_changes`` too), the booster's
training margin, ``_fused_blocked``, ``degrade_counts()``,
``grow_schedule_counts()``, ``fused_boundary_counts()``,
``hist_onehot_counts()``, ``hist_body_features()`` and ``binned_layout()``,
which ``measure`` imports before it makes any data, and, where the program
has it, ``kernel_vmem_bytes()``: the scoped VMEM each histogram kernel
states or is sized to. A program without that gauge runs on, and the line
on stderr says so.

The run's wall time by part goes on stderr as ``check``'s last line: the
whole run, compiling, has a budget of 300 s (PERF.md section 4).
"""

from __future__ import annotations

import gc
import json
import time

import numpy as np

from drivers import train_loop
from drivers.mesh_loop import model_trees
from drivers.sparse_loop import _max_rss_gb
from drivers.train_loop import training_margin
from lib import compare, data_wide
from lib import reference as ref
from lib import reference_blocks as rb

# the program's kernel tiling at F = 2,000 (ops/pallas/histogram.py): the
# refine build's feature block on the grid and the boundary sweep's group;
# the planted faults leave one of each out
REFINE_BLOCK = 200
SWEEP_GROUP = 250


def make_inputs(config: dict, seed: int):
    gen = data_wide.GENERATORS[config["data"]["generator"]]
    return gen(int(config["rows"]), int(config["features"]), seed, stream=0)


def wide_counts() -> dict:
    """What the program says of the paths a wide matrix takes."""
    from xgboost_tpu.obs.metrics import (binned_layout, fused_boundary_counts,
                                         hist_body_features,
                                         hist_onehot_counts)
    try:
        from xgboost_tpu.obs.metrics import kernel_vmem_bytes
        vmem = kernel_vmem_bytes()
    except ImportError:
        vmem = None                        # a program without the gauge
    return {"boundary": fused_boundary_counts(),
            "onehot": hist_onehot_counts(),
            "body_features": hist_body_features(), "kernel_vmem": vmem,
            **binned_layout()}


def source(X, y) -> rb.Source:
    """The rows in memory as the comparison's stream of blocks."""
    return rb.array_source(X, y, data_wide.BLOCK)


def measure(ctx) -> dict:
    """Set-up, then the window. Returns the state ``check`` reads, with the
    program's outputs on the host and the program's objects dropped."""
    import jax

    import xgboost_tpu as xgb
    from xgboost_tpu.obs.metrics import degrade_counts, grow_schedule_counts

    wide_counts()            # a program without the counters stops here
    start_s = time.perf_counter() - ctx.t_start
    config, mix = ctx.config, ctx.traffic
    params = dict(config["params"])
    params["seed"] = ctx.seed % (2 ** 31 - 1)
    rpc = int(mix["rounds_per_call"])

    t = time.perf_counter()
    X, y = make_inputs(config, ctx.seed)
    data_s = time.perf_counter() - t

    t = time.perf_counter()
    dtrain = xgb.DMatrix(X, label=y)
    binned = dtrain.binned(int(params["max_bin"]))
    np.asarray(binned.bins[:1])                  # upload finished
    ingest_s = time.perf_counter() - t
    ctx.say(f"data {data_s:.2f}s, ingest {ingest_s:.2f}s ({X.shape[0]} x "
            f"{X.shape[1]}, {100.0 * y.mean():.3f}% positive); bins "
            f"{binned.bins.dtype} {binned.max_nbins} slots, has_missing "
            f"{binned.has_missing}")

    def call(bst):
        # through the module, so that a test can break it from underneath
        bst = train_loop.train_call(xgb, params, dtrain, rpc, bst, [], {})
        jax.block_until_ready(training_margin(bst, dtrain))
        return bst

    # warm-up: one call of the cell's own shape, on the objects the window uses
    t = time.perf_counter()
    bst = call(None)
    warm_s = time.perf_counter() - t
    clock0 = ctx.compile_clock.snapshot()
    # the warm-up's garbage is collected in set-up: the first full
    # collection after tracing and compiling walks every object they left,
    # and would otherwise fall inside the window at a call of its choosing
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
    expected = mix["expect_schedule"][ctx.platform]
    schedules = grow_schedule_counts()
    wide = wide_counts()
    vmem = wide["kernel_vmem"]
    ctx.say(f"grow schedules traced {schedules} (expected {expected!r}); "
            f"wide counters: hist_body_features {wide['body_features']}, "
            f"fused boundaries {wide['boundary']}, kernel_vmem_bytes "
            f"{'not in this program' if vmem is None else vmem}, "
            f"one-hot builds {wide['onehot']}, bin bytes "
            f"{wide['bin_bytes']}")
    if not schedules.get(expected) or set(schedules) != {expected}:
        ctx.say(f"the grow programs did not all run the {expected!r} "
                "schedule")
        failed = max(failed, 1)
    t = time.perf_counter()
    trees, base = model_trees(bst)
    flush_s = time.perf_counter() - t
    compiles_in_window = clock1["compiles"] - clock0["compiles"]
    ctx.say(f"window {window_s:.3f}s: {len(calls)} calls, {rounds} rounds, "
            f"calls s {[round(c, 3) for c in calls]}, compilations inside "
            f"the window {compiles_in_window}, full collections inside it "
            f"{[round(x, 3) for x in full_gc]}, tree flush {flush_s:.2f}s, "
            f"host RSS peak so far {_max_rss_gb():.2f} GB")
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
                  "rounds_per_call": rpc, "wide": wide,
                  "round_programs": list(mix["round_programs"]),
                  "wall": {"start_s": start_s, "flush_s": flush_s,
                           "measured_at": time.perf_counter()}},
        # one chip holds the one copy of every tree: no replica to differ
        "outputs": {"trees": trees, "base_margin": base, "margin": margin,
                    "replica_gap": 0.0, "rounds_claimed": claimed,
                    "warm_rounds": rpc},
        "inputs": (X, y, params),
    }


def numbers(outputs: dict, X, y, params: dict, mix: dict,
            detail: dict | None = None) -> dict:
    """``lib/reference_blocks.py numbers`` over the rows in memory."""
    return rb.numbers(outputs, source(X, y), params,
                      int(mix["follow_rounds"]), int(mix["stride_blocks"]),
                      int(mix["top_levels"]), detail=detail)


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

def _unrouted(tree: dict, depth: int) -> dict:
    """The tree with its last level's splits not taken: a node at depth
    ``depth - 1`` that splits keeps its rows and gives them its own value,
    as rows that the last level's advance never moved would."""
    left, right = np.asarray(tree["left"]).copy(), \
        np.asarray(tree["right"]).copy()
    lo, hi = 2 ** (depth - 1) - 1, 2 ** depth - 1
    left[lo:hi] = right[lo:hi] = -1
    return dict(tree, left=left, right=right)


CASES = ("sound", "control_bf16", "block_left_out", "group_left_out",
         "last_level_unrouted", "half_batch", "state_unchanged")


def control_readings(config: dict, mix: dict, seed: int,
                     cases=CASES) -> dict:
    """The control and the planted faults, read with the blockwise reference
    in the program's place on this configuration's data:
    ``{case: {number: value}}``. Every case shares one sound warm-up call
    and differs in the window's first rounds, where the timed path runs:

    sound                the float32 reference itself
    control_bf16         the window's rounds with margin, gradient pairs and
                         leaf values held in bfloat16: the nearest precision
                         below float32
    block_left_out       the refine build's feature block of
                         ``REFINE_BLOCK`` columns that holds the column the
                         label reads most, left out of the window's
                         histograms
    group_left_out       the boundary sweep's group of ``SWEEP_GROUP``
                         columns that holds it, left out the same way
    last_level_unrouted  the margin the window carried took no step of its
                         trees' last level: the rows stayed where the level
                         above left them (the trees are stated whole)
    half_batch           the window's histograms miss the second half of
                         every block
    state_unchanged      the window's first call returned its state as it
                         got it
    """
    X, y = make_inputs(config, seed)
    params = dict(config["params"])
    follow, rpc = int(mix["follow_rounds"]), int(mix["rounds_per_call"])
    src = source(X, y)
    warm = rb.train(src, params, rpc)

    def window(rows=src, **fault):
        run = rb.train(rows, params, follow, start_margin=warm["margin"],
                       **fault)
        return {"trees": warm["trees"] + run["trees"], "warm_rounds": rpc,
                "base_margin": warm["base_margin"], "margin": run["margin"],
                "replica_gap": 0.0, "rounds_claimed": rpc + follow}

    def without(width):
        """The source with the ``width``-column slice that holds the strong
        column held constant: no cut, so no split, reads it."""
        at = data_wide.layout(X.shape[1])["strong"] // width * width
        Xm = X.copy()
        Xm[:, at:at + width] = 0.0
        return source(Xm, y)

    def first_half(_b, rows):
        return (np.arange(rows) < rows // 2).astype(np.float64)

    depth = int(params["max_depth"])
    make = {
        "control_bf16": lambda: window(precision="bfloat16"),
        "block_left_out": lambda: window(rows=without(REFINE_BLOCK)),
        "group_left_out": lambda: window(rows=without(SWEEP_GROUP)),
        "last_level_unrouted": lambda: dict(sound, margin=warm["margin"] + sum(
            ref.walk(_unrouted(t, depth), X) for t in sound["trees"][rpc:])),
        "half_batch": lambda: window(row_weight=first_half),
        "state_unchanged": lambda: dict(sound, trees=warm["trees"],
                                        margin=warm["margin"],
                                        rounds_claimed=2 * rpc),
    }
    sound = window()
    return {name: numbers(sound if name == "sound" else make[name](), X, y,
                          params, mix)
            for name in cases}
