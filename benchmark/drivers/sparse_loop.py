"""Traffic driver ``sparse_loop``: boosting-round continuation calls on one
persistent ``DMatrix`` of a float32 matrix that carries NaN, and one
``Booster``, through the public ``xgb.train``.

The window, ``setup_s`` and ``train_rounds_per_s`` are ``train_loop``'s, word
for word: the window opens after one warm-up call of the cell's own shape,
issues continuation calls while fewer than ``--seconds`` have passed, and
closes when the last call has returned and ``block_until_ready`` on the
booster's margin has returned; ``setup_s`` runs from process start to the
start of the window; ``train_rounds_per_s`` is all rounds completed in the
window over its whole length. ``train_loop`` itself reads its generator from
``lib/data.py``; this driver reads ``lib/data_sparse.py`` and holds the
program to ``lib/reference_sparse.py``, which knows NaN.

A mix's file gives ``rounds_per_call``, ``trace_calls``, ``follow_rounds``,
``round_programs`` and ``expect_schedule`` (by platform: the histogram
schedule ``xtpu_grow_schedule_total`` has to name, and no other). The
harness pins no ``hist_method`` and sets no switch of the program.

From the program this file takes ``xgb.DMatrix``, ``xgb.train``, the model as
``Booster.save_raw("json")`` states it (``default_left`` too), the booster's
training margin, ``_fused_blocked``, ``degrade_counts()``,
``grow_schedule_counts()``, ``fused_boundary_counts()``,
``hist_onehot_counts()``, ``hist_body_features()`` and ``binned_layout()``.
``measure`` imports the last four BEFORE it makes any data: a program without
them unrolls its kernel bodies over all 968 features, spends six to seven
minutes of set-up on tracing and compiling them, and cannot end a run inside
the time a run is given (PERF.md section 6, PR 36), so it fails at once with
an ``ImportError`` instead.

The run's wall time by part goes on stderr as ``check``'s last line: the
whole run, compiling, has a budget of 300 s (PERF.md section 4).
"""

from __future__ import annotations

import json
import time

import numpy as np

from drivers import train_loop
from drivers.train_loop import training_margin
from lib import compare, data_sparse
from lib import reference_sparse as rs


def make_inputs(config: dict, seed: int):
    gen = data_sparse.GENERATORS[config["data"]["generator"]]
    return gen(int(config["rows"]), int(config["features"]), seed, stream=0)


def model_trees(bst):
    """The model as the program states it, as arrays for the walker, each
    split's learned default direction among them. A leaf's value sits in
    ``split_conditions`` (XGBoost's JSON schema)."""
    model = json.loads(bytes(bst.save_raw("json")))["learner"]
    trees = [{"left": np.asarray(t["left_children"], np.int64),
              "right": np.asarray(t["right_children"], np.int64),
              "feat": np.asarray(t["split_indices"], np.int64),
              "thr": np.asarray(t["split_conditions"], np.float32),
              "dleft": np.asarray(t["default_left"], bool),
              "value": np.asarray(t["split_conditions"], np.float32),
              "sum_hess": np.asarray(t["sum_hessian"], np.float64)}
             for t in model["gradient_booster"]["trees"]]
    return trees, float(model["learner_model_param"]["base_score"][0])


def sparse_counts():
    """What the program says of the paths a missing slot and a wide matrix
    take."""
    from xgboost_tpu.obs.metrics import (binned_layout, fused_boundary_counts,
                                         hist_body_features,
                                         hist_onehot_counts)
    return {"boundary": fused_boundary_counts(),
            "onehot": hist_onehot_counts(),
            "body_features": hist_body_features(), **binned_layout()}


def _max_rss_gb() -> float:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def measure(ctx) -> dict:
    """Set-up, then the window. Returns the state ``check`` reads, with the
    program's outputs on the host and the program's objects dropped."""
    import jax

    import xgboost_tpu as xgb
    from xgboost_tpu.obs.metrics import degrade_counts, grow_schedule_counts

    sparse_counts()          # a program without the counters stops here
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
            f"{X.shape[1]}, {100.0 * np.isnan(X).mean():.3f}% NaN, "
            f"{100.0 * y.mean():.3f}% positive); bins {binned.bins.dtype} "
            f"{binned.max_nbins} slots, has_missing {binned.has_missing}")

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
    setup_s = time.perf_counter() - ctx.t_start
    ctx.say(f"warm-up call {warm_s:.2f}s, compile {clock0}")

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
    sparse = sparse_counts()
    ctx.say(f"grow schedules traced {schedules} (expected {expected!r}); "
            f"sparse counters {sparse}")
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
            f"the window {compiles_in_window}, tree flush {flush_s:.2f}s, "
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
                  "rounds_per_call": rpc, "sparse": sparse,
                  "round_programs": list(mix["round_programs"]),
                  "wall": {"start_s": start_s, "flush_s": flush_s,
                           "measured_at": time.perf_counter()}},
        "outputs": {"trees": trees, "base_margin": base, "margin": margin,
                    "rounds_claimed": claimed, "warm_rounds": rpc},
        "inputs": (X, y, params),
    }


# ---- the numbers that decide ``correct`` ------------------------------------

def _rel(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-300)


def _norm(v) -> float:
    return float(np.linalg.norm(np.asarray(v, np.float64)))


def numbers(outputs: dict, X, y, params: dict, follow_rounds: int,
            reference_run: dict | None = None, binned=None):
    """-> {name: value}; ``outputs`` and ``loss_gap``, ``grad_gap``,
    ``update_gap``, ``margin_gap``, ``rounds_gap`` as ``lib/compare.py``
    defines them, with the walker and the reference of
    ``lib/reference_sparse.py`` (NaN goes each split's stated default
    direction; the reference learns its own), and

        default_dir_gap  over every split node of the window's first
                         followed tree, from the raw rows in float64 and
                         the reference's gradient pairs at the state the
                         window started from: the gain of the stated
                         (feature, threshold) with the missing rows sent
                         the other way, less the gain as stated, over the
                         tree's largest gain, where positive; the largest
                         over the nodes (``reference_sparse.default_dir_gap``)
    """
    trees = outputs["trees"]
    warm = min(int(outputs["warm_rounds"]), len(trees))
    followed = trees[warm:warm + follow_rounds]
    base = np.float32(outputs["base_margin"])
    m = np.full(X.shape[0], base, np.float32)
    for tree in trees[:warm]:
        m = m + rs.walk(tree, X)
    m_start = m
    losses = []
    for tree in followed:
        m = m + rs.walk(tree, X)
        losses.append(rs.logloss(m, y))
    # rounds that are missing compare as the last state there is
    losses += [rs.logloss(m, y)] * (follow_rounds - len(followed))
    change = _norm(m - m_start)
    for tree in trees[warm + follow_rounds:]:
        m = m + rs.walk(tree, X)

    r = reference_run or rs.train(X, y, params, follow_rounds,
                                  start_margin=m_start, binned=binned)
    out = {"loss_gap": float(max(_rel(got, want)
                                 for got, want in zip(losses, r["losses"]))),
           "update_gap": _rel(change, _norm(r["margin"] - m_start))}

    def root_hess(some_trees):
        return float(some_trees[0]["sum_hess"][0]) if some_trees else 0.0
    _, h = rs.gradients(np.full(len(y), np.float32(rs.stump_margin(y))), y,
                        np.asarray)
    out["grad_gap"] = max(
        _rel(root_hess(followed), root_hess(r["trees"])),
        _rel(root_hess(trees), float(h.sum(dtype=np.float64))))
    out["default_dir_gap"] = rs.default_dir_gap(
        followed[0], X, *r["grad"], float(params.get("lambda", 1.0)),
        float(params.get("min_child_weight", 1.0))) if followed \
        else float("inf")

    state = np.asarray(outputs["margin"], np.float32).reshape(-1)
    if state.shape != m.shape or not np.isfinite(state).all():
        out["margin_gap"] = float("inf")
    else:
        out["margin_gap"] = float(np.abs(state - m).max()
                                  / max(float(np.abs(m).max()), 1e-30))
    claimed = outputs["rounds_claimed"]
    out["rounds_gap"] = abs(len(trees) - claimed) / max(claimed, 1)
    return out


def check(ctx, state) -> tuple:
    """The comparison with the plain reference -> (correct, table)."""
    X, y, params = state["inputs"]
    t = time.perf_counter()
    values = numbers(state["outputs"], X, y, params,
                     int(ctx.traffic["follow_rounds"]))
    ok, table = compare.judge(values, ctx.limits)
    check_s = time.perf_counter() - t
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


CASES = ("sound", "control_bf16", "missing_right", "imputed_zero",
         "station_left_out", "half_batch", "state_unchanged")


def control_readings(config: dict, mix: dict, seed: int,
                     cases=CASES) -> dict:
    """The control and the planted faults, read with the reference in the
    program's place on this configuration's data: {case: {number: value}}.
    Every case shares one sound warm-up call and differs in the window's
    first rounds, where the timed path runs:

    sound             the float32 reference itself (reads 0 everywhere)
    control_bf16      the window's rounds with margin, gradient pairs and
                      leaf values held in bfloat16: the nearest precision
                      below float32
    missing_right     every missing value sent right: the cuts are searched
                      soundly, then the learned direction is dropped (stated
                      false, the rows routed right)
    imputed_zero      NaN replaced by 0 before the window's rounds binned
                      their rows; the model still states thresholds, and the
                      walker meets NaN where training saw 0
    station_left_out  the columns of the station the label reads most left
                      out of the window's histograms
    half_batch        the window's rounds trained on the first half of the
                      rows
    state_unchanged   the window's first call returned its state as it got it
    """
    X, y = make_inputs(config, seed)
    params = dict(config["params"])
    follow, rpc = int(mix["follow_rounds"]), int(mix["rounds_per_call"])
    binned = rs.make_binned(X, int(params["max_bin"]))
    warm = rs.train(X, y, params, rpc, binned=binned)

    def window(rows=X, **fault):
        """Outputs of a run: the warm-up, then the window's followed rounds."""
        run = rs.train(rows, y, params, follow, start_margin=warm["margin"],
                       binned=binned if rows is X else None, **fault)
        return run, {
            "trees": warm["trees"] + run["trees"], "warm_rounds": rpc,
            "base_margin": warm["base_margin"], "margin": run["margin"],
            "rounds_claimed": rpc + follow}

    lay = data_sparse.layout(X.shape[1])
    sound_run, sound = window()
    make = {
        "sound": lambda: sound,
        "control_bf16": lambda: window(precision="bfloat16")[1],
        "missing_right": lambda: window(force_right=True)[1],
        "imputed_zero": lambda: window(rows=rs.impute_zero(X))[1],
        "station_left_out": lambda: window(
            skip_features=lay["station_of"] == lay["A"])[1],
        "half_batch": lambda: window(row_limit=X.shape[0] // 2)[1],
        "state_unchanged": lambda: dict(
            sound, trees=warm["trees"], margin=warm["margin"],
            rounds_claimed=2 * rpc),
    }
    return {name: numbers(make[name](), X, y, params, follow,
                          reference_run=sound_run)
            for name in cases}
