"""Traffic driver ``train_loop``: boosting-round continuation calls on one
persistent ``DMatrix`` and one ``Booster`` through the public ``xgb.train``.

A mix's file gives ``rounds_per_call``, whether an eval set rides along
(``evals``: the held-out rows, ``eval_metric``), how many calls a traced run
profiles (``trace_calls``), how many of the window's first rounds the
reference follows (``follow_rounds``) and which XLA programs are round
programs. The harness pins no ``hist_method`` and sets no switch of the
program but those the configuration states under ``program_env`` (``run.py``
applies them).

From the program this file takes ``xgb.DMatrix``, ``xgb.train``, the model as
``Booster.save_raw("json")`` states it, the booster's training margin (the
state the window carries), ``_fused_blocked`` and ``degrade_counts()``.
"""

from __future__ import annotations

import json
import time

import numpy as np

from lib import compare, data
from lib import reference as ref


def train_call(xgb, params, dtrain, rounds, bst, evals, sink):
    """One call of the timed path. Tests break it from underneath."""
    kw = {"xgb_model": bst} if bst is not None else {}
    if evals:
        kw.update(evals=evals, evals_result=sink)
    return xgb.train(params, dtrain, num_boost_round=rounds,
                     verbose_eval=False, **kw)


def training_margin(bst, dtrain):
    """The booster's own margin over the training rows, on the device."""
    return bst._state_of(dtrain, is_train=True)["margin"]


def model_trees(bst):
    """The model as the program states it, as arrays for the walker. A leaf's
    value sits in ``split_conditions`` (XGBoost's JSON schema)."""
    model = json.loads(bytes(bst.save_raw("json")))["learner"]
    trees = [{"left": np.asarray(t["left_children"], np.int64),
              "right": np.asarray(t["right_children"], np.int64),
              "feat": np.asarray(t["split_indices"], np.int64),
              "thr": np.asarray(t["split_conditions"], np.float32),
              "value": np.asarray(t["split_conditions"], np.float32),
              "sum_hess": np.asarray(t["sum_hessian"], np.float64)}
             for t in model["gradient_booster"]["trees"]]
    return trees, float(model["learner_model_param"]["base_score"][0])


def make_inputs(config: dict, seed: int):
    gen = data.GENERATORS[config["data"]["generator"]]
    X, y = gen(int(config["rows"]), int(config["features"]), seed, stream=0)
    held = int(config.get("held_out_rows", 0))
    Xe, ye = gen(held, int(config["features"]), seed, stream=1) if held \
        else (None, None)
    return X, y, Xe, ye


def measure(ctx) -> dict:
    """Set-up, then the window. Returns the state ``check`` reads, with the
    program's outputs on the host and the program's objects dropped."""
    import jax

    import xgboost_tpu as xgb
    from xgboost_tpu.obs.metrics import degrade_counts

    config, mix = ctx.config, ctx.traffic
    params = dict(config["params"])
    params["seed"] = ctx.seed % (2 ** 31 - 1)
    rpc = int(mix["rounds_per_call"])
    with_eval = bool(mix.get("evals"))
    if with_eval:
        params["eval_metric"] = mix["eval_metric"]

    t = time.perf_counter()
    X, y, Xe, ye = make_inputs(config, ctx.seed)
    data_s = time.perf_counter() - t
    if not with_eval:
        Xe = ye = None

    t = time.perf_counter()
    dtrain = xgb.DMatrix(X, label=y)
    binned = dtrain.binned(int(params["max_bin"]))
    np.asarray(binned.bins[:1])                  # upload finished
    evals = []
    if with_eval:
        evals = [(xgb.DMatrix(Xe, label=ye), mix["evals"])]
    ingest_s = time.perf_counter() - t
    ctx.say(f"data {data_s:.2f}s, ingest {ingest_s:.2f}s "
            f"({X.shape[0]} x {X.shape[1]}, held out "
            f"{0 if Xe is None else Xe.shape[0]})")

    eval_log: list = []

    def call(bst):
        sink: dict = {}
        bst = train_call(xgb, params, dtrain, rpc, bst, evals, sink)
        jax.block_until_ready(training_margin(bst, dtrain))
        if with_eval:
            eval_log.extend(sink[mix["evals"]][mix["eval_metric"]])
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
    t = time.perf_counter()
    trees, base = model_trees(bst)
    flush_s = time.perf_counter() - t
    compiles_in_window = clock1["compiles"] - clock0["compiles"]
    ctx.say(f"window {window_s:.3f}s: {len(calls)} calls, {rounds} rounds, "
            f"calls s {[round(c, 3) for c in calls]}, compilations inside "
            f"the window {compiles_in_window}, tree flush {flush_s:.2f}s")
    del bst, dtrain, binned, evals               # the program's state goes

    return {
        "end_to_end": {"setup_s": setup_s,
                       "train_rounds_per_s": rounds / window_s},
        "attempted": len(calls), "failed": failed,
        "memory_peak_bytes": memory_peak,
        "facts": {"ingest_s": ingest_s, "data_s": data_s, "warm_s": warm_s,
                  "compile_s": clock0["compile_s"], "setup_clock": clock0,
                  "compiles_in_window": compiles_in_window,
                  "call_s": calls, "window_s": window_s, "rounds": rounds,
                  "rounds_per_call": rpc,
                  "round_programs": list(mix["round_programs"])},
        "outputs": {"trees": trees, "base_margin": base, "margin": margin,
                    "eval_losses": eval_log if with_eval else None,
                    "rounds_claimed": claimed, "warm_rounds": rpc},
        "inputs": (X, y, Xe, ye, params),
    }


def check(ctx, state) -> tuple:
    """The comparison with the plain reference -> (correct, table)."""
    X, y, Xe, ye, params = state["inputs"]
    t = time.perf_counter()
    values = compare.numbers(state["outputs"], X, y, params,
                             int(ctx.traffic["follow_rounds"]),
                             X_eval=Xe, y_eval=ye)
    ok, table = compare.judge(values, ctx.limits)
    ctx.say(f"reference and comparison {time.perf_counter() - t:.2f}s")
    return ok and state["failed"] == 0, table


def control_readings(config: dict, mix: dict, seed: int) -> dict:
    """The control and the planted faults, read with the reference in the
    program's place on this configuration's data: {case: {number: value}}.
    Every case shares one sound warm-up call and differs in the window's
    first rounds, where the timed path runs:

    sound            the float32 reference itself (reads 0 everywhere)
    control_bf16     the window's rounds with margin, gradient pairs and leaf
                     values held in bfloat16: the nearest precision below
                     float32
    half_batch       the window's rounds trained on the first half of the rows
    state_unchanged  the window's first call returned its state as it got it
    stale_margin     the window's rounds took their gradients from the margin
                     the warm-up started with: they boost its first trees again
    eval_stale       (mixes with an eval set) the eval program returned its
                     margin unchanged for the window's first round: every eval
                     loss of the window is the one of the round before
    """
    X, y, Xe, ye = make_inputs(config, seed)
    if not mix.get("evals"):
        Xe = ye = None
    params = dict(config["params"])
    follow, rpc = int(mix["follow_rounds"]), int(mix["rounds_per_call"])
    warm = ref.train(X, y, params, rpc, X_eval=Xe, y_eval=ye)

    def window(**fault):
        """Outputs of a run: the warm-up, then the window's followed rounds."""
        run = ref.train(X, y, params, follow, X_eval=Xe, y_eval=ye,
                        start_margin=warm["margin"],
                        start_eval_margin=warm.get("eval_margin"), **fault)
        return run, {
            "trees": warm["trees"] + run["trees"], "warm_rounds": rpc,
            "base_margin": warm["base_margin"], "margin": run["margin"],
            "rounds_claimed": rpc + follow,
            "eval_losses": None if Xe is None
            else warm["eval_losses"] + run["eval_losses"]}

    sound_run, sound = window()
    cases = {
        "sound": sound,
        "control_bf16": window(precision="bfloat16")[1],
        "half_batch": window(row_limit=X.shape[0] // 2)[1],
        "state_unchanged": dict(
            sound, trees=warm["trees"], margin=warm["margin"],
            rounds_claimed=2 * rpc,
            eval_losses=None if Xe is None else warm["eval_losses"]),
    }
    again = warm["trees"][:follow]
    cases["stale_margin"] = dict(
        sound, trees=warm["trees"] + again,
        margin=warm["margin"] + sum(ref.walk(t, X) for t in again),
        eval_losses=None if Xe is None else warm["eval_losses"] + [
            ref.logloss(warm["eval_margin"] + sum(
                ref.walk(t, Xe) for t in again[:i + 1]), ye)
            for i in range(len(again))])
    if Xe is not None:
        cases["eval_stale"] = dict(sound, eval_losses=(
            warm["eval_losses"] + warm["eval_losses"][-1:]
            + sound_run["eval_losses"][:-1]))
    return {name: compare.numbers(out, X, y, params, follow, X_eval=Xe,
                                  y_eval=ye, reference_run=sound_run)
            for name, out in cases.items()}
