"""Traffic driver ``rank_loop``: boosting-round continuation calls of a
learning-to-rank job (``rank:ndcg``, query groups through ``qid``) on one
persistent ``DMatrix`` and one ``Booster`` through the public ``xgb.train``.

The window, ``setup_s`` and ``train_rounds_per_s`` are ``train_loop``'s, word
for word: the window opens after one warm-up call of the cell's own shape,
issues continuation calls while fewer than ``--seconds`` have passed, and
closes when the last call has returned and ``block_until_ready`` on the
booster's margin has returned; ``setup_s`` runs from process start to the
start of the window; ``train_rounds_per_s`` is all rounds completed in the
window over its whole length.

A mix's file gives ``rounds_per_call``, ``trace_calls``, ``follow_rounds``, the
``metric`` read once after the window (``ndcg@10``), ``round_programs`` (the
general path's programs as a trace names them) and ``gradient_program`` (the
one of them that is the objective's gradient).

From the program this file takes ``xgb.DMatrix``, ``xgb.train``,
``Booster.eval``, the model as ``Booster.save_raw("json")`` states it, the
booster's training margin, its objective's ``get_gradient`` (once, after the
window, on the margin the window started from), ``_fused_blocked``,
``degrade_counts()`` and ``rank_counts()``. A program without ``rank_counts``
or the ``rank.*`` scopes cannot show that its gradient ran on the device, and
is refused before any data is made (exit 4).
"""

from __future__ import annotations

import time

import numpy as np

from drivers import train_loop
from drivers.train_loop import model_trees, training_margin
from lib import compare, data_rank
from lib import reference as ref
from lib import reference_rank as rr

METRIC_K = 10


def make_inputs(config: dict, seed: int):
    gen = data_rank.GENERATORS[config["data"]["generator"]]
    return gen(int(config["rows"]), int(config["features"]),
               int(config["groups"]), seed)


def host_pair_counts(ptr, truncation: int, chunk_rule: int = 1 << 24):
    """The benchmark's own count, from the group sizes alone, of what one
    gradient dispatch sweeps and keeps: (pair slots, pairs kept). Slots: the
    groups padded up to whole chunks of ``chunk_rule // L^2`` groups, times
    L x L with L the longest group. Kept: per group of n rows and m = min(k,
    n), m (n - 1) - m (m - 1) / 2 pairs (rank r < m against the n - 1 - r
    rows ranked below it)."""
    n = np.diff(np.asarray(ptr, np.int64))
    G, L = len(n), int(n.max())
    chunk = max(1, min(G, chunk_rule // (L * L)))
    m = n if truncation <= 0 else np.minimum(truncation, n)
    return (-(-G // chunk) * chunk * L * L,
            int(np.sum(m * (n - 1) - m * (m - 1) // 2)))


def require_program():
    """What this driver needs of the program beyond ``train_loop``'s."""
    try:
        from xgboost_tpu.obs.metrics import rank_counts
        from xgboost_tpu.obs.trace import RANK_SCOPES  # noqa: F401
    except ImportError as e:
        import sys

        print("this program has no ranking counters or rank.* scopes "
              f"({e}): the cell cannot show where its gradient ran; nothing "
              "measured", file=sys.stderr, flush=True)
        raise SystemExit(4)
    return rank_counts


def _max_rss_gb() -> float:
    """The process's peak resident set: the machine's 40 GiB of host memory
    is the tight resource of this cell (PERF.md section 4)."""
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def measure(ctx) -> dict:
    """Set-up, then the window. Returns the state ``check`` reads, with the
    program's outputs on the host and the program's objects dropped."""
    rank_counts = require_program()
    import jax
    import jax.numpy as jnp

    import xgboost_tpu as xgb
    from xgboost_tpu.obs.metrics import degrade_counts

    config, mix = ctx.config, ctx.traffic
    params = dict(config["params"])
    params["seed"] = ctx.seed % (2 ** 31 - 1)
    params["eval_metric"] = mix["metric"]
    rpc = int(mix["rounds_per_call"])

    t = time.perf_counter()
    X, y, ptr = make_inputs(config, ctx.seed)
    data_s = time.perf_counter() - t

    t = time.perf_counter()
    qid = np.repeat(np.arange(len(ptr) - 1, dtype=np.int32), np.diff(ptr))
    dtrain = xgb.DMatrix(X, label=y, qid=qid)
    binned = dtrain.binned(int(params["max_bin"]))
    np.asarray(binned.bins[:1])                  # upload finished
    ingest_s = time.perf_counter() - t
    ctx.say(f"data {data_s:.2f}s, ingest {ingest_s:.2f}s ({X.shape[0]} x "
            f"{X.shape[1]}, {len(ptr) - 1} groups of {np.diff(ptr).min()} to "
            f"{np.diff(ptr).max()} rows)")

    def call(bst):
        # through the module, so that a test can break it from underneath
        bst = train_loop.train_call(xgb, params, dtrain, rpc, bst, [], {})
        jax.block_until_ready(training_margin(bst, dtrain))
        return bst

    # warm-up: one call of the cell's own shape, on the objects the window uses
    t = time.perf_counter()
    bst = call(None)
    warm_s = time.perf_counter() - t
    # the state the window starts from, kept on the host for the comparison
    margin_start = np.asarray(training_margin(bst, dtrain),
                              np.float32).reshape(-1)
    counts0 = rank_counts()
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
    counts1 = rank_counts()

    margin = np.asarray(training_margin(bst, dtrain), np.float32).reshape(-1)
    if not np.isfinite(margin).all():
        failed = max(failed, 1)
    degrades = degrade_counts()
    if bst._fused_blocked or any(degrades.values()):
        ctx.say(f"degraded: _fused_blocked={bst._fused_blocked} {degrades}")
        failed = max(failed, 1)
    # every round's gradient ran on the device, by the pair method stated
    method = str(params["lambdarank_pair_method"])
    dispatched = (counts1["dispatches"].get(method, 0)
                  - counts0["dispatches"].get(method, 0))
    rank = {"pair_slots": counts1["pair_slots"] - counts0["pair_slots"],
            "pairs_kept": counts1["pairs_kept"] - counts0["pairs_kept"],
            "fill_ratio": counts1["fill_ratio"], "dispatches": dispatched}
    slots, kept = host_pair_counts(
        ptr, int(params["lambdarank_num_pair_per_sample"]))
    counted = (rank["pair_slots"] == rounds * slots
               and rank["pairs_kept"] == rounds * kept)
    ctx.say(f"rank counters over the window: {rank}; a dispatch by the "
            f"group sizes: {slots} slots, {kept} pairs kept "
            f"({100.0 * kept / slots:.6f}%); equal to the program's: "
            f"{counted}; device gradient dispatches {dispatched} for "
            f"{rounds} rounds")
    if dispatched != rounds:
        ctx.say("a round's gradient did not run through the device path")
        failed = max(failed, 1)

    # outside the window: the program's gradient from the state the window
    # started from, and its metric on the state it ended in
    t = time.perf_counter()
    info = bst._state_of(dtrain, is_train=True)["info"]
    grad_start = np.asarray(bst.obj.get_gradient(
        jnp.asarray(margin_start)[:, None], info, rounds0),
        np.float32)[:, 0, :]
    metric = float(bst.eval(dtrain).split(":")[-1])
    after_s = time.perf_counter() - t
    t = time.perf_counter()
    trees, base = model_trees(bst)
    flush_s = time.perf_counter() - t
    compiles_in_window = clock1["compiles"] - clock0["compiles"]
    ctx.say(f"window {window_s:.3f}s: {len(calls)} calls, {rounds} rounds, "
            f"calls s {[round(c, 3) for c in calls]}, compilations inside "
            f"the window {compiles_in_window}, tree flush {flush_s:.2f}s, "
            f"gradient pull and {mix['metric']} after it {after_s:.2f}s, "
            f"host RSS peak so far {_max_rss_gb():.2f} GB")
    del bst, dtrain, binned, info                # the program's state goes

    return {
        "end_to_end": {"setup_s": setup_s,
                       "train_rounds_per_s": rounds / window_s},
        "attempted": len(calls), "failed": failed,
        "memory_peak_bytes": memory_peak,
        "facts": {"ingest_s": ingest_s, "data_s": data_s, "warm_s": warm_s,
                  "compile_s": clock0["compile_s"], "setup_clock": clock0,
                  "compiles_in_window": compiles_in_window,
                  "call_s": calls, "window_s": window_s, "rounds": rounds,
                  "rounds_per_call": rpc, "rank": rank,
                  "gradient_program": mix["gradient_program"],
                  "round_programs": list(mix["round_programs"])},
        "outputs": {"trees": trees, "base_margin": base, "margin": margin,
                    "grad_start": grad_start, "metric": metric,
                    "rounds_claimed": claimed, "warm_rounds": rpc},
        "inputs": (X, y, ptr, params),
    }


# ---- the numbers that decide ``correct`` ------------------------------------

def _rel(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-300)


def _norm(v) -> float:
    return float(np.linalg.norm(np.asarray(v, np.float64)))


def leaf_gap(tree, X, grad, params) -> float:
    """The per-row update a tree implies against the gradient pairs it should
    have been grown from: each row's leaf value against ``-eta G / (H +
    lambda)`` with G, H the sums of ``grad`` over the rows the tree sends to
    that leaf; the norm of the difference over the rows, over the norm of
    what the sums imply. The splits are the tree's own."""
    ids = np.arange(len(tree["left"]), dtype=np.float32)
    node = ref.walk(dict(tree, value=ids), X).astype(np.int64)
    G = np.bincount(node, weights=grad[:, 0].astype(np.float64),
                    minlength=len(ids))
    H = np.bincount(node, weights=grad[:, 1].astype(np.float64),
                    minlength=len(ids))
    rows = np.bincount(node, minlength=len(ids)).astype(np.float64)
    want = -float(params["eta"]) * G / (H + float(params.get("lambda", 1.0)))
    got = np.asarray(tree["value"], np.float64)
    return float(np.sqrt(np.sum(rows * (got - want) ** 2)
                         / max(np.sum(rows * want ** 2), 1e-300)))


def numbers(outputs: dict, X, y, ptr, params: dict, follow_rounds: int,
            reference_run: dict | None = None, binned=None,
            detail: dict | None = None):
    """-> {name: value}; ``outputs`` as ``lib/compare.py`` describes them,
    plus ``grad_start`` ([n, 2]: the program's gradient pairs from the state
    the window started from) and ``metric`` (its ``ndcg@10`` of the state the
    window ended in).

        grad_gap    the worst of four readings of the reference's gradient
                    pairs from the state the window started from (its walker
                    carries the warm-up's trees there): the program's own
                    gradient pairs from that state, gradients and hessians
                    each as the norm of the difference over the reference's
                    norm; the window's first tree's root sum-hessian against
                    their sum of hessians; and ``leaf_gap`` of that tree
                    against them (the per-row update it implies)
        update_gap  gap of the norms of the margin's change over the followed
                    rounds, the program's trees (walked) against the
                    reference's
        ndcg_gap    max over the followed rounds of the relative gap between
                    training ``ndcg@10`` under the program's trees and under
                    the reference's
        margin_gap  max gap between the booster's own margin when the window
                    closed and the walk of all its trees, over the largest
                    margin
        metric_gap  the program's ``ndcg@10`` against ``ndcg_at`` of the
                    booster's own margin
        rounds_gap  trees stated against calls x rounds a call; exact
    """
    trees = outputs["trees"]
    warm = min(int(outputs["warm_rounds"]), len(trees))
    followed = trees[warm:warm + follow_rounds]
    m = np.full(X.shape[0], np.float32(outputs["base_margin"]), np.float32)
    for tree in trees[:warm]:
        m = m + ref.walk(tree, X)
    m_start = m
    ndcgs = []
    for tree in followed:
        m = m + ref.walk(tree, X)
        ndcgs.append(rr.ndcg_at(m, y, ptr, METRIC_K))
    # rounds that are missing compare as the last state there is
    ndcgs += [rr.ndcg_at(m, y, ptr, METRIC_K)] * (follow_rounds
                                                 - len(followed))
    change = _norm(m - m_start)
    for tree in trees[warm + follow_rounds:]:
        m = m + ref.walk(tree, X)

    r = reference_run or rr.train(X, y, ptr, params, follow_rounds,
                                  start_margin=m_start, binned=binned)
    g_ref = r["grad"]
    out = {"update_gap": _rel(change, _norm(r["margin"] - m_start)),
           "ndcg_gap": float(max(_rel(got, want)
                                 for got, want in zip(ndcgs, r["ndcgs"])))}
    got = np.asarray(outputs["grad_start"], np.float32)
    parts = {"rows": float("inf")} if got.shape != g_ref.shape else {
        "row_gradients": _norm(got[:, 0] - g_ref[:, 0]) / _norm(g_ref[:, 0]),
        "row_hessians": _norm(got[:, 1] - g_ref[:, 1]) / _norm(g_ref[:, 1])}
    if followed:
        parts["root_hessian"] = _rel(
            float(followed[0]["sum_hess"][0]),
            float(g_ref[:, 1].sum(dtype=np.float64)))
        parts["leaves"] = leaf_gap(followed[0], X, g_ref, params)
    else:
        parts["no_tree"] = 1.0               # no tree to hold against it
    out["grad_gap"] = float(max(parts.values()))
    if detail is not None:
        detail["grad_gap"] = parts

    state = np.asarray(outputs["margin"], np.float32).reshape(-1)
    if state.shape != m.shape or not np.isfinite(state).all():
        out["margin_gap"] = out["metric_gap"] = float("inf")
    else:
        out["margin_gap"] = float(np.abs(state - m).max()
                                  / max(float(np.abs(m).max()), 1e-30))
        out["metric_gap"] = _rel(float(outputs["metric"]),
                                 rr.ndcg_at(state, y, ptr, METRIC_K))
    claimed = outputs["rounds_claimed"]
    out["rounds_gap"] = abs(len(trees) - claimed) / max(claimed, 1)
    return out


def check(ctx, state) -> tuple:
    """The comparison with the plain reference -> (correct, table)."""
    X, y, ptr, params = state["inputs"]
    t = time.perf_counter()
    detail: dict = {}
    values = numbers(state["outputs"], X, y, ptr, params,
                     int(ctx.traffic["follow_rounds"]), detail=detail)
    ok, table = compare.judge(values, ctx.limits)
    ctx.say("grad_gap is the worst of "
            + ", ".join(f"{k} {v:.3g}" for k, v in detail["grad_gap"].items()))
    ctx.say(f"reference and comparison {time.perf_counter() - t:.2f}s, host "
            f"RSS peak {_max_rss_gb():.2f} GB")
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
    half_queries     the window's rounds took gradients from the first half of
                     the queries only
    state_unchanged  the window's first call returned its state as it got it
    stale_margin     the window's rounds took their gradients from the margin
                     the warm-up started with: they boost its first trees again
    groups_shifted   every inner group boundary one row late, in the window's
                     gradients and in the metric
    all_pairs        the truncation ignored: every pair of a query counted
    """
    X, y, ptr = make_inputs(config, seed)
    params = dict(config["params"])
    follow, rpc = int(mix["follow_rounds"]), int(mix["rounds_per_call"])
    binned = rr.make_binned(X, params)
    warm = rr.train(X, y, ptr, params, rpc, binned=binned)
    shifted = np.asarray(ptr).copy()
    shifted[1:-1] += 1

    def window(metric_ptr=ptr, **fault):
        """Outputs of a run: the warm-up, then the window's followed rounds."""
        run = rr.train(X, y, ptr, params, follow, start_margin=warm["margin"],
                       binned=binned, **fault)
        return run, {
            "trees": warm["trees"] + run["trees"], "warm_rounds": rpc,
            "base_margin": 0.0, "margin": run["margin"],
            "grad_start": run["grad"], "rounds_claimed": rpc + follow,
            "metric": rr.ndcg_at(run["margin"], y, metric_ptr, METRIC_K)}

    sound_run, sound = window()
    again = warm["trees"][:follow]
    stale = warm["margin"] + sum(ref.walk(t, X) for t in again)
    cases = {
        "sound": sound,
        "control_bf16": window(precision="bfloat16")[1],
        "half_queries": window(query_limit=(len(ptr) - 1) // 2)[1],
        "state_unchanged": dict(
            sound, trees=warm["trees"], margin=warm["margin"],
            rounds_claimed=2 * rpc,
            metric=rr.ndcg_at(warm["margin"], y, ptr, METRIC_K)),
        "stale_margin": dict(
            sound, trees=warm["trees"] + again, margin=stale,
            grad_start=warm["grad"],
            metric=rr.ndcg_at(stale, y, ptr, METRIC_K)),
        "groups_shifted": window(metric_ptr=shifted, grad_ptr=shifted)[1],
        "all_pairs": window(truncation=0)[1],
    }
    return {name: numbers(out, X, y, ptr, params, follow,
                          reference_run=sound_run)
            for name, out in cases.items()}
