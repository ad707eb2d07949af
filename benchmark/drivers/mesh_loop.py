"""Traffic driver ``mesh_loop``: boosting-round continuation calls of one
data-parallel job over a host's chips: one ``QuantileDMatrix`` built from a
``DataIter`` of row batches, row-sharded over ``xgb.make_data_mesh()``, and
one ``Booster``, through the public ``xgb.train``.

The window, ``setup_s`` and ``train_rounds_per_s`` are ``train_loop``'s, word
for word: the window opens after one warm-up call of the cell's own shape,
issues continuation calls while fewer than ``--seconds`` have passed, and
closes when the last call has returned and ``block_until_ready`` on the
booster's margin has returned; ``setup_s`` runs from process start to the
start of the window; ``train_rounds_per_s`` is all rounds completed in the
window over its whole length.

The configuration's ``rows`` are the rows ONE chip holds (``lib/work.py``
divides them by one chip's HBM peak); ``job_rows`` = ``rows`` x
``chips_sharing`` is what the iterator hands over. The raw matrix is never
whole on the host: the iterator makes ``batch_blocks`` generator blocks a
batch (``lib/data_ctr.py``: block b depends on (seed, stream, b) alone) and
the comparison regenerates them block by block (``lib/reference_blocks.py``).

A mix's file gives ``rounds_per_call``, ``trace_calls``, ``follow_rounds``,
``batch_blocks``, ``stride_blocks`` (the comparison walks every tree and
searches the top splits on one block in so many), ``top_levels`` and
``round_programs``.

From the program this file takes ``xgb.DataIter``, ``xgb.QuantileDMatrix``,
``xgb.make_data_mesh``, ``xgb.train``, the model as
``Booster.save_raw("json")`` states it, the booster's training margin, the
last round's tree as each chip holds it (``gbm._trees[-1].arrays``),
``_fused_blocked``, ``degrade_counts()`` and ``mesh_counts()``. A program
without ``mesh_counts`` or the ``mesh.*`` scopes cannot show that its
collectives ran, and is refused before any data is made (exit 4).
"""

from __future__ import annotations

import json
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from drivers import train_loop
from drivers.train_loop import training_margin
from lib import compare, data_ctr
from lib import reference_blocks as rb


def require_program():
    """What this driver needs of the program beyond ``train_loop``'s."""
    try:
        from xgboost_tpu.obs.metrics import mesh_counts
        from xgboost_tpu.obs.trace import MESH_SCOPES  # noqa: F401
    except ImportError as e:
        import sys

        print("this program has no mesh counters or mesh.* scopes "
              f"({e}): the cell cannot show that its collectives ran; "
              "nothing measured", file=sys.stderr, flush=True)
        raise SystemExit(4)
    return mesh_counts


def _max_rss_gb() -> float:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def block_iter(xgb, seed: int, job_rows: int, batch_blocks: int):
    """A ``DataIter`` over the seeded stream: ``batch_blocks`` generator
    blocks a batch, the next batch made on a thread while the program works
    on this one. ``.wait_s`` sums how long ``next`` waited for data."""

    class BlockIter(xgb.DataIter):
        def __init__(self):
            super().__init__()
            self.total = data_ctr.n_blocks(job_rows)
            self.pool = ThreadPoolExecutor(1, thread_name_prefix="bench-it")
            self.wait_s = 0.0
            self.batches = 0
            self.reset()

        def _make(self, first):
            if first >= self.total:
                return None
            return self.pool.submit(
                data_ctr.blocks, seed, 0, first,
                min(batch_blocks, self.total - first), job_rows)

        def reset(self):
            self.first = 0
            self.pending = self._make(0)

        def next(self, input_data):
            if self.pending is None:
                return 0
            t = time.perf_counter()
            X, y = self.pending.result()
            self.wait_s += time.perf_counter() - t
            self.first += batch_blocks
            self.pending = self._make(self.first)
            self.batches += 1
            input_data(data=X, label=y)
            return 1

    return BlockIter()


def model_trees(bst):
    """The model as the program states it, as arrays for the walker, with
    each split's stated gain (``loss_changes``) besides ``train_loop``'s."""
    model = json.loads(bytes(bst.save_raw("json")))["learner"]
    trees = [{"left": np.asarray(t["left_children"], np.int64),
              "right": np.asarray(t["right_children"], np.int64),
              "feat": np.asarray(t["split_indices"], np.int64),
              "thr": np.asarray(t["split_conditions"], np.float32),
              "value": np.asarray(t["split_conditions"], np.float32),
              "sum_hess": np.asarray(t["sum_hessian"], np.float64),
              "gain": np.asarray(t["loss_changes"], np.float64)}
             for t in model["gradient_booster"]["trees"]]
    return trees, float(model["learner_model_param"]["base_score"][0])


def replica_gap(bst) -> float:
    """The share of the newest tree's arrays that differ between the chips'
    copies (the grow program states its tree replicated: every chip must
    hold the same bytes). 0 where every copy equals the first; 1.0 where
    there is no tree on the devices to read."""
    tree = bst.gbm._trees[-1] if bst.gbm._trees else None
    arrays = getattr(tree, "arrays", None)
    if not arrays:
        return 1.0
    differing = 0
    for arr in arrays.values():
        copies = [np.asarray(s.data) for s in arr.addressable_shards]
        differing += any(c.tobytes() != copies[0].tobytes()
                         for c in copies[1:])
    return differing / len(arrays)


def measure(ctx) -> dict:
    """Set-up, then the window. Returns the state ``check`` reads, with the
    program's outputs on the host and the program's objects dropped."""
    mesh_counts = require_program()
    import jax

    import xgboost_tpu as xgb
    from xgboost_tpu.obs.metrics import degrade_counts

    config, mix = ctx.config, ctx.traffic
    params = dict(config["params"])
    params["seed"] = ctx.seed % (2 ** 31 - 1)
    rpc = int(mix["rounds_per_call"])
    chips = int(config["chips_sharing"])
    job_rows = int(config["job_rows"])
    if chips != ctx.chips or job_rows != chips * int(config["rows"]):
        raise SystemExit(f"the configuration shares {job_rows} rows over "
                         f"{chips} chips of {config['rows']}; the cell asks "
                         f"for {ctx.chips} chips")

    t = time.perf_counter()
    it = block_iter(xgb, ctx.seed, job_rows, int(mix["batch_blocks"]))
    dtrain = xgb.QuantileDMatrix(it, max_bin=int(params["max_bin"]))
    data_s = it.wait_s
    ingest_s = time.perf_counter() - t - data_s
    it.pool.shutdown()
    ctx.say(f"waited for data {data_s:.2f}s, sketch and bin {ingest_s:.2f}s "
            f"({dtrain.num_row()} x {dtrain.num_col()} in {it.batches} "
            f"batches over two passes), host RSS peak {_max_rss_gb():.2f} GB")
    params["mesh"] = xgb.make_data_mesh(chips)

    def call(bst):
        # through the module, so that a test can break it from underneath
        bst = train_loop.train_call(xgb, params, dtrain, rpc, bst, [], {})
        jax.block_until_ready(training_margin(bst, dtrain))
        return bst

    # warm-up: one call of the cell's own shape, on the objects the window
    # uses; it places the bin matrix on its shards first
    t = time.perf_counter()
    bst = call(None)
    warm_s = time.perf_counter() - t
    counts0 = mesh_counts()
    clock0 = ctx.compile_clock.snapshot()
    setup_s = time.perf_counter() - ctx.t_start
    bins = bst._state_of(dtrain, is_train=True)["binned"].bins
    shard_rows = sorted({int(s.data.shape[0])
                         for s in bins.addressable_shards})
    ctx.say(f"warm-up call {warm_s:.2f}s, compile {clock0}, bins "
            f"{bins.shape} {bins.dtype} in shards of {shard_rows} rows on "
            f"{len({s.device for s in bins.addressable_shards})} devices")

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
    counts1 = mesh_counts()

    t = time.perf_counter()
    margin = np.asarray(training_margin(bst, dtrain), np.float32).reshape(-1)
    pull_s = time.perf_counter() - t
    if not np.isfinite(margin).all():
        failed = max(failed, 1)
    degrades = degrade_counts()
    if bst._fused_blocked or any(degrades.values()):
        ctx.say(f"degraded: _fused_blocked={bst._fused_blocked} {degrades}")
        failed = max(failed, 1)
    # the mesh as the program laid it out, and its collectives by the rounds
    mesh = {"shards": counts1["shards"],
            "rows_per_shard": counts1["rows_per_shard"],
            "allreduce": {k: v - counts0["allreduce"].get(k, 0)
                          for k, v in counts1["allreduce"].items()},
            "bytes": {k: v - counts0["bytes"].get(k, 0)
                      for k, v in counts1["bytes"].items()}}
    ctx.say(f"mesh counters over the window: {mesh} for {rounds} rounds")
    if (mesh["shards"] != chips or len(shard_rows) != 1
            or shard_rows[0] * chips != bins.shape[0]
            or mesh["rows_per_shard"] != shard_rows[0]):
        ctx.say(f"the rows are not laid out {chips} ways: shards of "
                f"{shard_rows} rows, counters {mesh}")
        failed = max(failed, 1)
    if (mesh["allreduce"].get("root_psum", 0) != rounds
            or mesh["allreduce"].get("hist_psum", 0)
            < rounds * int(params["max_depth"])
            or mesh["allreduce"].get("unscoped", 0)):
        ctx.say("the collectives dispatched do not match the rounds: one "
                "root_psum a round, a hist_psum or more a level, none "
                "outside a mesh.* scope")
        failed = max(failed, 1)
    replicas = replica_gap(bst)
    t = time.perf_counter()
    trees, base = model_trees(bst)
    flush_s = time.perf_counter() - t
    compiles_in_window = clock1["compiles"] - clock0["compiles"]
    ctx.say(f"window {window_s:.3f}s: {len(calls)} calls, {rounds} rounds, "
            f"calls s {[round(c, 3) for c in calls]}, compilations inside "
            f"the window {compiles_in_window}, margin pull {pull_s:.2f}s, "
            f"tree flush {flush_s:.2f}s, host RSS peak {_max_rss_gb():.2f} GB")
    del bst, dtrain, bins, it                    # the program's state goes

    return {
        "end_to_end": {"setup_s": setup_s,
                       "train_rounds_per_s": rounds / window_s},
        "attempted": len(calls), "failed": failed,
        "memory_peak_bytes": memory_peak,
        "facts": {"ingest_s": ingest_s, "data_s": data_s, "warm_s": warm_s,
                  "compile_s": clock0["compile_s"], "setup_clock": clock0,
                  "compiles_in_window": compiles_in_window,
                  "call_s": calls, "window_s": window_s, "rounds": rounds,
                  "rounds_per_call": rpc, "mesh": mesh, "chips": chips,
                  "round_programs": list(mix["round_programs"])},
        "outputs": {"trees": trees, "base_margin": base, "margin": margin,
                    "replica_gap": replicas,
                    "rounds_claimed": claimed, "warm_rounds": rpc},
        "inputs": (ctx.seed, job_rows, params),
    }


def check(ctx, state) -> tuple:
    """The comparison with the blockwise reference -> (correct, table)."""
    seed, job_rows, params = state["inputs"]
    mix = ctx.traffic
    t = time.perf_counter()
    detail: dict = {}
    values = rb.numbers(
        state["outputs"], rb.Source(data_ctr.block, data_ctr.BLOCK, seed, 0,
                                    job_rows),
        params, int(mix["follow_rounds"]), int(mix["stride_blocks"]),
        int(mix["top_levels"]), detail=detail)
    ok, table = compare.judge(values, ctx.limits)
    ctx.say("the comparison in detail: " + json.dumps(detail))
    ctx.say(f"reference and comparison {time.perf_counter() - t:.2f}s, host "
            f"RSS peak {_max_rss_gb():.2f} GB")
    return ok and state["failed"] == 0, table


def control_readings(config: dict, mix: dict, seed: int) -> dict:
    """The control and the planted faults, read with the blockwise reference
    in the program's place on this configuration's data:
    ``{case: {number: value}}`` (``lib/reference_blocks.py
    control_outputs`` lists the cases)."""
    params = dict(config["params"])
    source = rb.Source(data_ctr.block, data_ctr.BLOCK, seed, 0,
                       int(config["job_rows"]))
    follow, rpc = int(mix["follow_rounds"]), int(mix["rounds_per_call"])
    cases = rb.control_outputs(source, params, rpc, follow,
                               shards=int(config["chips_sharing"]))
    return {name: rb.numbers(out, source, params, follow,
                             int(mix["stride_blocks"]),
                             int(mix["top_levels"]))
            for name, out in cases.items()}
