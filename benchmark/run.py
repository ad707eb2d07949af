#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. Fails unless jax's default backend is a TPU with the chips the
cell asks for (``--rehearse`` allows the CPU at the configuration's tiny
rehearsal size and then reports ``platform: "cpu"``, so it can never pass for
a chip line). The last line of stdout is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, and last ``compared``: each number compared
beside its limit. Everything else goes on earlier lines and on stderr.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import glob              # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import math              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import manifest as mf          # noqa: E402


class Ctx:
    """What a driver gets: the cell's files, the run's arguments, and the
    harness's services (tracing, memory reading, the compile clock)."""

    def __init__(self, args, cell, platform):
        self.t_start = T_START
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.limits = cell["limits"]
        self.chips = int(cell["entry"]["chips"])
        self.platform = platform
        self.out_dir = os.path.join(ROOT, "benchmark_out")
        self.trace_dir = os.path.join(self.out_dir, "trace")
        self.traced_rounds = 0
        self._span = None
        from lib.compile_clock import CompileClock
        self.compile_clock = CompileClock()

    def say(self, msg: str) -> None:
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    def start_trace(self) -> None:
        import jax
        from lib.trace_reduce import WINDOW_SPAN

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the runtime's host events are kept
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop_trace(self, rounds: int) -> None:
        import jax

        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.traced_rounds = rounds

    def read_memory_peak(self) -> int:
        """Peak bytes held on the fullest chip. The TPU runtime counts live
        buffers under ``peak_bytes_in_use`` and the temporaries of loaded
        executables apart, as a reservation (``peak_bytes_reserved``): both
        are HBM nobody else can use, so the peak is their sum (PERF.md)."""
        import jax

        peaks = []
        for d in jax.local_devices()[:self.chips]:
            stats = d.memory_stats() or {}
            self.say(f"memory_stats {d}: {stats}")
            peaks.append(int(stats.get("peak_bytes_in_use", 0))
                         + int(stats.get("peak_bytes_reserved", 0)))
        if self.rehearse and not max(peaks):
            import resource          # XLA:CPU reports no device memory

            return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        return max(peaks)


def read_trace(ctx, facts, dump_dir):
    from lib import trace_reduce as tr

    found = glob.glob(os.path.join(ctx.trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise SystemExit(f"expected one .xplane.pb under {ctx.trace_dir}, "
                         f"found {found}")
    planes = tr.load_xplane(found[0])
    if dump_dir:
        os.makedirs(dump_dir, exist_ok=True)
        with open(os.path.join(dump_dir, "trace_described.txt"), "w") as fh:
            fh.write(tr.describe(planes, per_line=40))
        tr.save_fixture(tr.cut_down(planes, ctx.platform),
                        os.path.join(dump_dir, "trace_cut.json.gz"))
    busy_s, window_s = tr.busy_and_window(planes, ctx.platform, ctx.chips)
    if not (0 < busy_s <= window_s):
        raise SystemExit(f"trace reduction gave busy_s={busy_s}, window_s="
                         f"{window_s}: not 0 < busy_s <= window_s; no line "
                         "printed rather than one the driver refuses")
    programs = tr.program_seconds(planes, ctx.platform)
    ops = tr.op_self_seconds(planes, ctx.platform)
    facts["trace"] = {"busy_s": busy_s, "window_s": window_s,
                      "rounds": ctx.traced_rounds, "programs": programs,
                      "op_self": ops}
    ctx.say(f"traced {window_s:.3f}s, busy {busy_s:.3f}s, "
            f"{ctx.traced_rounds} rounds, programs "
            f"{ {k: round(v, 4) for k, v in programs.items()} }, executions "
            f"{tr.program_counts(planes, ctx.platform)}")
    return {"device_ops": tr.top_device_ops(planes, ctx.platform, 10),
            "idle_gaps": tr.idle_gaps(planes, ctx.platform, 10)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox only: CPU, the configuration's tiny size")
    ap.add_argument("--dump-trace", default=None,
                    help="directory for a described and a cut-down trace")
    args = ap.parse_args(argv)

    manifest = mf.load(ROOT)
    cell = mf.cell(manifest, args.workload, ROOT)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(ROOT, ".jax_cache_rehearse"))
        tiny = cell["config"].get("rehearse", {})
        cell["config"] = {**cell["config"], **tiny, "params": {
            **cell["config"]["params"], **tiny.get("params", {})}}

    # what the configuration states about the program's own switches; read at
    # the program's import, so set before the driver imports it
    os.environ.update(cell["config"].get("program_env", {}))

    import jax

    platform = jax.default_backend()
    devices = jax.local_devices()
    chips = int(cell["entry"]["chips"])
    if platform != "tpu" and not args.rehearse:
        print(f"jax's default backend is {platform!r}, not a TPU; the "
              "benchmark measures nothing elsewhere (--rehearse for the "
              "sandbox)", file=sys.stderr)
        return 3
    if len(devices) < chips:
        print(f"the cell asks for {chips} chip(s), jax sees {len(devices)}",
              file=sys.stderr)
        return 3
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}

    ctx = Ctx(args, cell, platform)
    ctx.say(f"{args.workload} seed {args.seed} seconds {args.seconds} trace "
            f"{args.trace} on {device}, compile cache "
            f"{jax.config.jax_compilation_cache_dir}, program_env "
            f"{cell['config'].get('program_env', {})}")
    driver = importlib.import_module("drivers." + cell["traffic"]["driver"])
    state = driver.measure(ctx)
    device["memory_peak_bytes"] = state["memory_peak_bytes"]

    facts = dict(state["facts"], config=ctx.config, platform=platform,
                 device_kind="rehearsal" if args.rehearse else device["kind"],
                 trace=None)
    breakdown = read_trace(ctx, facts, args.dump_trace) if ctx.trace else None
    if breakdown is not None:
        device["busy_s"] = facts["trace"]["busy_s"]
        device["window_s"] = facts["trace"]["window_s"]

    correct, table = driver.check(ctx, state)

    metrics = {}
    for m in mf.metrics_of(manifest, "end_to_end", args.workload):
        metrics[m["name"]] = {"value": state["end_to_end"][m["name"]],
                              "unit": m["unit"]}
    if ctx.trace:
        for m in mf.metrics_of(manifest, "per_layer", args.workload):
            value = mf.layer_reader(m["name"]).read(facts)
            if value is None:
                ctx.say(f"per-layer metric {m['name']}: nothing to read")
            else:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    bad = [k for k, v in metrics.items() if not math.isfinite(v["value"])]
    if bad:
        raise SystemExit(f"non-finite metrics {bad}; no line printed")

    for name, (value, limit) in table.items():
        ctx.say(f"compared {name} = {value:.6g}  limit {limit:.6g}  "
                f"{'ok' if value <= limit else 'OVER'}")
    result = {"correct": bool(correct), "attempted": state["attempted"],
              "failed": state["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": v if math.isfinite(v) else 1e300,
                               "limit": lim}
                          for k, (v, lim) in table.items()}
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
