"""What the program's own names say about a trace: device time by
``xtpu.<stage>`` scope, and the device's idle time by the program span the
host was in.

**The channel.** A TPU trace carries an op's ``jax.named_scope`` path as the
``tf_op`` stat of the op's *event metadata* on the device plane
(``jit(_fused_round_fn)/xtpu.grow/.../xtpu.sort/xtpu.permute/jit(_take)/gather:``).
``jax.profiler.ProfileData`` yields an event's own stats only, so ``load``
reads the ``.xplane.pb`` through the generated ``xplane_pb2`` where one is
installed (here only tensorflow ships one; ``xprof`` and
``tensorboard_plugin_profile`` do not) and, where none is, through a reader
of the wire format (standard library only), into ``trace_reduce``'s neutral
form plus, per line, a ``stats`` list parallel to ``events``:

    {"name": line, "events": [[name, start_ns, dur_ns], ...],
     "stats": [{"tf_op": ...} | {"iteration": 3, ...} | {}, ...]}

Host spans (``round*``, ``train/*``: ``xgboost_tpu/obs/trace.py``) are
``TraceAnnotation``s, which the profiler puts on the clock of the device
lines; their stats are the span's args.

**Whose scopes.** jax's persistent compile cache leaves metadata out of its
key: an executable it serves carries the scopes of the source that wrote the
entry. ``scope_check`` holds the trace to what the program exports
(``obs.trace.ROUND_ROOTS``, ``opened_stages()``, the cache hits by program):
an op that proves other source makes every ``stage_*_ms`` read 0 and
``stage_unattributed_pct`` 100; a cache hit alone is printed beside them.

    python3 benchmark/lib/program_trace.py OUT.json.gz _fused_round_fn 2
                              # cut a fixture of 2 rounds from the last trace
"""

from __future__ import annotations

import functools
import glob
import importlib.util
import os
import re
import struct
import sys

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from lib import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# run.py's own rule: the one trace of the run
TRACE_GLOB = os.path.join(ROOT, "benchmark_out", "trace", "plugins",
                          "profile", "*", "*.xplane.pb")

SCOPE = re.compile(r"xtpu\.[A-Za-z0-9_.]+")
SPAN = re.compile(r"^(round(/.*)?|train/.*)$")

# Which metric a stage's self time goes to. "What is left directly under"
# a sweep's own scope (padding, transposes, loop glue) goes with the sweep.
GROUPS = {
    "partition": ("advance", "count_sort", "delta", "sort"),
    "permute": ("permute",),
    "hist": ("kernel.", "quantise", "fold", "hist", "advance_hist",
             "root", "apply"),
    "split": ("window", "refine", "eval", "exchange", "pop", "push",
              "finalize"),
    "objective": ("gradient", "leaf", "margin", "grow"),
}


# ---- the wire format --------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        byte = buf[i]
        i += 1
        out |= (byte & 0x7F) << shift
        shift += 7
        if not byte & 0x80:
            return out, i


def _fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value stays a memoryview."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = buf[i:i + 8], i + 8
        elif wire == 5:
            val, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, wire, val


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """XStat -> (name, value): metadata_id=1, double=2, uint64=3, int64=4,
    str=5, bytes=6 (skipped), ref=7 (a stat metadata's name)."""
    key = val = None
    for f, _w, v in _fields(buf):
        if f == 1:
            key = stat_names.get(v)
        elif f == 2:
            val = struct.unpack("<d", bytes(v))[0]
        elif f in (3, 4):
            val = v - (1 << 64) if f == 4 and v >= 1 << 63 else v
        elif f == 5:
            val = _text(v)
        elif f == 7:
            val = stat_names.get(v, "")
    return key, val


def _keep(name: str, stats: dict) -> dict:
    """The stats worth carrying: an op's scope path, a program span's args."""
    if SPAN.match(name):
        return {k: v for k, v in stats.items() if not k.startswith("_")}
    return {"tf_op": stats["tf_op"]} if "tf_op" in stats else {}


def _load_wire(space) -> list:
    """``load`` with no ``xplane_pb2``, off the bytes. XSpace.planes=1;
    XPlane: name=2, lines=3, event_metadata=4, stat_metadata=5; XLine:
    name=2, timestamp_ns=3, events=4; XEvent: metadata_id=1, offset_ps=2,
    duration_ps=3, stats=4; XEventMetadata: name=2, stats=5."""
    space = memoryview(space)
    planes = []
    for f, _w, plane in _fields(space):
        if f != 1:
            continue
        parts = list(_fields(plane))
        stat_names, metas, name = {}, {}, ""
        for f2, _w2, v in parts:
            if f2 == 2:
                name = _text(v)
            elif f2 == 5:                      # map entry: key=1, value=2
                entry = dict((f3, v3) for f3, _w3, v3 in _fields(v))
                stat_names[entry.get(1, 0)] = next(
                    (_text(v4) for f4, _w4, v4 in _fields(entry[2])
                     if f4 == 2), "")
        for f2, _w2, v in parts:
            if f2 != 4:
                continue
            entry = dict((f3, v3) for f3, _w3, v3 in _fields(v))
            meta_name, stats = "", {}
            for f4, _w4, v4 in _fields(entry[2]):
                if f4 == 2:
                    meta_name = _text(v4)
                elif f4 == 5:
                    key, val = _stat(v4, stat_names)
                    if key is not None and val is not None:
                        stats[key] = val
            metas[entry.get(1, 0)] = (meta_name, stats)
        lines = []
        for f2, _w2, v in parts:
            if f2 != 3:
                continue
            line_name, t0, events, stats = "", 0, [], []
            raw = []
            for f3, _w3, v3 in _fields(v):
                if f3 == 2:
                    line_name = _text(v3)
                elif f3 == 3:
                    t0 = v3
                elif f3 == 4:
                    raw.append(v3)
            for ev in raw:
                meta_id = offset = dur = 0
                own = {}
                for f4, _w4, v4 in _fields(ev):
                    if f4 == 1:
                        meta_id = v4
                    elif f4 == 2:
                        offset = v4
                    elif f4 == 3:
                        dur = v4
                    elif f4 == 4:
                        key, val = _stat(v4, stat_names)
                        if key is not None and val is not None:
                            own[key] = val
                ev_name, meta_stats = metas.get(meta_id, ("", {}))
                events.append([ev_name, t0 + offset / 1e3, dur / 1e3])
                stats.append(_keep(ev_name, {**meta_stats, **own}))
            lines.append({"name": line_name, "events": events,
                          "stats": stats})
        planes.append({"name": name, "lines": lines})
    return planes


@functools.lru_cache(maxsize=1)
def xplane_pb2():
    """The generated ``xplane_pb2``, or None where no installed package
    ships one. tensorflow's is the only one here, and ``import tensorflow``
    costs 22 s and 4,900 modules in the process that holds the chip, so its
    one file is loaded by path: that imports ``google.protobuf`` alone."""
    try:
        spec = importlib.util.find_spec("tensorflow")
        path = os.path.join(spec.submodule_search_locations[0], "tsl",
                            "profiler", "protobuf", "xplane_pb2.py")
        spec = importlib.util.spec_from_file_location("_xtpu_xplane_pb2", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    except Exception as err:  # not installed, or refused by the protobuf
        print(f"[bench] xplane reader: wire format (no xplane_pb2: "
              f"{type(err).__name__}: {err})", file=sys.stderr, flush=True)
        return None
    print(f"[bench] xplane reader: {path}", file=sys.stderr, flush=True)
    return module


def _load_pb2(pb2, space) -> list:
    planes = []
    for plane in pb2.XSpace.FromString(space).planes:
        names = {k: m.name for k, m in plane.stat_metadata.items()}

        def stats_of(stats):
            out = {}
            for st in stats:
                kind = st.WhichOneof("value")
                if st.metadata_id in names and kind not in (None,
                                                            "bytes_value"):
                    val = getattr(st, kind)
                    out[names[st.metadata_id]] = (
                        names.get(val, "") if kind == "ref_value" else val)
            return out

        metas = {k: (m.name, stats_of(m.stats))
                 for k, m in plane.event_metadata.items()}
        lines = []
        for line in plane.lines:
            events, stats = [], []
            for ev in line.events:
                name, meta_stats = metas.get(ev.metadata_id, ("", {}))
                events.append([name, line.timestamp_ns + ev.offset_ps / 1e3,
                               ev.duration_ps / 1e3])
                stats.append(_keep(name, {**meta_stats,
                                          **stats_of(ev.stats)}))
            lines.append({"name": line.name, "events": events,
                          "stats": stats})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def load(path: str) -> list:
    """An ``.xplane.pb`` in the neutral form, with ``stats``."""
    with open(path, "rb") as fh:
        space = fh.read()
    pb2 = xplane_pb2()
    return _load_wire(space) if pb2 is None else _load_pb2(pb2, space)


_load_once = functools.lru_cache(maxsize=1)(load)


def last_trace():
    """The run's one trace with stats, loaded once a process; None when the
    run left none (or more than one)."""
    found = glob.glob(TRACE_GLOB)
    return _load_once(found[0]) if len(found) == 1 else None


# ---- device time by stage ---------------------------------------------------

def stage_of(tf_op: str) -> str:
    """The innermost ``xtpu.<stage>`` on an op's scope path, without the
    prefix; ``""`` for none."""
    scopes = SCOPE.findall(tf_op or "")
    return scopes[-1][len("xtpu."):] if scopes else ""


def round_ops(planes, platform: str, round_programs) -> list:
    """[(start, end, inside, tf_op)] of the first device plane's ops, clipped
    to the traced interval and sorted outermost first; ``inside``: the op
    starts inside an execution of a round program."""
    lo, hi = tr.traced_interval(planes)
    names = tr.device_planes(planes, platform)
    if not names:
        return []
    runs = sorted((s, s + d) for n, s, d in tr._module_events(planes, platform)
                  if any(p in n for p in round_programs))
    rule = tr.RULES[platform]
    skip = rule.get("op_skip")
    ops = []
    for plane, line in tr._lines(planes, rule["plane"], rule["op_line"]):
        if plane["name"] != names[0]:
            continue
        stats = line.get("stats") or [{}] * len(line["events"])
        ops.extend((max(s, lo), min(s + d, hi), s, st.get("tf_op", ""))
                   for (n, s, d), st in zip(line["events"], stats)
                   if d > 0 and not (skip and re.search(skip, n)))
    ops.sort(key=lambda o: (o[0], -o[1]))
    out, run_i = [], 0
    for start, end, true_start, tf_op in ops:
        while run_i < len(runs) and runs[run_i][1] <= true_start:
            run_i += 1
        inside = run_i < len(runs) and runs[run_i][0] <= true_start
        out.append((start, end, inside, tf_op))
    return out


def stage_self_seconds(planes, platform: str, round_programs) -> dict:
    """{stage: seconds}: self time (a ``while`` less its body, as
    ``trace_reduce.op_self_seconds`` counts it) of the ops that start inside
    an execution of a round program on the first device plane, clipped to
    the traced interval, by innermost stage; ``""`` holds what no scope
    covers. Ops of other programs (the eval walk) are left out."""
    out: dict = {}
    stack: list = []                  # [stage or None, start, end, inner]

    def pop():
        stage, start, end, inner = stack.pop()
        if stage is not None:
            out[stage] = out.get(stage, 0.0) \
                + max(0.0, (end - start) - inner) / 1e9

    for start, end, inside, tf_op in round_ops(planes, platform,
                                               round_programs):
        if end <= start:
            continue
        while stack and stack[-1][2] <= start:
            pop()
        if stack:
            end = min(end, stack[-1][2])
            stack[-1][3] += end - start
        stack.append([stage_of(tf_op) if inside else None, start, end, 0.0])
    while stack:
        pop()
    return out


# ---- whose scopes ------------------------------------------------------------

def program_exports(round_programs):
    """What the program says of its own scopes: the stages every scoped op
    of a round program starts with, the stages this process opened while
    tracing, and ``hit`` or ``miss`` for each round program it compiled.
    None where the program says nothing (a parent from before PR 27)."""
    try:
        from xgboost_tpu.obs import metrics, trace
        roots, opened = set(trace.ROUND_ROOTS), set(trace.opened_stages())
    except (ImportError, AttributeError):
        return None
    return {"roots": roots, "opened": opened, "served": {
        name: "hit" if c.get("cache_hits") else "miss"
        for name, c in sorted(metrics.program_compile_counts().items())
        if c["compiles"] and any(p in name for p in round_programs)}}


def scope_check(paths, exports) -> dict:
    """Hold the scope paths of a trace's round-program ops to the program's
    exports. ``foreign``: chains no trace of this source can have produced
    (a stage this process never opened, or a whole path, one from ``jit(``
    on, that does not start with a root): proof of an executable of other
    source. ``absent``: stages opened here that no op carries: scopes whose
    ops were fused away or, after a cache hit, scopes the entry's writer
    did not have."""
    foreign, seen = set(), set()
    for tf_op in paths:
        scopes = [s[len("xtpu."):] for s in SCOPE.findall(tf_op or "")]
        seen.update(scopes)
        if scopes and (not set(scopes) <= exports["opened"] or (
                tf_op.startswith("jit(")
                and scopes[0] not in exports["roots"])):
            foreign.add("/".join(scopes))
    return {"foreign": sorted(foreign),
            "absent": sorted(exports["opened"] - seen)}


def group_of(stage: str) -> str:
    """The metric group of a stage: ``partition`` ... ``objective``, or
    ``""`` for a device op under no scope (or one no group lists)."""
    for group, members in GROUPS.items():
        if any(stage == m or (m.endswith(".") and stage.startswith(m))
               for m in members):
            return group
    return ""


@functools.lru_cache(maxsize=1)
def _group_seconds_once(platform: str, round_programs: tuple) -> dict:
    planes = last_trace()
    if planes is None:
        return None
    out = {g: 0.0 for g in list(GROUPS) + [""]}
    for stage, sec in stage_self_seconds(planes, platform,
                                         round_programs).items():
        out[group_of(stage)] += sec
    exports = program_exports(round_programs)
    if not (exports and exports["served"]):
        return out           # a trace this process did not make: a fixture
    check = scope_check([tf_op for _s, _e, inside, tf_op in round_ops(
        planes, platform, round_programs) if inside], exports)
    served = ", ".join(f"{n} cache={v}" for n, v in exports["served"].items())
    if check["foreign"]:
        print(f"[bench] STALE SCOPES ({served}): ops of the round programs "
              f"carry {check['foreign'][:6]}, which this source cannot have "
              "traced: the compile cache served an executable of other "
              "source. Every stage_*_ms reads 0 and stage_unattributed_pct "
              "100; run on an empty JAX_COMPILATION_CACHE_DIR.",
              file=sys.stderr, flush=True)
        return {**{g: 0.0 for g in GROUPS}, "": sum(out.values())}
    print(f"[bench] scopes: {served}; opened by this source and on no op of "
          f"the trace: {check['absent'] or 'none'}"
          + ("; a hit carries the scopes of whoever wrote the entry "
             "(PERF.md 7)" if "hit" in exports["served"].values() else ""),
          file=sys.stderr, flush=True)
    return out


def stage_group_seconds(facts) -> dict:
    """{group: seconds} over the last trace, ``""`` for what no stage
    covers; None when the run was not traced. A trace that ``scope_check``
    proves to be of other source reads all of it under ``""``."""
    if not facts.get("trace"):
        return None
    return _group_seconds_once(facts["platform"],
                               tuple(facts["round_programs"]))


def stage_group_ms(facts, group: str):
    """One group's device time per traced round, in ms (0.0 where no op
    carries the stage); None when the run was not traced."""
    sec = stage_group_seconds(facts)
    if sec is None or not facts["trace"].get("rounds"):
        return None
    return 1e3 * sec[group] / facts["trace"]["rounds"]


# ---- idle time by program span ----------------------------------------------

def idle_intervals(planes, platform: str) -> list:
    """[(start, end)] in which no op ran on the first device plane, inside
    the traced interval (the complement of the op line's union)."""
    lo, hi = tr.traced_interval(planes)
    names = tr.device_planes(planes, platform)
    if not names:
        return [(lo, hi)]
    gaps, edge = [], lo
    for s, e in sorted((max(s, lo), min(s + d, hi)) for _n, s, d in
                       tr.op_events(planes, platform, names[0])):
        if e <= s:
            continue
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        gaps.append((edge, hi))
    return gaps


def span_segments(planes) -> list:
    """The host timeline cut at every program span's edges:
    [(start, end, name)] sorted and disjoint, ``name`` the innermost
    (shortest) span that covers the segment."""
    spans = [(s, s + d, n) for _p, line in tr._lines(planes, tr.HOST_PLANE, "")
             for n, s, d in line["events"] if d > 0 and SPAN.match(n)]
    edges = sorted({t for s, e, _n in spans for t in (s, e)})
    starts = sorted(spans)
    active, out, k = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(starts) and starts[k][0] <= a:
            active.append(starts[k])
            k += 1
        active = [sp for sp in active if sp[1] > a]
        if active:
            out.append((a, b, min(active, key=lambda sp: sp[1] - sp[0])[2]))
    return out


def idle_by_span(planes, platform: str) -> dict:
    """{span name: seconds} of device idle time in the traced interval, each
    idle interval split among the innermost program span that covers it on
    the host plane; ``""`` holds what no span covers."""
    gaps, segs = idle_intervals(planes, platform), span_segments(planes)
    out: dict = {}
    j = 0
    for gs, ge in gaps:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= gs:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < ge:
            part = min(ge, segs[k][1]) - max(gs, segs[k][0])
            if part > 0:
                out[segs[k][2]] = out.get(segs[k][2], 0.0) + part / 1e9
                covered += part
            k += 1
        rest = (ge - gs) - covered
        if rest > 0:
            out[""] = out.get("", 0.0) + rest / 1e9
    return out


def idle_seconds(facts):
    """``idle_by_span`` of the last trace; None when the run was not
    traced."""
    if not facts.get("trace"):
        return None
    planes = last_trace()
    return None if planes is None else idle_by_span(planes, facts["platform"])


def idle_ms_under(facts, pattern: str):
    """Idle ms per traced round under the spans whose name matches
    ``pattern`` (a regex on the whole name)."""
    idle = idle_seconds(facts)
    if idle is None or not facts["trace"].get("rounds"):
        return None
    sec = sum(v for k, v in idle.items() if re.fullmatch(pattern, k))
    return 1e3 * sec / facts["trace"]["rounds"]


# ---- the fixture -------------------------------------------------------------

def cut_down(planes, platform: str, until_ns: float) -> list:
    """A small, whole copy for a fixture: the first device plane's op and
    module lines and the host lines that hold a program span or the
    harness's annotations, stats kept, every event that starts before
    ``until_ns``; the traced-window annotation is cut to end there, so the
    copy is a shorter traced run and every reduction still adds up."""
    rule = tr.RULES[platform]
    keep = []
    for plane in planes:
        lines = []
        for line in plane["lines"]:
            dev = re.search(rule["plane"], plane["name"]) and (
                re.search(rule["op_line"], line["name"])
                or re.search(rule["module_line"], line["name"]))
            host = re.search(tr.HOST_PLANE, plane["name"]) and any(
                e[0].startswith("bench.") or SPAN.match(e[0])
                for e in line["events"])
            if not (dev or host):
                continue
            pairs = [([n, s, min(d, until_ns - s) if n == tr.WINDOW_SPAN
                       else d], st)
                     for (n, s, d), st in zip(line["events"], line["stats"])
                     if s < until_ns]
            lines.append({"name": line["name"],
                          "events": [e for e, _st in pairs],
                          "stats": [st for _e, st in pairs]})
        if lines:
            keep.append({"name": plane["name"], "lines": lines})
    return keep


def main(argv) -> int:
    """OUT.json.gz PROGRAM N [TRACE.xplane.pb]: the traced window up to the
    start of PROGRAM's execution number N + 1 (so N whole rounds of a
    per-round program, with what ran between them)."""
    out, program, n = argv[0], argv[1], int(argv[2])
    planes = load(argv[3]) if len(argv) > 3 else last_trace()
    lo, hi = tr.traced_interval(planes)
    starts = sorted(s for name, s, _d in tr._module_events(planes, "tpu")
                    if program in name and lo <= s < hi)
    tr.save_fixture(cut_down(planes, "tpu",
                             starts[n] if n < len(starts) else hi), out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
