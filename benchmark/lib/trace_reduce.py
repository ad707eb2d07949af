"""From a profiler trace to numbers. Works on a neutral form of the trace,

    [{"name": plane, "lines": [{"name": line, "events": [[name, start_ns, dur_ns], ...]}]}]

which ``load_xplane`` makes from an ``.xplane.pb`` with nothing but jax, and
which the recorded fixture under ``benchmark/fixtures/`` holds as JSON.

Rules, each learned from a refusal or a real trace:
- busy time is the UNION of the op intervals of ONE device plane's ONE op-level
  line, clipped to the traced interval; never a sum over lines (steps, modules
  and ops overlap) and never over several devices' planes (those are averaged);
- programs are read from the module-level line, by name;
- the traced interval is the host annotation ``bench.traced_window``, which the
  profiler puts on the same clock as the device lines.
"""

from __future__ import annotations

import gzip
import json
import re

WINDOW_SPAN = "bench.traced_window"

# Where each platform's trace keeps what. The cpu rule exists for the sandbox
# rehearsal only: there the "device" is XLA's host thread pool.
RULES = {
    "tpu": {"plane": r"^/device:TPU:\d+$", "op_line": r"^XLA Ops$",
            "module_plane": None, "module_line": r"^XLA Modules$",
            "module_event": r"^(?P<name>.+?)(\(\d+\))?$"},
    "cpu": {"plane": r"^/host:CPU$", "op_line": r"^tf_XLA",
            "module_plane": r"^/host:CPU$", "module_line": r"",
            "module_event": r"^PjitFunction\((?P<name>.+)\)$",
            "op_skip": r"^(ThreadpoolListener|ThunkExecutor)"},
}
HOST_PLANE = r"^/host:CPU$"


def load_xplane(path: str) -> list:
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = [{"name": line.name,
                  "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                             for e in line.events]}
                 for line in plane.lines]
        planes.append({"name": plane.name, "lines": lines})
    return planes


def load_fixture(path: str) -> list:
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


def save_fixture(planes: list, path: str) -> None:
    with gzip.open(path, "wt") as fh:
        json.dump(planes, fh, separators=(",", ":"))


def union_seconds(intervals, lo: float, hi: float) -> float:
    """Length of the union of [start, end) intervals (ns) clipped to
    [lo, hi], in seconds."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def _lines(planes, plane_pat, line_pat):
    for plane in planes:
        if re.search(plane_pat, plane["name"]):
            for line in plane["lines"]:
                if re.search(line_pat, line["name"]):
                    yield plane, line


def traced_interval(planes):
    """(start_ns, end_ns) of the ``bench.traced_window`` annotation."""
    for _plane, line in _lines(planes, HOST_PLANE, r""):
        for name, start, dur in line["events"]:
            if name == WINDOW_SPAN:
                return start, start + dur
    raise ValueError(f"no {WINDOW_SPAN!r} annotation in the trace's host plane")


def device_planes(planes, platform: str) -> list:
    """Names of the device planes that hold an op-level line with events,
    sorted."""
    rule = RULES[platform]
    return sorted({p["name"] for p, ln in _lines(
        planes, rule["plane"], rule["op_line"]) if ln["events"]})


def op_events(planes, platform: str, plane_name: str) -> list:
    """[name, start, dur] of one device plane's op-level line(s). On a TPU
    that is exactly one line; the rehearsal's host pool has several."""
    rule = RULES[platform]
    skip = rule.get("op_skip")
    out = []
    for plane, line in _lines(planes, rule["plane"], rule["op_line"]):
        if plane["name"] != plane_name:
            continue
        out.extend(e for e in line["events"]
                   if e[2] > 0 and not (skip and re.search(skip, e[0])))
    return out


def busy_and_window(planes, platform: str, chips: int):
    """(busy_s, window_s): busy is the union of op intervals inside the
    traced interval on each of the first ``chips`` device planes, averaged."""
    lo, hi = traced_interval(planes)
    names = device_planes(planes, platform)[:chips]
    if len(names) < chips:
        raise ValueError(f"trace holds {len(names)} device plane(s) with an "
                         f"op line, the cell uses {chips}")
    busy = [union_seconds(((s, s + d) for _n, s, d in
                           op_events(planes, platform, name)), lo, hi)
            for name in names]
    return sum(busy) / len(busy), (hi - lo) / 1e9


def _module_events(planes, platform: str):
    """(program name, start, dur) of the first device plane's module line."""
    rule = RULES[platform]
    first = None
    for plane, line in _lines(planes, rule["module_plane"] or rule["plane"],
                              rule["module_line"]):
        first = first or plane["name"]
        if plane["name"] != first:
            continue
        for name, start, dur in line["events"]:
            m = re.search(rule["module_event"], name)
            if m:
                yield m.group("name"), start, dur


def program_seconds(planes, platform: str) -> dict:
    """Device seconds of each XLA program inside the traced interval, by the
    program's name, on the first device plane."""
    lo, hi = traced_interval(planes)
    out: dict = {}
    for name, start, dur in _module_events(planes, platform):
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
    return out


def program_counts(planes, platform: str) -> dict:
    """Executions of each XLA program that start inside the traced interval,
    on the first device plane. A per-round program's count is the rounds."""
    lo, hi = traced_interval(planes)
    out: dict = {}
    for name, start, _dur in _module_events(planes, platform):
        if lo <= start < hi:
            out[name] = out.get(name, 0) + 1
    return out


def matching_seconds(programs: dict, patterns) -> float:
    return sum(sec for name, sec in programs.items()
               if any(p in name for p in patterns))


def op_self_seconds(planes, platform: str) -> dict:
    """Self time of each op on the first device plane's op line, clipped to
    the traced interval: an op's time less the time of the ops nested in it
    (a ``while`` covers its body's ops on the same line), by the op's full
    name as the trace gives it. The values add up to the busy time."""
    lo, hi = traced_interval(planes)
    names = device_planes(planes, platform)
    if not names:
        return {}
    events = sorted(((n, max(s, lo), min(s + d, hi)) for n, s, d in
                     op_events(planes, platform, names[0])),
                    key=lambda e: (e[1], -e[2]))
    out: dict = {}
    stack: list = []                     # [name, start, end, children's time]

    def pop():
        name, start, end, inner = stack.pop()
        out[name] = out.get(name, 0.0) + max(0.0, (end - start) - inner) / 1e9

    for name, start, end in events:
        if end <= start:
            continue
        while stack and stack[-1][2] <= start:
            pop()
        if stack:                        # nested: clip to the parent
            end = min(end, stack[-1][2])
            stack[-1][3] += end - start
        stack.append([name, start, end, 0.0])
    while stack:
        pop()
    return out


def short_op_name(name: str) -> str:
    """``%fusion.8 = u8[10502144,28]{...} fusion(...), kind=kCustom`` ->
    ``fusion.8 fusion u8[10502144,28]`` (a custom call keeps its target)."""
    m = re.match(r"%?(?P<id>[^\s=]+) = (?P<shape>\(?[a-z0-9]+\[[^\]]*\])?"
                 r".*?(?P<op>[a-z][a-z\-]*)\(", name)
    if not m:
        return name[:100]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    parts = [m.group("id"), target.group(1) if target else m.group("op"),
             (m.group("shape") or "").lstrip("(")]
    return " ".join(p for p in parts if p)[:100]


def top_device_ops(planes, platform: str, k: int = 10) -> list:
    """The k ops that took most device time of their own in the traced
    interval, under short names."""
    tot: dict = {}
    for name, sec in op_self_seconds(planes, platform).items():
        key = short_op_name(name)
        tot[key] = tot.get(key, 0.0) + sec
    return [[n, s] for n, s in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(planes, platform: str, k: int = 10) -> list:
    """The k longest intervals in which no op ran on the first device plane,
    each named by the host event that covers most of it (the harness's own
    annotations around the calls, and whatever the runtime's host tracer
    recorded), summed by that name."""
    lo, hi = traced_interval(planes)
    names = device_planes(planes, platform)
    if not names:
        return []
    spans = sorted((max(s, lo), min(s + d, hi)) for _n, s, d in
                   op_events(planes, platform, names[0]))
    gaps, edge = [], lo
    for s, e in spans:
        if e <= s:
            continue
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    if hi > edge:
        gaps.append((edge, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:50]
    host = [(n, s, s + d) for _p, line in _lines(planes, HOST_PLANE, r"")
            if not re.search(RULES[platform]["op_line"], line["name"])
            for n, s, d in line["events"] if d > 0 and n != WINDOW_SPAN]
    tot: dict = {}
    for gs, ge in gaps:
        best, cover = "no host event", 0.0
        for n, s, e in host:
            c = min(e, ge) - max(s, gs)
            # the tightest event that covers the gap names it best
            if c > cover or (c == cover and c > 0 and e - s < best_len):
                best, cover, best_len = n, c, e - s
        tot[best] = tot.get(best, 0.0) + (ge - gs) / 1e9
    return [[n, s] for n, s in sorted(tot.items(), key=lambda kv: -kv[1])[:k]]


def cut_down(planes, platform: str, max_events: int = 4000) -> list:
    """A small copy of a trace for the fixture: the device planes' op and
    module lines and the host lines that hold the harness's annotations, each
    capped at its first ``max_events`` events."""
    rule = RULES[platform]
    keep = []
    for plane in planes:
        lines = []
        for line in plane["lines"]:
            dev = re.search(rule["plane"], plane["name"]) and (
                re.search(rule["op_line"], line["name"])
                or re.search(rule["module_line"], line["name"]))
            host = re.search(HOST_PLANE, plane["name"]) and any(
                e[0].startswith("bench.") for e in line["events"])
            if dev or host:
                lines.append({"name": line["name"],
                              "events": line["events"][:max_events]})
        if lines:
            keep.append({"name": plane["name"], "lines": lines})
    return keep


def describe(planes, per_line: int = 12) -> str:
    """Planes, lines and the first events of each, for reading by hand."""
    out = []
    for plane in planes:
        out.append(f"PLANE {plane['name']!r}")
        for line in plane["lines"]:
            ev = line["events"]
            span = (min(e[1] for e in ev), max(e[1] + e[2] for e in ev)) \
                if ev else (0, 0)
            out.append(f"  LINE {line['name']!r}: {len(ev)} events, "
                       f"{span[0] / 1e9:.6f}..{span[1] / 1e9:.6f} s")
            for name, start, dur in ev[:per_line]:
                out.append(f"    {start / 1e9:.6f} +{dur / 1e6:.3f} ms  "
                           f"{name[:120]}")
    return "\n".join(out)
