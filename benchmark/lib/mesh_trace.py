"""The mesh cell's device trace by collective and by chip.

Collectives: the self time of the round programs' ops by the innermost
``mesh.<what>`` scope on each op's ``tf_op`` path (``xgboost_tpu/obs/trace.py
MESH_SCOPES``: ``hist_psum``, ``root_psum``, ``scale_pmax``), on the first
device plane. The scopes sit one level below the ``xtpu.`` stage that holds
the collective and carry no ``xtpu.`` prefix, so ``program_trace.stage_of``
does not take them for stages: a histogram's all-reduce still counts under
its stage (``stage_split_ms`` for ``xtpu.exchange``) and is read here
besides. A program without the scopes (a parent from before they were
added) reads None: nothing to read.

Chips: the busy time (union of op intervals inside the traced interval) of
each of the cell's device planes.
"""

from __future__ import annotations

import functools
import re
import sys

from lib import program_trace as pt
from lib import trace_reduce as tr

MESH_SCOPE = re.compile(r"mesh\.[a-z_]+")


def what_of(tf_op: str) -> str:
    """The innermost ``mesh.<what>`` on an op's scope path, without the
    prefix; ``""`` for none."""
    scopes = MESH_SCOPE.findall(tf_op or "")
    return scopes[-1][len("mesh."):] if scopes else ""


def collective_self_seconds(planes, platform: str, round_programs) -> dict:
    """{what: seconds} of self time (a ``while`` less its body, as
    ``program_trace.stage_self_seconds`` counts it) of the ops that start
    inside an execution of a round program, clipped to the traced interval;
    ``""`` holds what no ``mesh.`` scope covers."""
    out: dict = {}
    stack: list = []                  # [what or None, start, end, inner]

    def pop():
        what, start, end, inner = stack.pop()
        if what is not None:
            out[what] = out.get(what, 0.0) \
                + max(0.0, (end - start) - inner) / 1e9

    for start, end, inside, tf_op in pt.round_ops(planes, platform,
                                                  round_programs):
        if end <= start:
            continue
        while stack and stack[-1][2] <= start:
            pop()
        if stack:
            end = min(end, stack[-1][2])
            stack[-1][3] += end - start
        stack.append([what_of(tf_op) if inside else None, start, end, 0.0])
    while stack:
        pop()
    return out


def chip_busy_seconds(planes, platform: str, chips: int) -> list:
    """Busy seconds inside the traced interval of each of the first
    ``chips`` device planes."""
    lo, hi = tr.traced_interval(planes)
    return [tr.union_seconds(((s, s + d) for _n, s, d in
                              tr.op_events(planes, platform, name)), lo, hi)
            for name in tr.device_planes(planes, platform)[:chips]]


@functools.lru_cache(maxsize=1)
def _once(platform: str, round_programs: tuple, chips: int):
    planes = pt.last_trace()
    if planes is None:
        return None
    by_what = collective_self_seconds(planes, platform, round_programs)
    busy = chip_busy_seconds(planes, platform, chips)
    print("[bench] collectives by mesh.* scope, s in the traced interval: "
          f"{ {k or '(none)': round(v, 5) for k, v in by_what.items()} }; "
          f"busy s by chip {[round(b, 4) for b in busy]}",
          file=sys.stderr, flush=True)
    return by_what, busy


def _read(facts):
    trace = facts.get("trace")
    if not trace or not trace.get("rounds") or not facts.get("round_programs"):
        return None
    return _once(facts["platform"], tuple(facts["round_programs"]),
                 int(facts.get("chips", 1)))


def allreduce_ms(facts):
    """Self ms a traced round of the ops under any ``mesh.`` scope; None
    when the run was not traced or no op carries one."""
    got = _read(facts)
    if got is None or not any(got[0]):
        return None
    return 1e3 * sum(v for k, v in got[0].items() if k) \
        / facts["trace"]["rounds"]


def chip_skew_pct(facts):
    """Largest less smallest busy time over the cell's device planes, over
    their mean; None with fewer than two planes."""
    got = _read(facts)
    if got is None or len(got[1]) < 2 or not sum(got[1]):
        return None
    busy = got[1]
    return 100.0 * (max(busy) - min(busy)) / (sum(busy) / len(busy))
