"""Device time of the ranking gradient by its parts: the self time of the
gradient program's ops by the innermost ``rank.<part>`` scope on each op's
``tf_op`` path (``xgboost_tpu/obs/trace.py RANK_SCOPES``: ``layout``,
``order``, ``pairs``, ``reduce``). The scopes sit one level below
``xtpu.gradient`` and carry no ``xtpu.`` prefix, so ``program_trace.stage_of``
does not take them for stages and the whole gradient still counts under
``stage_objective_ms``.

Which program is the gradient is the mix's ``gradient_program``. A program
without the scopes (a parent from before they were added) reads None
everywhere: nothing to read."""

from __future__ import annotations

import functools
import re
import sys

from lib import program_trace as pt

RANK_SCOPE = re.compile(r"rank\.[a-z_]+")


def part_of(tf_op: str) -> str:
    """The innermost ``rank.<part>`` on an op's scope path, without the
    prefix; ``""`` for none."""
    scopes = RANK_SCOPE.findall(tf_op or "")
    return scopes[-1][len("rank."):] if scopes else ""


def part_self_seconds(planes, platform: str, gradient_program: str) -> dict:
    """{part: seconds} of self time (a ``while`` less its body, as
    ``program_trace.stage_self_seconds`` counts it) of the ops that start
    inside an execution of the gradient program, clipped to the traced
    interval; ``""`` holds what no ``rank.`` scope covers."""
    out: dict = {}
    stack: list = []                  # [part or None, start, end, inner]

    def pop():
        part, start, end, inner = stack.pop()
        if part is not None:
            out[part] = out.get(part, 0.0) \
                + max(0.0, (end - start) - inner) / 1e9

    for start, end, inside, tf_op in pt.round_ops(planes, platform,
                                                  [gradient_program]):
        if end <= start:
            continue
        while stack and stack[-1][2] <= start:
            pop()
        if stack:
            end = min(end, stack[-1][2])
            stack[-1][3] += end - start
        stack.append([part_of(tf_op) if inside else None, start, end, 0.0])
    while stack:
        pop()
    return out


@functools.lru_cache(maxsize=1)
def _parts_once(platform: str, gradient_program: str):
    planes = pt.last_trace()
    if planes is None:
        return None
    parts = part_self_seconds(planes, platform, gradient_program)
    if not any(parts):                # no rank.* scope on any op
        return None
    print("[bench] ranking gradient by rank.* scope, s in the traced "
          f"interval: { {k or '(none)': round(v, 4) for k, v in parts.items()} }",
          file=sys.stderr, flush=True)
    return parts


def part_ms(facts, parts: tuple):
    """Self ms a traced round under the given ``rank.<part>`` scopes; None
    when the run was not traced or no op carries a ``rank.`` scope."""
    trace, prog = facts.get("trace"), facts.get("gradient_program")
    if not trace or not trace.get("rounds") or not prog:
        return None
    sec = _parts_once(facts["platform"], prog)
    if sec is None:
        return None
    return 1e3 * sum(sec.get(p, 0.0) for p in parts) / trace["rounds"]


def gradient_span_stats(planes) -> list:
    """The args of every ``round/gradient`` span of the trace (``objective``,
    ``groups``, ``layout_key_ms``), in order."""
    out = []
    for plane in planes:
        for line in plane["lines"]:
            stats = line.get("stats") or []
            out += [st for (name, _s, _d), st in zip(line["events"], stats)
                    if name == "round/gradient"]
    return out
