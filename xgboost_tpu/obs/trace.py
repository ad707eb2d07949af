"""Program spans and stage scopes: names that reach the profiler's trace.

**The switch is the profiler session.** Every ``span()`` site opens a
``jax.profiler.TraceAnnotation`` (a ``StepTraceAnnotation`` when ``step=``
is given): with no ``jax.profiler`` session running that is one inert
object, about a third of a microsecond a site; with one running, the span
lands in the trace's host plane on the profiler's own clock, beside the
device lines, with its ``args`` as stats. Nothing has to be set before
import for that.

Stages *inside* one jitted program are named at trace time with
:func:`stage` (``jax.named_scope("xtpu.<stage>")``): HLO metadata only,
no value changes. On a TPU the scope path arrives in the trace as the
``tf_op`` stat of each op's event metadata; a device op belongs to the
innermost ``xtpu.`` scope on that path. :data:`STAGES` lists every stage
the program may emit, with its nesting.

The **ring** is the operator's offline exporter, on the process clock
(``time.perf_counter``): one process-wide :class:`Tracer` that, when
enabled, also records every span into a fixed-capacity ring for export as
Chrome/Perfetto JSON or jsonl, for the flight recorder's merged timelines
and the crash black box (``obs/flight.py``). It is OFF by default; a span
then leaves no ring record.

Knobs of the ring (read at import; flip programmatically with
:func:`enable` / :func:`disable` mid-process):

- ``XTPU_TRACE``      — ``1`` enables the ring (default ``0``).
- ``XTPU_TRACE_BUF``  — ring capacity in spans (default ``65536``);
  the ring keeps the newest spans when it wraps.
- ``XTPU_TRACE_OUT``  — path to auto-export on process exit;
  ``*.jsonl`` writes one span per line, anything else writes
  Chrome/Perfetto trace JSON (load in ``ui.perfetto.dev``).
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from . import metrics as _metrics

__all__ = ["Span", "Tracer", "enable", "disable", "enabled", "tracer",
           "span", "phase", "instant", "export", "reset", "set_identity",
           "STAGES", "ROUND_ROOTS", "stage", "opened_stages",
           "RANK_SCOPES", "rank_scope", "MESH_SCOPES", "mesh_scope"]

# Every ``xtpu.<stage>`` scope a compiled program may carry. A device op
# belongs to the INNERMOST one on its path. Nesting, outermost first:
#
#   gradient | grow | leaf | margin          the round programs (core.py)
#     grow >  advance_hist | hist            one data sweep of a level
#               > advance | quantise | kernel.<name> | fold
#             exchange | window | refine | eval | delta
#             advance > kernel.advance_leaf     below the last level
#
# ``sort``, ``count_sort``, ``permute`` and ``kernel.scan_hist``: no
# program opens these since PR 31; the benchmark's recorded PR 27 trace
# names them.
KERNELS = ("build_hist", "build_hist_int8", "fused_advance_coarse",
           "scan_hist", "advance_leaf")   # the round programs', by ``name=``
STAGES = (
    "gradient", "grow", "leaf", "margin",
    "sort", "advance_hist", "hist",
    "advance", "count_sort", "permute", "quantise", "fold",
    "exchange", "window", "refine", "eval", "delta",
) + tuple("kernel." + k for k in KERNELS)
_STAGE_SET = frozenset(STAGES)

# What a reader of a device trace holds an executable's scopes to. jax's
# persistent compile cache leaves metadata out of its key, so an executable
# it serves carries the scopes of the source that WROTE the entry. Every
# scoped op of a round program starts its path with one of ROUND_ROOTS
# (``core._fused_round_body`` opens nothing else at its top), and names
# only stages this process has opened: an op that does neither proves an
# executable of other source.
ROUND_ROOTS = ("gradient", "grow", "leaf", "margin")
_opened: set = set()


@contextlib.contextmanager
def _stage(name: str):
    _opened.add(name)
    with jax.named_scope("xtpu." + name):
        yield


def stage(name: str):
    """``jax.named_scope("xtpu.<name>")`` for a stage in :data:`STAGES`
    (a context manager, or a decorator); any other name raises at trace
    time, so a scope no reader knows cannot be added by accident."""
    if name not in _STAGE_SET:
        raise ValueError(f"unknown stage {name!r}: add it to "
                         "xgboost_tpu.obs.trace.STAGES (and to the readers "
                         "under benchmark/lib/program_trace.py)")
    return _stage(name)


def opened_stages() -> frozenset:
    """The stages this process has opened while tracing, so far."""
    return frozenset(_opened)


# The ranking gradient's parts (``objective/ranking.py``), one level below
# ``xtpu.gradient``: ``rank.<part>``. They are NOT stages: the name carries
# no ``xtpu.`` prefix, so a reader that books an op to the innermost
# ``xtpu.<stage>`` on its path still books the whole gradient to
# ``gradient``, and a reader of the parts takes the innermost ``rank.``
# scope. ``layout``: rows gathered into the padded ``[G, L]`` buffers;
# ``order``: the per-group sorts (by score into rank order and back by
# slot under ``topk``), the label sort and the ideal DCG;
# ``pairs``: the pair block (``[C, K, L]`` in rank order under ``topk``,
# K anchors a group) and the chunk loop around it;
# ``reduce``: the padded sums gathered back to rows.
RANK_SCOPES = ("layout", "order", "pairs", "reduce")


def rank_scope(name: str):
    """``jax.named_scope("rank.<name>")`` for a part in :data:`RANK_SCOPES`;
    any other name raises at trace time, as :func:`stage` does."""
    if name not in RANK_SCOPES:
        raise ValueError(f"unknown ranking scope {name!r}: add it to "
                         "xgboost_tpu.obs.trace.RANK_SCOPES")
    return jax.named_scope("rank." + name)


# The row-split collectives of the mesh grow program (``tree/grow.py _grow``
# under ``shard_map``), one level below the ``xtpu.`` stage they sit in:
# ``mesh.<what>``. Like ``rank.``, no ``xtpu.`` prefix, so the stage readers
# keep booking the ops to their stage and a reader of the collectives takes
# the innermost ``mesh.`` scope. ``hist_psum``: a level's histogram sums
# across the row shards; ``root_psum``: the root's gradient sums;
# ``scale_pmax``: the int8x2 quantisation scale every shard must share.
MESH_SCOPES = ("hist_psum", "root_psum", "scale_pmax")


def mesh_scope(name: str):
    """``jax.named_scope("mesh.<name>")`` for a collective in
    :data:`MESH_SCOPES`; any other name raises at trace time."""
    if name not in MESH_SCOPES:
        raise ValueError(f"unknown mesh scope {name!r}: add it to "
                         "xgboost_tpu.obs.trace.MESH_SCOPES")
    return jax.named_scope("mesh." + name)


class Span:
    """One finished span: ``[t0, t1)`` seconds on ``time.perf_counter``'s
    clock, ``depth`` = nesting level within the recording thread."""

    __slots__ = ("name", "cat", "t0", "t1", "depth", "tid", "args")

    def __init__(self, name: str, cat: str, t0: float, t1: float,
                 depth: int, tid: int, args: Optional[Dict[str, Any]]):
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.t1 = t1
        self.depth = depth
        self.tid = tid
        self.args = args

    @property
    def dur(self) -> float:
        return self.t1 - self.t0

    def to_dict(self) -> Dict[str, Any]:
        d = {"name": self.name, "cat": self.cat, "t0": self.t0,
             "t1": self.t1, "dur": self.t1 - self.t0, "depth": self.depth,
             "tid": self.tid}
        if self.args:
            d["args"] = self.args
        return d


class _LiveSpan:
    """Enabled-path context manager: one per ``with span(...)`` block."""

    __slots__ = ("_tr", "name", "cat", "args", "_t0", "_ann")

    def __init__(self, tr: "Tracer", name: str, cat: str,
                 args: Optional[Dict[str, Any]], ann):
        self._tr = tr
        self.name = name
        self.cat = cat
        self.args = args
        self._ann = ann

    def __enter__(self):
        self._ann.__enter__()
        tl = self._tr._tl
        tl.depth = getattr(tl, "depth", 0) + 1
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        tl = self._tr._tl
        depth = getattr(tl, "depth", 1)
        tl.depth = depth - 1
        self._ann.__exit__(*exc)
        self._tr._record(Span(self.name, self.cat, self._t0, t1,
                              depth - 1, threading.get_ident(), self.args))
        return False


class Tracer:
    """Fixed-capacity ring of :class:`Span` records."""

    def __init__(self, capacity: int = 65536) -> None:
        self.capacity = max(int(capacity), 1)
        self._buf: List[Optional[Span]] = [None] * self.capacity
        self._n = 0                       # total spans ever recorded
        self._lock = threading.Lock()
        self._tl = threading.local()
        self._epoch = time.perf_counter()  # export time base
        self.rank: Optional[int] = None    # distributed identity (flight)
        self.world: Optional[int] = None

    def set_identity(self, rank: int, world: int) -> None:
        """Tag this ring with its ``(rank, world)`` — exported spans and
        Perfetto events carry the identity so N rings stay attributable
        after :func:`~.flight.merge_rings`."""
        self.rank = int(rank)
        self.world = int(world)

    # ------------------------------------------------------------ recording
    def span(self, name: str, cat: str = "",
             args: Optional[Dict[str, Any]] = None,
             step: Optional[int] = None) -> _LiveSpan:
        return _LiveSpan(self, name, cat, args, _annotation(name, args, step))

    def instant(self, name: str, cat: str = "",
                args: Optional[Dict[str, Any]] = None) -> None:
        t = time.perf_counter()
        self._record(Span(name, cat, t, t,
                          getattr(self._tl, "depth", 0),
                          threading.get_ident(), args))

    def _record(self, sp: Span) -> None:
        with self._lock:
            self._buf[self._n % self.capacity] = sp
            self._n += 1

    # ------------------------------------------------------------- reading
    def __len__(self) -> int:
        return min(self._n, self.capacity)

    @property
    def dropped(self) -> int:
        """Spans the ring overwrote (0 until it wraps)."""
        return max(self._n - self.capacity, 0)

    def spans(self) -> List[Span]:
        """Chronological copy of the ring's current contents."""
        with self._lock:
            n, cap = self._n, self.capacity
            if n <= cap:
                return [s for s in self._buf[:n]]
            i = n % cap
            return self._buf[i:] + self._buf[:i]

    def clear(self) -> None:
        with self._lock:
            self._buf = [None] * self.capacity
            self._n = 0
            self._epoch = time.perf_counter()

    # ------------------------------------------------------------- export
    def to_perfetto(self) -> Dict[str, Any]:
        """Chrome/Perfetto trace-event JSON (``ph: "X"`` complete events,
        microsecond timestamps relative to the tracer epoch)."""
        events = []
        pid = os.getpid()
        if self.rank is not None:
            events.append({"name": "process_name", "ph": "M", "pid": pid,
                           "args": {"name": f"rank {self.rank}/"
                                            f"{self.world}"}})
        for s in self.spans():
            ev: Dict[str, Any] = {
                "name": s.name, "ph": "X", "pid": pid, "tid": s.tid,
                "ts": (s.t0 - self._epoch) * 1e6,
                "dur": (s.t1 - s.t0) * 1e6,
            }
            if s.cat:
                ev["cat"] = s.cat
            if s.args:
                ev["args"] = dict(s.args)
            if self.rank is not None:
                ev.setdefault("args", {})["rank"] = self.rank
            events.append(ev)
        return {"displayTimeUnit": "ms", "traceEvents": events}

    def dump(self, path: str) -> int:
        """Write the ring to ``path``: jsonl (one span dict per line) when
        the name ends in ``.jsonl``, Perfetto JSON otherwise. Returns the
        number of spans written."""
        spans = self.spans()
        if path.endswith(".jsonl"):
            with open(path, "w", encoding="utf-8") as fh:
                for s in spans:
                    d = s.to_dict()
                    if self.rank is not None:
                        d["rank"], d["world"] = self.rank, self.world
                    fh.write(json.dumps(d) + "\n")
        else:
            with open(path, "w", encoding="utf-8") as fh:
                json.dump(self.to_perfetto(), fh)
        return len(spans)


# ------------------------------------------------------- module-level state

_tracer: Optional[Tracer] = None


def enable(capacity: Optional[int] = None) -> Tracer:
    """Turn tracing on (idempotent); returns the live tracer."""
    global _tracer
    if _tracer is None or (capacity is not None
                           and _tracer.capacity != int(capacity)):
        _tracer = Tracer(capacity if capacity is not None
                         else _default_capacity())
    return _tracer


def disable() -> None:
    global _tracer
    _tracer = None


def enabled() -> bool:
    return _tracer is not None


def tracer() -> Optional[Tracer]:
    return _tracer


def _annotation(name: str, args: Optional[Dict[str, Any]],
                step: Optional[int]):
    if step is not None:
        return StepTraceAnnotation(name, step_num=step, **(args or {}))
    return TraceAnnotation(name, **args) if args else TraceAnnotation(name)


def span(name: str, cat: str = "", args: Optional[Dict[str, Any]] = None,
         step: Optional[int] = None):
    """The one instrumentation entry point: a context manager that puts
    ``name`` (with ``args`` as stats) on the profiler's host timeline when
    a ``jax.profiler`` session is running, and is one inert annotation
    when none is. ``step``: make it a ``StepTraceAnnotation`` with that
    ``step_num`` (the ``round`` span). With the ring enabled the span is
    also recorded there, on the process clock."""
    t = _tracer
    if t is None:
        return _annotation(name, args, step)
    return t.span(name, cat, args, step)


def phase(name: str, cat: str = "", args: Optional[Dict[str, Any]] = None,
          step: Optional[int] = None):
    """A span that is also booked when nothing is listening: the same
    annotation ``span()`` opens (profiler and ring see it as before), and on
    exit its self time (its duration less the phases that ran inside it on
    this thread) goes to ``xtpu_phase_seconds_total{phase}`` and one to
    ``xtpu_phase_total{phase}``. For the coarse parts of a job's set-up and
    the ``round`` span, never for anything inside a round: two clock reads
    and two counter adds a site. Off the thread that entered the library it
    is a plain span."""
    return _metrics.Phase(name, span(name, cat, args, step))


def record_interval(name: str, secs: float,
                    args: Optional[Dict[str, Any]] = None) -> None:
    """A phase that jax timed itself (``program/trace_lower``,
    ``program/compile``: ``obs/metrics.py``'s listeners), just ended: into
    the ring as ``[now - secs, now)``, where the ring is on."""
    t = _tracer
    if t is not None:
        now = time.perf_counter()
        t._record(Span(name, "program", now - secs, now,
                       getattr(t._tl, "depth", 0), threading.get_ident(),
                       args))


def instant(name: str, cat: str = "",
            args: Optional[Dict[str, Any]] = None) -> None:
    """Zero-duration marker (retry events, promotions)."""
    t = _tracer
    if t is not None:
        t.instant(name, cat, args)


def export(path: Optional[str] = None) -> int:
    """Dump the current ring (0 spans when tracing is off). Default path:
    ``XTPU_TRACE_OUT`` or ``xtpu_trace.json``."""
    t = _tracer
    if t is None:
        return 0
    return t.dump(path or _OUT or "xtpu_trace.json")


def reset() -> None:
    """Clear the ring, keeping tracing in its current on/off state."""
    t = _tracer
    if t is not None:
        t.clear()


def set_identity(rank: int, world: int) -> None:
    """Tag the global tracer (if enabled) with its distributed identity;
    the flight recorder calls this once rank/world are known."""
    t = _tracer
    if t is not None:
        t.set_identity(rank, world)


def _default_capacity() -> int:
    try:
        return int(os.environ.get("XTPU_TRACE_BUF", 65536))
    except ValueError:
        return 65536


_OUT = os.environ.get("XTPU_TRACE_OUT") or None

if os.environ.get("XTPU_TRACE", "0") not in ("0", ""):
    enable()
    if _OUT:
        import atexit

        atexit.register(export, _OUT)
