"""One process-wide metrics registry + Prometheus text exposition.

Every subsystem that counts things — serve's :class:`ServeMetrics`,
the pipeline loop, the paged prefetch ring, the recompile counter, the
resilient communicator — *registers a collector* here instead of
growing its own ad-hoc snapshot format. Collection is pull-based (the
Prometheus model): sources keep their native state behind their native
locks and hand the registry a locked read on demand, so registration
adds zero cost to the hot paths and a dead source (GC'd server, closed
communicator) silently drops out via its weakref.

Exposition follows the Prometheus text format 0.0.4: ``# HELP`` /
``# TYPE`` headers, ``_total`` counter suffixes, histograms as
cumulative ``_bucket{le=...}`` series plus ``_sum``/``_count``. When
two live sources emit the same (name, labels) sample — two servers in
one test process — counter/histogram samples are summed and gauges keep
the last value collected. ``tools/validate_obs.py`` lints the rendered
output; docs/observability.md has the metric glossary.
"""

from __future__ import annotations

import math
import os
import threading
import time
import weakref
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax.monitoring

__all__ = ["Sample", "Family", "HistogramData", "MetricsRegistry",
           "get_registry", "render_families", "count_degrade",
           "degrade_counts", "count_round_dispatch", "count_tree_flush",
           "count_grow_schedule", "grow_schedule_counts",
           "count_grow_epilogue", "grow_epilogue_counts",
           "count_eval_walk", "eval_walk_counts",
           "count_rank_gradient", "rank_counts",
           "count_mesh_dispatch", "set_mesh_layout", "mesh_counts",
           "program_compile_counts", "Phase", "phase_seconds",
           "phase_counts", "book_import", "freeze_startup",
           "startup_report"]

LabelSet = Tuple[Tuple[str, str], ...]


class HistogramData:
    """One histogram labelset: cumulative ``(le, count)`` pairs (the final
    edge must be ``inf``), plus sum and count."""

    __slots__ = ("buckets", "sum", "count")

    def __init__(self, buckets: List[Tuple[float, int]], sum_: float,
                 count: int) -> None:
        self.buckets = buckets
        self.sum = sum_
        self.count = count


class Sample:
    __slots__ = ("labels", "value")

    def __init__(self, value, labels: LabelSet = ()) -> None:
        self.labels = labels
        self.value = value  # number, or HistogramData for histograms


class Family:
    """One metric family: a name, a kind, and its samples."""

    __slots__ = ("name", "kind", "help", "samples")

    def __init__(self, name: str, kind: str, help: str,
                 samples: Iterable[Sample]) -> None:
        assert kind in ("counter", "gauge", "histogram"), kind
        self.name = name
        self.kind = kind
        self.help = help
        self.samples = list(samples)


_NAME_OK = "abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_:"


def sanitize(name: str) -> str:
    out = "".join(ch if ch in _NAME_OK else "_" for ch in name)
    return out if out and not out[0].isdigit() else "_" + out


def _fmt_value(v) -> str:
    if v == math.inf:
        return "+Inf"
    if v == -math.inf:
        return "-Inf"
    f = float(v)
    if f.is_integer() and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _fmt_labels(labels: LabelSet, extra: Optional[Tuple[str, str]] = None
                ) -> str:
    items = list(labels) + ([extra] if extra else [])
    if not items:
        return ""
    parts = []
    for k, v in items:
        ve = str(v).replace("\\", r"\\").replace('"', r'\"') \
                   .replace("\n", r"\n")
        parts.append(f'{sanitize(k)}="{ve}"')
    return "{" + ",".join(parts) + "}"


def render_families(families: List[Family]) -> str:
    """Prometheus text exposition 0.0.4 for a merged family list."""
    lines: List[str] = []
    for fam in sorted(families, key=lambda f: f.name):
        name = sanitize(fam.name)
        if fam.help:
            lines.append(f"# HELP {name} {fam.help}")
        lines.append(f"# TYPE {name} {fam.kind}")
        for s in fam.samples:
            if fam.kind == "histogram":
                h: HistogramData = s.value
                for le, cum in h.buckets:
                    lines.append(
                        f"{name}_bucket"
                        f"{_fmt_labels(s.labels, ('le', _fmt_value(le)))}"
                        f" {cum}")
                lines.append(f"{name}_sum{_fmt_labels(s.labels)} "
                             f"{_fmt_value(h.sum)}")
                lines.append(f"{name}_count{_fmt_labels(s.labels)} "
                             f"{h.count}")
            else:
                lines.append(f"{name}{_fmt_labels(s.labels)} "
                             f"{_fmt_value(s.value)}")
    return "\n".join(lines) + "\n"


class MetricsRegistry:
    """Collector registry + a small set of direct counters/gauges.

    Direct counters (:meth:`inc`/:meth:`set_gauge`) serve code that has
    no natural stats object of its own (retry events, checkpoint
    flushes); everything stateful registers a collector instead.
    """

    def __init__(self) -> None:
        self._lock = threading.RLock()
        # name -> (kind, help); shared across direct metrics
        self._meta: Dict[str, Tuple[str, str]] = {}
        self._counters: Dict[Tuple[str, LabelSet], float] = {}
        self._gauges: Dict[Tuple[str, LabelSet], float] = {}
        # id -> (weakref-to-owner | None, collect(owner) -> List[Family])
        self._sources: Dict[int, Tuple[Optional[weakref.ref], Callable]] = {}
        self._next_id = 0

    # -------------------------------------------------------- direct metrics
    def inc(self, name: str, by: float = 1.0, labels: LabelSet = (),
            help: str = "") -> None:
        with self._lock:
            self._meta.setdefault(name, ("counter", help))
            key = (name, labels)
            self._counters[key] = self._counters.get(key, 0.0) + by

    def set_gauge(self, name: str, value: float, labels: LabelSet = (),
                  help: str = "") -> None:
        with self._lock:
            self._meta.setdefault(name, ("gauge", help))
            self._gauges[(name, labels)] = float(value)

    def get(self, name: str, labels: LabelSet = (), default: float = 0.0
            ) -> float:
        with self._lock:
            key = (name, labels)
            if key in self._counters:
                return self._counters[key]
            return self._gauges.get(key, default)

    # ------------------------------------------------------------ collectors
    def register(self, collect: Callable[..., List[Family]],
                 owner: Optional[object] = None) -> int:
        """Add a collector. With ``owner``, ``collect(owner)`` is called
        on each collection and the registration dies with the owner
        (weakref — pass the *unbound* function, not a bound method).
        Without, ``collect()`` is called until :meth:`unregister`."""
        with self._lock:
            sid = self._next_id
            self._next_id += 1
            ref = None
            if owner is not None:
                ref = weakref.ref(owner, lambda _r, s=sid: self.unregister(s))
            self._sources[sid] = (ref, collect)
            return sid

    def unregister(self, sid: int) -> None:
        with self._lock:
            self._sources.pop(sid, None)

    # ------------------------------------------------------------ collection
    def collect(self) -> List[Family]:
        """Merged family list: direct metrics + every live collector.
        Duplicate (name, labels) samples sum (counters/histograms) or
        keep the last value (gauges)."""
        with self._lock:
            metas = dict(self._meta)
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            sources = list(self._sources.values())
        raw: List[Family] = []
        for name, (kind, hlp) in metas.items():
            store = counters if kind == "counter" else gauges
            samples = [Sample(v, lbls) for (n, lbls), v in store.items()
                       if n == name]
            if samples:
                raw.append(Family(name, kind, hlp, samples))
        for ref, fn in sources:
            if ref is not None:
                owner = ref()
                if owner is None:
                    continue
                fams = fn(owner)
            else:
                fams = fn()
            raw.extend(fams or [])
        return _merge(raw)

    def render_prometheus(self) -> str:
        return render_families(self.collect())

    def snapshot(self) -> Dict[str, Any]:
        """JSON-friendly view of every collected sample (debug surface;
        the exposition format is the contract)."""
        out: Dict[str, Any] = {}
        for fam in self.collect():
            for s in fam.samples:
                key = fam.name + "".join(f"{{{k}={v}}}" for k, v in s.labels)
                if isinstance(s.value, HistogramData):
                    out[key] = {"count": s.value.count,
                                "sum": s.value.sum}
                else:
                    out[key] = s.value
        return out


def _merge(raw: List[Family]) -> List[Family]:
    by_name: Dict[str, Family] = {}
    for fam in raw:
        cur = by_name.get(fam.name)
        if cur is None:
            by_name[fam.name] = Family(fam.name, fam.kind, fam.help,
                                       fam.samples)
            continue
        by_label: Dict[LabelSet, Sample] = {s.labels: s for s in cur.samples}
        for s in fam.samples:
            old = by_label.get(s.labels)
            if old is None:
                by_label[s.labels] = s
            elif cur.kind == "counter":
                by_label[s.labels] = Sample(old.value + s.value, s.labels)
            elif cur.kind == "histogram":
                by_label[s.labels] = Sample(_merge_hist(old.value, s.value),
                                            s.labels)
            else:  # gauge: last write wins
                by_label[s.labels] = s
        cur.samples = list(by_label.values())
    return list(by_name.values())


def _merge_hist(a: HistogramData, b: HistogramData) -> HistogramData:
    if len(a.buckets) != len(b.buckets):  # mismatched layouts: keep newest
        return b
    buckets = [(le, ca + cb) for (le, ca), (_, cb)
               in zip(a.buckets, b.buckets)]
    return HistogramData(buckets, a.sum + b.sum, a.count + b.count)


_registry = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-wide registry every source registers into."""
    return _registry


_DEGRADES = "xtpu_degrades_total"
DEGRADE_PATHS = ("insight_disarm", "paged_collapse")


def count_degrade(path: str) -> None:
    """Count one run that caught a failure and kept going on a slower
    tier. These are the only two such handlers on the training path (the
    insight-armed round disarming, the paged resident collapse dropping to
    streaming); anything that reports on the chip asserts both stay zero
    (``chip_smoke.py``)."""
    if path not in DEGRADE_PATHS:
        raise ValueError(f"unknown degrade path {path!r}")
    _registry.inc(_DEGRADES, labels=(("path", path),),
                  help="failures caught by a handler that continued on a "
                       "slower tier, by path")


def degrade_counts() -> Dict[str, int]:
    return {p: int(_registry.get(_DEGRADES, (("path", p),)))
            for p in DEGRADE_PATHS}


# ---- the round driver's counts, at the boundaries its spans mark ----------
# For the operator of a process that trains and serves at once (the
# pipeline loop scraped through serve's ``GET /metrics``): rounds/s of a
# train tick, an alert on ``program="general"`` (a tick that left the fused
# round programs by CONFIGURATION; ``xtpu_degrades_total`` counts only the
# two handlers that caught a failure), flushes a round. 1.1 us a dispatch
# and 0.7 us a flush (CPU, 200k calls), whatever the length of a round.

def count_round_dispatch(program: str, rounds: int = 1) -> None:
    """One dispatch of a round program (``program``: the jitted function's
    name, or ``general`` for the unfused path) that boosted ``rounds``."""
    _registry.inc("xtpu_round_dispatches_total",
                  labels=(("program", program),),
                  help="round-program dispatches, by program")
    _registry.inc("xtpu_rounds_total", by=rounds,
                  help="boosting rounds completed")


def count_tree_flush() -> None:
    _registry.inc("xtpu_tree_flushes_total",
                  help="device-to-host pulls of pending trees")


_GROW_SCHEDULE = "xtpu_grow_schedule_total"


def count_grow_schedule(schedule: str) -> None:
    """One trace of the depth-wise grow program under ``schedule``
    (``tree/grow.py Schedule.name``): what ``hist_method`` resolved to at
    the shape and on the backend it was traced for. Counted on the host
    while jax traces, so a steady window pays nothing."""
    _registry.inc(_GROW_SCHEDULE, labels=(("schedule", schedule),),
                  help="grow programs traced, by histogram schedule")


_GROW_EPILOGUE = "xtpu_grow_epilogue_total"


def count_grow_epilogue(kind: str) -> None:
    """One trace of the depth-wise grow program, by what advances the rows
    below its LAST level (``ops/histogram.py advance_leaf``): ``kernel``
    (one Mosaic sweep that also writes the leaf delta), ``walk`` (the
    per-row gather walk), ``dense`` (the matmul advance) or ``none`` (no
    deferred advance: the one-pass schedules). Counted while jax traces,
    beside ``count_grow_schedule``."""
    _registry.inc(_GROW_EPILOGUE, labels=(("kind", kind),),
                  help="grow programs traced, by last-level advance")


def _by_label(name: str, label: str) -> Dict[str, float]:
    """``{value of label: count}`` over every series of counter ``name``."""
    with _registry._lock:
        counters = dict(_registry._counters)
    return {dict(labels)[label]: value
            for (n, labels), value in counters.items() if n == name}


def grow_schedule_counts() -> Dict[str, int]:
    return {k: int(v) for k, v in _by_label(_GROW_SCHEDULE, "schedule").items()}


def grow_epilogue_counts() -> Dict[str, int]:
    return {k: int(v) for k, v in _by_label(_GROW_EPILOGUE, "kind").items()}


_CAT_SPLITS = "xtpu_cat_splits_total"
CAT_SPLIT_KINDS = ("partition", "onehot", "numeric")


def count_cat_splits(partition: int, onehot: int, numeric: int) -> None:
    """The splits of grown depthwise trees, by kind: ``partition`` (a
    categorical column's sorted-partition left set), ``onehot`` (one
    category against the rest) and ``numeric`` (a threshold). Counted on
    the host where a tree is pulled from the device
    (``tree/grow.py TreeGrower.to_tree_model``), from the arrays pulled: no
    device pass of its own."""
    for kind, n in zip(CAT_SPLIT_KINDS, (partition, onehot, numeric)):
        if n:
            _registry.inc(_CAT_SPLITS, by=n, labels=(("kind", kind),),
                          help="splits of grown trees, by kind")


def cat_split_counts() -> Dict[str, int]:
    return {k: int(v) for k, v in _by_label(_CAT_SPLITS, "kind").items()}


_FUSED_BOUNDARY = "xtpu_fused_boundary_total"
FUSED_BOUNDARY_BODIES = ("kernel", "xla")


def count_fused_boundary(body: str) -> None:
    """One level boundary of a traced grow program under the ``fused``
    schedule, by what ``ops/histogram.py fused_advance_coarse`` took for
    it: ``kernel`` (the Mosaic sweep: advance and the new level's coarse
    histogram from one read of the bin tile) or ``xla`` (the XLA body: the
    advance, then ``coarse_bin_ids`` over the whole matrix and an unfused
    coarse build). Counted while jax traces, beside ``count_grow_schedule``."""
    if body not in FUSED_BOUNDARY_BODIES:
        raise ValueError(f"unknown boundary body {body!r}")
    _registry.inc(_FUSED_BOUNDARY, labels=(("body", body),),
                  help="level boundaries of traced fused grow programs, by "
                       "what advanced the rows and built the coarse "
                       "histogram")


def fused_boundary_counts() -> Dict[str, int]:
    return {k: int(v) for k, v in _by_label(_FUSED_BOUNDARY, "body").items()}


_REFINE_REMAP = "xtpu_refine_remap_total"
REFINE_REMAP_WHERE = ("kernel", "xla")


def count_refine_remap(where: str) -> None:
    """One refine build of the two-level search (``ops/histogram.py
    build_hist(window=...)``), by where the raw bin ids were remapped into
    the node's window: ``kernel`` (in VMEM inside the int8x2 Pallas
    kernel, from the resident tile: no remapped copy in HBM) or ``xla``
    (``refine_bin_ids`` over the whole matrix ahead of the build: the CPU,
    levels past 128 nodes, the f32/bf16 kernels). Counted while jax
    traces, as ``count_fused_boundary``: one a refined level of a traced
    grow program."""
    if where not in REFINE_REMAP_WHERE:
        raise ValueError(f"unknown refine remap {where!r}")
    _registry.inc(_REFINE_REMAP, labels=(("where", where),),
                  help="refine builds of traced programs, by where the "
                       "bin ids were remapped into the window")


def refine_remap_counts() -> Dict[str, int]:
    return {k: int(v) for k, v in _by_label(_REFINE_REMAP, "where").items()}


_HIST_ONEHOT = "xtpu_hist_onehot_total"
HIST_ONEHOT_BUILDS = ("swar", "compare")


def count_hist_onehot(build: str) -> None:
    """One trace of a Pallas histogram kernel's wrapper (``ops/pallas/
    histogram.py build_hist_pallas``, ``fused_advance_coarse_pallas``), by
    how its kernel builds the bin one-hot: ``swar`` (four bins a uint32 word,
    a zero-byte detect: the width is a multiple of 4 and at most 256) or
    ``compare`` (a ``[B, R]`` int32 compare: every other width, 257 slots
    among them, and the f32/bf16 kernels). The wrappers are jitted, so a
    width and node count traced before in the process is not counted
    again."""
    if build not in HIST_ONEHOT_BUILDS:
        raise ValueError(f"unknown one-hot build {build!r}")
    _registry.inc(_HIST_ONEHOT, labels=(("build", build),),
                  help="Pallas histogram kernels traced, by one-hot build")


def hist_onehot_counts() -> Dict[str, int]:
    return {k: int(v) for k, v in _by_label(_HIST_ONEHOT, "build").items()}


_HIST_DOT = "xtpu_hist_dot_total"
_HIST_DOT_ROWS = "xtpu_hist_dot_rows"
HIST_DOT_FORMS = ("feature", "stacked")


def count_hist_dot(form: str, rows: int) -> None:
    """One trace of a Pallas histogram kernel's wrapper, by the form of its
    histogram dot (``ops/pallas/histogram.py _dot_features``): ``feature``
    (one feature's one-hot a dot) or ``stacked`` (eight features' SWAR
    one-hots, unpadded, in one dot: every width up to 64 slots, the
    two-level search's 20 and 36 among them). ``rows`` is the one-hot rows the
    kernel's dot contracts; the gauge keeps the most seen in the process.
    Counted as ``count_hist_onehot`` counts: a shape traced before is not
    counted again."""
    if form not in HIST_DOT_FORMS:
        raise ValueError(f"unknown histogram dot form {form!r}")
    _registry.inc(_HIST_DOT, labels=(("form", form),),
                  help="Pallas histogram kernels traced, by dot form")
    if rows > _registry.get(_HIST_DOT_ROWS):
        _registry.set_gauge(_HIST_DOT_ROWS, rows,
                            help="most one-hot rows one traced histogram "
                                 "dot contracts")


def hist_dot_counts() -> Dict[str, int]:
    return {k: int(v) for k, v in _by_label(_HIST_DOT, "form").items()}


def hist_dot_rows() -> int:
    return int(_registry.get(_HIST_DOT_ROWS))


_HIST_BODY = "xtpu_hist_body_features"


def note_hist_body_features(features: int) -> None:
    """The features one traced Pallas histogram kernel body unrolls
    (``ops/pallas/histogram.py``: a feature block of ``build_hist_pallas``,
    a group of ``fused_advance_coarse_pallas``). The gauge keeps the largest
    seen in the process: tracing, lowering and Mosaic's compile are linear
    in it, and ``FEATURE_GROUP`` is its ceiling."""
    if features > _registry.get(_HIST_BODY):
        _registry.set_gauge(_HIST_BODY, features,
                            help="largest number of features a traced "
                                 "histogram kernel body unrolls")


def hist_body_features() -> int:
    return int(_registry.get(_HIST_BODY))


_KERNEL_VMEM = "xtpu_kernel_vmem_bytes"


def note_kernel_vmem(kernel: str, nbytes: int) -> None:
    """The scoped VMEM one traced Mosaic histogram kernel compiles under
    (``ops/pallas/histogram.py``, by its ``name``): the ``vmem_limit_bytes``
    its wrapper states, or the compiler's default where it states none.
    Counted while jax traces, as ``note_hist_body_features``; the gauge keeps
    the largest seen in the process for each kernel."""
    labels = (("kernel", kernel),)
    if nbytes > _registry.get(_KERNEL_VMEM, labels):
        _registry.set_gauge(_KERNEL_VMEM, nbytes, labels,
                            help="largest scoped VMEM a traced Mosaic "
                                 "histogram kernel states or is sized to, "
                                 "by kernel")


def kernel_vmem_bytes() -> Dict[str, int]:
    """``{kernel: bytes}`` of ``xtpu_kernel_vmem_bytes``."""
    with _registry._lock:
        gauges = dict(_registry._gauges)
    return {dict(labels)["kernel"]: int(value)
            for (name, labels), value in gauges.items()
            if name == _KERNEL_VMEM}


_BINNED_MISSING = "xtpu_binned_missing_ratio"
_BINNED_BYTES = "xtpu_binned_bin_bytes"


def set_binned_layout(missing: int, entries: int, bin_bytes: int) -> None:
    """The bin matrix the last binning pass made (``data/binned.py
    BinnedMatrix.from_dense``, ``DMatrix._init_from_iter``): the missing
    entries the pass counted on the host over all entries, and the bytes a
    value takes in the matrix. Host arithmetic, no device pull."""
    _registry.set_gauge(_BINNED_MISSING, missing / entries if entries else 0.0,
                        help="missing entries / entries of the last matrix "
                             "binned")
    _registry.set_gauge(_BINNED_BYTES, bin_bytes,
                        help="bytes a value takes in the last matrix binned")


def binned_layout() -> Dict[str, float]:
    return {"missing_ratio": _registry.get(_BINNED_MISSING),
            "bin_bytes": int(_registry.get(_BINNED_BYTES))}


_EVAL_WALK = "xtpu_eval_walk_total"


def count_eval_walk(kind: str) -> None:
    """One margin increment of new trees over a binned non-training matrix
    (``boosting/gbtree.py margin_delta_binned``: an eval set's rows every
    round), by the walk that computed it: ``heap`` (on the device from the
    pending trees' heap arrays, ``ops/histogram.py heap_walk_delta``) or
    ``forest`` (the trees flushed to the host and walked by
    ``ForestPredictor``'s per-row gathers)."""
    _registry.inc(_EVAL_WALK, labels=(("kind", kind),),
                  help="binned margin increments, by walk")


def eval_walk_counts() -> Dict[str, int]:
    return {k: int(v) for k, v in _by_label(_EVAL_WALK, "kind").items()}


_RANK_SLOTS = "xtpu_rank_pair_slots_total"
_RANK_KEPT = "xtpu_rank_pairs_kept_total"
_RANK_DISPATCHES = "xtpu_rank_gradient_dispatches_total"
_RANK_FILL = "xtpu_rank_layout_fill_ratio"
_RANK_LAYOUT_BYTES = "xtpu_rank_layout_bytes_total"


def count_rank_gradient(method: str, pair_slots: int, pairs_kept: int,
                        fill_ratio: float, layout_bytes: int) -> None:
    """One device dispatch of the ranking gradient (``objective/ranking.py``).
    ``pair_slots``: slots of the pair blocks it sweeps (steps x C x K x L
    under ``topk``: the ``[C, K, L]`` block in rank order, K = the truncation
    or L, whichever is less; steps x C x L x k under ``mean``).
    ``pairs_kept``: pairs
    the truncation admits inside the groups' real rows, from the group sizes
    alone: an upper bound on the pairs that carry a lambda (label ties are
    not counted out). ``layout_bytes``: what the dispatch moves between row
    order and the padded ``[Gp, L]`` layout, both ways (the scores in, the
    gradient and hessian sums out: each read once in one layout and written
    once in the other). All are host arithmetic on the cached layout: no
    device pull."""
    _registry.inc(_RANK_LAYOUT_BYTES, by=float(layout_bytes),
                  help="bytes the ranking gradient moves between row order "
                       "and its padded layout")
    _registry.inc(_RANK_DISPATCHES, labels=(("method", method),),
                  help="device dispatches of the ranking gradient, by pair "
                       "method")
    _registry.inc(_RANK_SLOTS, by=float(pair_slots),
                  help="pair-block slots swept by the ranking gradient")
    _registry.inc(_RANK_KEPT, by=float(pairs_kept),
                  help="pairs the truncation admits within real rows (upper "
                       "bound on pairs with a lambda)")
    _registry.set_gauge(_RANK_FILL, fill_ratio,
                        help="rows / (groups x longest group) of the padded "
                             "ranking layout")


def rank_counts() -> Dict[str, Any]:
    """The ranking counters as one dict: ``pair_slots``, ``pairs_kept``,
    ``layout_bytes``, ``fill_ratio`` and ``dispatches`` by method."""
    return {"pair_slots": _registry.get(_RANK_SLOTS),
            "pairs_kept": _registry.get(_RANK_KEPT),
            "layout_bytes": _registry.get(_RANK_LAYOUT_BYTES),
            "fill_ratio": _registry.get(_RANK_FILL),
            "dispatches": {k: int(v) for k, v in _by_label(
                _RANK_DISPATCHES, "method").items()}}


_MESH_ALLREDUCE = "xtpu_mesh_allreduce_total"
_MESH_BYTES = "xtpu_mesh_allreduce_bytes_total"
_MESH_SHARDS = "xtpu_mesh_shards"
_MESH_ROWS = "xtpu_mesh_rows_per_shard"


def count_mesh_dispatch(collectives: Dict[str, Tuple[int, int]]) -> None:
    """One dispatch of the row-split mesh grow program (``tree/grow.py
    TreeGrower._sharded``). ``collectives``: ``{what: (count, bytes)}`` of
    the program's collectives by their ``mesh.<what>`` scope, read once off
    the traced program (shapes only): bytes are one shard's operand, what
    each chip contributes to the exchange. Host arithmetic, no device pull."""
    for what, (count, nbytes) in collectives.items():
        labels = (("what", what),)
        _registry.inc(_MESH_ALLREDUCE, by=count, labels=labels,
                      help="row-split collectives dispatched, by mesh.* "
                           "scope")
        _registry.inc(_MESH_BYTES, by=float(nbytes), labels=labels,
                      help="operand bytes a shard handed to those "
                           "collectives")


def set_mesh_layout(shards: int, rows_per_shard: int) -> None:
    """The row-sharded training state as it was placed (``core.Booster.
    _make_sharded_train_state``)."""
    _registry.set_gauge(_MESH_SHARDS, shards,
                        help="devices the training rows are sharded over")
    _registry.set_gauge(_MESH_ROWS, rows_per_shard,
                        help="rows of the binned matrix a device holds, "
                             "pad rows included")


def mesh_counts() -> Dict[str, Any]:
    """The mesh counters as one dict: ``allreduce`` and ``bytes`` by what,
    ``shards`` and ``rows_per_shard``."""
    return {"allreduce": {k: int(v) for k, v in _by_label(
                _MESH_ALLREDUCE, "what").items()},
            "bytes": {k: int(v) for k, v in _by_label(
                _MESH_BYTES, "what").items()},
            "shards": int(_registry.get(_MESH_SHARDS)),
            "rows_per_shard": int(_registry.get(_MESH_ROWS))}


# ---- phases: spans that are booked when nothing is listening ---------------
# ``obs.trace.phase(name)`` opens the annotation ``span()`` opens and, on
# exit, adds its SELF time on ``time.perf_counter`` (its duration less the
# phases that ran inside it) to ``xtpu_phase_seconds_total{phase}`` and one
# to ``xtpu_phase_total{phase}``. Phases are coarse by rule: a few dozen a
# job and one a ``round`` span, never inside a round. Only the thread that
# entered the library (imported this module) books them; pool and uploader
# threads keep plain spans, so the stack and the sums below have one writer
# and no lock (a collector hands the sums to the registry when it is read).
# The compile listeners further down book ``program/trace_lower`` and
# ``program/compile`` as intervals inside whatever phase is open, so tracing
# and compiling come off the ``round`` they happen in.

_PHASE_S = "xtpu_phase_seconds_total"
_PHASE_N = "xtpu_phase_total"
_STARTUP = "xtpu_startup_seconds"
# containers: what they hold beyond their named parts is nobody's
PHASE_CONTAINERS = ("ingest", "train/call")


class Phase:
    """``obs.trace.phase``'s context manager: the span, and around its
    inside the booking frame (none off the booking thread)."""

    __slots__ = ("name", "_span", "t0", "inside")

    def __init__(self, name: str, span_cm) -> None:
        self.name = name
        self._span = span_cm
        self.t0 = None
        self.inside = 0.0     # seconds of the phases that ran inside it

    def __enter__(self):
        self._span.__enter__()
        if threading.get_ident() == _phase_thread:
            _stack.append(self)
            self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.t0 is not None:
            dur = time.perf_counter() - self.t0
            _stack.pop()      # ``with`` blocks close in order: it is on top
            (_stack[-1] if _stack else _root).inside += dur
            _book_phase(self.name, dur - self.inside)
        return self._span.__exit__(*exc)


_phase_thread = threading.get_ident()
_stack: List[Phase] = []
_root = Phase("", None)       # inside: seconds under any phase since import
_marks: List[Tuple[Phase, float]] = []    # open compile-path intervals
_phase_sums: Dict[str, List[float]] = {}  # phase -> [self seconds, count]
_process_t0: Optional[float] = None       # process start on perf_counter
_import_end: Optional[float] = None
_startup: Optional[Dict[str, float]] = None


def _book_phase(name: str, secs: float) -> None:
    sums = _phase_sums.get(name)
    if sums is None:
        sums = _phase_sums[name] = [0.0, 0]
    sums[0] += secs
    sums[1] += 1


def _collect_phases() -> List[Family]:
    rows = [((("phase", name),), sums[0], sums[1])
            for name, sums in list(_phase_sums.items())]
    if not rows:
        return []
    return [Family(_PHASE_S, "counter",
                   "self seconds of the library's phases on the thread that "
                   "entered it, by phase",
                   [Sample(secs, labels) for labels, secs, _n in rows]),
            Family(_PHASE_N, "counter", "phases finished, by phase",
                   [Sample(n, labels) for labels, _secs, n in rows])]


_registry.register(_collect_phases)


def _interval_open() -> None:
    """A compile-path event started (outermost trace or lowering, a backend
    compile): remember what the open phase held, so that phases finishing
    inside the interval come off it and are not taken from their parent
    twice."""
    if threading.get_ident() == _phase_thread:
        parent = _stack[-1] if _stack else _root
        _marks.append((parent, parent.inside))


def _interval_close(name: str, secs: float, args: Dict[str, Any]) -> None:
    """Book the interval ``[now - secs, now)`` as phase ``name`` inside
    whatever phase is open."""
    if threading.get_ident() != _phase_thread:
        return
    parent = _stack[-1] if _stack else _root
    nested = 0.0
    if _marks:
        marked, inside = _marks.pop()
        if marked is parent:
            nested = parent.inside - inside
    parent.inside += secs - nested
    _book_phase(name, secs - nested)
    from . import trace       # imports this module: not at the top

    trace.record_interval(name, secs, args)


def phase_seconds() -> Dict[str, float]:
    """``{phase: self seconds}`` as booked so far."""
    return {name: sums[0] for name, sums in list(_phase_sums.items())}


def phase_counts() -> Dict[str, int]:
    """``{phase: phases finished}`` as booked so far."""
    return {name: sums[1] for name, sums in list(_phase_sums.items())}


def _process_age() -> Optional[float]:
    """Seconds since the process started: its start time in
    ``/proc/self/stat`` (field 22, clock ticks after boot) against
    ``CLOCK_BOOTTIME``. None where either cannot be read."""
    try:
        with open("/proc/self/stat", "rb") as fh:
            fields = fh.read().rsplit(b")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError, AttributeError):
        return None


def book_import(t0: float) -> None:
    """Called once, from the last line of ``xgboost_tpu/__init__.py``, with
    ``perf_counter`` at its first: books ``import`` (less what was booked
    while it ran: every phase so far lies inside it) and ``before_import``
    (process start to that first line; left out where the process's age
    cannot be read)."""
    global _process_t0, _import_end
    if _import_end is not None or threading.get_ident() != _phase_thread:
        return
    now = time.perf_counter()
    _book_phase("import", now - t0 - sum(phase_seconds().values()))
    age = _process_age()
    if age is not None and age >= now - t0:
        _process_t0 = now - age
        _book_phase("before_import", t0 - _process_t0)
    else:
        _process_t0 = t0
    _root.inside = 0.0
    _import_end = now


def freeze_startup() -> Optional[Dict[str, float]]:
    """At the end of the process's first ``train()``: write the gauges
    ``xtpu_startup_seconds{phase}`` once. Each phase's self time so far;
    ``caller`` (this thread's time after ``import`` under no phase: the
    caller's own code); ``unattributed`` (self time of the containers
    ``ingest`` and ``train/call``); ``total`` (the process's age now). The
    parts add up to ``total``. Returns the report on the call that froze
    it, None on every other."""
    global _startup
    if _startup is not None or _import_end is None \
            or threading.get_ident() != _phase_thread:
        return None
    now = time.perf_counter()
    report = {"unattributed": 0.0}
    for name, secs in phase_seconds().items():
        if name in PHASE_CONTAINERS:
            report["unattributed"] += secs
        else:
            report[name] = secs
    report["caller"] = now - _import_end - _root.inside
    report["total"] = now - _process_t0
    for name, secs in report.items():
        _registry.set_gauge(_STARTUP, secs, labels=(("phase", name),),
                            help="where the time went from process start "
                                 "to the end of the first train(), frozen "
                                 "there: self seconds by phase")
    _startup = report
    return dict(report)


def startup_report() -> Optional[Dict[str, float]]:
    """The frozen start-up report as ``{phase: seconds}``; None until the
    process's first ``train()`` has returned."""
    return None if _startup is None else dict(_startup)


# ---- compile counters by program -------------------------------------------
# jax.monitoring listeners, registered once at import. jax 0.9 passes
# ``fun_name`` with its three compile-path duration events and announces
# each one's START through the scalar listener. Tracing and lowering nest
# (a jitted helper traced inside a round program fires its own events, and
# its seconds lie inside the outer one's), so trace+lower seconds are
# booked to the OUTERMOST program only, whole: the inner events are
# dropped, never added. Backend compiles do not nest in each other and are
# all counted; a load from the persistent cache counts as a compile, as it
# does in jax's own event, and is counted again as a cache hit (jax's
# ``cache_hits`` event names no program: it fires inside the compile event
# of the program it serves, on the same thread). A compile that jax WRITES
# to the persistent cache is counted again as a cache miss (jax's
# ``cache_misses`` event, in the same place): the programs a later process
# is served. A program that compiles faster than
# ``jax_persistent_cache_min_compile_time_secs`` (1 s) is neither: it is
# compiled in every process. A cache hit matters to
# whoever reads scopes off a device trace: the cache's key leaves metadata
# out, so the executable carries the scopes of the source that wrote it.
# The listeners run only when jax traces or compiles: a steady window pays
# nothing.

_TRACE_LOWER = ("/jax/core/compile/jaxpr_trace_duration",
                "/jax/core/compile/jaxpr_to_mlir_module_duration")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_COMPILES = "xtpu_program_compiles_total"
_COMPILE_S = "xtpu_program_compile_seconds_total"
_CACHE_HITS = "xtpu_program_cache_hits_total"
_CACHE_MISSES = "xtpu_program_cache_misses_total"
_TRACE_LOWER_S = "xtpu_program_trace_lower_seconds_total"
_nesting = threading.local()


def _program_label(fun_name) -> LabelSet:
    """The tracing event names a program ``f``, the lowering and compile
    events name its module ``jit(f)``: one label for both."""
    name = str(fun_name)
    for prefix in ("jit(", "pmap("):
        if name.startswith(prefix) and name.endswith(")"):
            name = name[len(prefix):-1]
    return (("program", name),)


def _on_compile_start(event: str, _value, **_kw) -> None:
    if event in _TRACE_LOWER:
        depth = _nesting.depth = getattr(_nesting, "depth", 0) + 1
        if depth == 1:
            _interval_open()
    elif event == _BACKEND_COMPILE:
        _nesting.cache_hit = _nesting.cache_miss = False
        _interval_open()


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _nesting.cache_hit = True
    elif event == _CACHE_MISS:
        _nesting.cache_miss = True


def _on_compile_duration(event: str, secs: float, fun_name=None,
                         **_kw) -> None:
    if event in _TRACE_LOWER:
        depth = _nesting.depth = max(getattr(_nesting, "depth", 1) - 1, 0)
        if depth == 0:
            labels = _program_label(fun_name)
            _registry.inc(_TRACE_LOWER_S, by=secs, labels=labels,
                          help="seconds tracing and lowering, booked to "
                               "the outermost program")
            _interval_close("program/trace_lower", secs,
                            {"program": labels[0][1]})
    elif event == _BACKEND_COMPILE:
        labels = _program_label(fun_name)
        _interval_close("program/compile", secs, {
            "program": labels[0][1],
            "cache_hit": int(getattr(_nesting, "cache_hit", False))})
        _registry.inc(_COMPILES, labels=labels,
                      help="backend compiles (or persistent-cache loads), "
                           "by program")
        _registry.inc(_COMPILE_S, by=secs, labels=labels,
                      help="seconds in backend compile or cache load")
        if getattr(_nesting, "cache_hit", False):
            _nesting.cache_hit = False
            _registry.inc(_CACHE_HITS, labels=labels,
                          help="compiles served from the persistent cache: "
                               "the executable carries its writer's scopes")
        if getattr(_nesting, "cache_miss", False):
            _nesting.cache_miss = False
            _registry.inc(_CACHE_MISSES, labels=labels,
                          help="compiles written to the persistent cache: "
                               "what a later process is served")


def program_compile_counts() -> Dict[str, Dict[str, float]]:
    """``{program: {"compiles", "compile_s", "cache_hits", "cache_misses",
    "trace_lower_s"}}`` as counted so far in this process."""
    out: Dict[str, Dict[str, float]] = {}
    for key, field in ((_COMPILES, "compiles"), (_COMPILE_S, "compile_s"),
                       (_CACHE_HITS, "cache_hits"),
                       (_CACHE_MISSES, "cache_misses"),
                       (_TRACE_LOWER_S, "trace_lower_s")):
        for program, value in _by_label(key, "program").items():
            out.setdefault(program, {
                "compiles": 0, "compile_s": 0.0, "cache_hits": 0,
                "cache_misses": 0, "trace_lower_s": 0.0})[field] = value
    return out


jax.monitoring.register_scalar_listener(_on_compile_start)
jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_duration_secs_listener(_on_compile_duration)
