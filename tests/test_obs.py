"""xtpuobs: span tracing, the metrics registry, and their contracts.

The three load-bearing promises (docs/observability.md):

1. with the ring off a span site is one inert profiler annotation: no
   ring record, no growth of the ring (its cost without a profiler
   session is bounded in ``tests/test_trace_program.py``);
2. tracing NEVER changes the model — traced and untraced training
   produce byte-identical ``save_raw`` artifacts;
3. exports round-trip — Perfetto JSON loads back with the spans, names,
   and nesting the recorder saw.

Plus the one-registry surface: collector registration, weakref
expiry, duplicate-sample merging, and Prometheus text exposition.
"""

import gc
import json
import tracemalloc

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.obs import metrics as om
from xgboost_tpu.obs import trace as tr


@pytest.fixture(autouse=True)
def _trace_off_after():
    yield
    tr.disable()


def _data(n=2000, f=10, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    return X, y


def _train(X, y, **params):
    p = {"objective": "binary:logistic", "max_depth": 4, "max_bin": 64}
    p.update(params)
    return xgb.train(p, xgb.DMatrix(X, label=y), num_boost_round=3,
                     verbose_eval=False)


# ------------------------------------------------------------ span tracer

def test_disabled_span_leaves_no_ring_record():
    """Ring off: a span site is a bare profiler annotation. Nothing is
    recorded, and a ring enabled afterwards starts empty."""
    tr.disable()
    assert tr.tracer() is None
    for _ in range(100):
        with tr.span("round/fused", "train", {"iteration": 3}):
            pass
        with tr.span("round", "train", {"rounds": 4}, step=3):
            pass
        tr.instant("collective/retry")
    assert tr.tracer() is None and tr.export() == 0
    t = tr.enable(capacity=16)
    assert len(t) == 0 and t.dropped == 0
    with tr.span("round/fused", "train", {"iteration": 4}):
        pass
    assert [s.name for s in t.spans()] == ["round/fused"]
    tr.disable()
    with tr.span("round/fused"):
        pass
    assert len(t) == 1          # the ring that was live did not grow


def test_disabled_memory_hooks_are_allocation_free():
    # same contract as the disabled tracer: the memory-accounting call
    # sites core.py / paged.py / binned.py leave on the hot path must
    # cost nothing when XTPU_FLIGHT_MEM is off (one predicate, no allocs)
    from xgboost_tpu.obs import memory as mem
    mem.disable()
    assert not mem.enabled()
    # warm the call sites first: the first pass may pay one-shot
    # interpreter setup that is not a per-call cost
    for _ in range(50):
        mem.sample("round")
        mem.book("carry/margin", 4096)
        mem.unbook("carry/margin")
        mem.note_round()
    flt = tracemalloc.Filter(True, mem.__file__)
    # a genuine per-call allocation fails every attempt; the retries only
    # forgive one-shot noise (e.g. a stray background thread from an
    # earlier test touching a hook once inside the measured window)
    for attempt in range(3):
        tracemalloc.start()
        try:
            gc.collect()
            base = tracemalloc.take_snapshot().filter_traces([flt])
            for _ in range(1000):
                mem.sample("round")
                mem.book("carry/margin", 4096)
                mem.unbook("carry/margin")
                mem.note_round()
            after = tracemalloc.take_snapshot().filter_traces([flt])
        finally:
            tracemalloc.stop()
        diff = after.compare_to(base, "lineno")
        grown = [d for d in diff if d.size_diff > 0]
        if not grown:
            break
    assert not grown, [str(d) for d in grown]


def test_enabled_spans_record_nesting_and_args():
    tr.disable()
    t = tr.enable(capacity=128)
    with tr.span("outer", "cat", {"k": 1}):
        with tr.span("inner"):
            pass
    spans = t.spans()
    by_name = {s.name: s for s in spans}
    assert by_name["outer"].depth == 0
    assert by_name["inner"].depth == 1
    assert by_name["outer"].args == {"k": 1}
    assert by_name["inner"].t0 >= by_name["outer"].t0
    assert by_name["inner"].t1 <= by_name["outer"].t1


def test_ring_keeps_newest_and_counts_dropped():
    tr.disable()
    t = tr.enable(capacity=8)
    for i in range(20):
        with t.span(f"s{i}"):
            pass
    assert len(t) == 8
    assert t.dropped == 12
    assert [s.name for s in t.spans()] == [f"s{i}" for i in range(12, 20)]


def test_perfetto_roundtrip(tmp_path):
    tr.disable()
    t = tr.enable(capacity=64)
    with tr.span("a", "train"):
        with tr.span("b"):
            pass
    path = tmp_path / "trace.json"
    n = t.dump(str(path))
    assert n == 2
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert set(evs) == {"a", "b"}
    assert all(e["ph"] == "X" for e in evs.values())
    # b nests inside a on the export's own timeline
    assert evs["b"]["ts"] >= evs["a"]["ts"]
    assert (evs["b"]["ts"] + evs["b"]["dur"]
            <= evs["a"]["ts"] + evs["a"]["dur"] + 1e-3)
    assert evs["a"]["cat"] == "train"
    # jsonl flavor round-trips too
    jpath = tmp_path / "trace.jsonl"
    assert t.dump(str(jpath)) == 2
    lines = [json.loads(ln) for ln in jpath.read_text().splitlines()]
    assert {ln["name"] for ln in lines} == {"a", "b"}
    assert {ln["depth"] for ln in lines} == {0, 1}


def test_traced_training_is_byte_identical():
    X, y = _data()
    tr.disable()
    raw_plain = _train(X, y).save_raw()
    raw_lg_plain = _train(X, y, max_depth=6, grow_policy="lossguide",
                          max_leaves=12).save_raw()
    tr.enable()
    raw_traced = _train(X, y).save_raw()
    raw_lg_traced = _train(X, y, max_depth=6, grow_policy="lossguide",
                           max_leaves=12).save_raw()
    assert raw_traced == raw_plain
    assert raw_lg_traced == raw_lg_plain
    # ...and the trace actually saw the round structure while at it
    names = {s.name for s in tr.tracer().spans()}
    assert "round/fused" in names


def test_trace_spans_cover_paged_level_structure(tmp_path, monkeypatch):
    """The paged driver's host spans reproduce the level loop: one hist
    span per (round, level) in depth order, exchange/eval beside them."""
    import sys
    import os
    sys.path.insert(0, os.path.dirname(__file__))
    from test_data_iterator import BatchIter

    monkeypatch.setenv("XTPU_PAGE_ROWS", "700")
    monkeypatch.setenv("XTPU_PAGED_COLLAPSE", "0")
    monkeypatch.setenv("XTPU_PAGE_CACHE_BYTES", "0")  # force streaming
    X, y = _data(n=2100)
    it = BatchIter(X, y, n_batches=3)
    it.cache_prefix = str(tmp_path / "pc")
    dm = xgb.QuantileDMatrix(it, max_bin=64)
    depth, rounds = 3, 2
    tr.disable()
    t = tr.enable()
    xgb.train({"objective": "binary:logistic", "max_depth": depth,
               "max_bin": 64}, dm, num_boost_round=rounds,
              verbose_eval=False)
    hist = [s for s in t.spans() if s.name == "paged/hist"]
    assert len(hist) == rounds * depth
    depths = [s.args["depth"] for s in hist]
    assert depths == list(range(depth)) * rounds
    names = {s.name for s in t.spans()}
    assert {"paged/exchange", "paged/eval", "paged/fetch"} <= names


# ------------------------------------------------------- metrics registry

def _fam(name, kind="counter", value=1, labels=()):
    return om.Family(name, kind, f"help for {name}",
                     [om.Sample(value, labels)])


def test_registry_direct_and_collector_sources():
    reg = om.MetricsRegistry()
    reg.inc("xtpu_test_events_total", 2)
    reg.inc("xtpu_test_events_total", 3)
    reg.set_gauge("xtpu_test_depth", 6)
    reg.register(lambda: [_fam("xtpu_test_pages_total", value=7)])
    text = reg.render_prometheus()
    assert "# TYPE xtpu_test_events_total counter" in text
    assert "xtpu_test_events_total 5" in text
    assert "xtpu_test_depth 6" in text
    assert "xtpu_test_pages_total 7" in text


def test_registry_merges_duplicate_samples():
    reg = om.MetricsRegistry()
    reg.register(lambda: [_fam("xtpu_dup_total", value=2)])
    reg.register(lambda: [_fam("xtpu_dup_total", value=3)])
    reg.register(lambda: [_fam("xtpu_last_gauge", "gauge", 1),
                          _fam("xtpu_last_gauge", "gauge", 9)])
    fams = {f.name: f for f in reg.collect()}
    assert fams["xtpu_dup_total"].samples[0].value == 5   # counters sum
    assert fams["xtpu_last_gauge"].samples[0].value == 9  # gauges last-win


def test_registry_weakref_drops_dead_collector():
    reg = om.MetricsRegistry()

    class Src:
        def collect(self):
            return [_fam("xtpu_ghost_total")]

    s = Src()
    reg.register(Src.collect, owner=s)
    assert "xtpu_ghost_total" in reg.render_prometheus()
    del s
    gc.collect()
    assert "xtpu_ghost_total" not in reg.render_prometheus()


def test_histogram_exposition_format():
    reg = om.MetricsRegistry()
    h = om.HistogramData([(0.01, 3), (0.1, 5), (float("inf"), 6)],
                         0.25, 6)
    reg.register(lambda: [om.Family(
        "xtpu_lat_seconds", "histogram", "latency",
        [om.Sample(h, (("stage", "e2e"),))])])
    text = reg.render_prometheus()
    assert '# TYPE xtpu_lat_seconds histogram' in text
    assert 'xtpu_lat_seconds_bucket{stage="e2e",le="0.01"} 3' in text
    assert 'xtpu_lat_seconds_bucket{stage="e2e",le="+Inf"} 6' in text
    assert 'xtpu_lat_seconds_sum{stage="e2e"} 0.25' in text
    assert 'xtpu_lat_seconds_count{stage="e2e"} 6' in text
    # cumulative buckets must be monotone and end at count
    vals = [int(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
            if ln.startswith("xtpu_lat_seconds_bucket")]
    assert vals == sorted(vals) and vals[-1] == 6


def test_serve_metrics_families_and_locked_reads():
    from xgboost_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics(register=False)
    m.inc("requests", 4)
    m.inc("sheds")
    m.observe("e2e", 0.02)
    m.hit_bucket(8, padded_rows=3)
    assert m.get("requests") == 4
    assert m.get("missing", -1) == -1
    cut = m.get_many(("requests", "sheds", "errors"))
    assert cut == {"requests": 4, "sheds": 1, "errors": 0}
    fams = {f.name: f for f in m._collect_obs()}
    assert fams["xtpu_serve_requests_total"].samples[0].value == 4
    # pre-declared schema: core counters expose at 0 before first inc
    assert fams["xtpu_serve_errors_total"].samples[0].value == 0
    hits = fams["xtpu_serve_bucket_hits_total"].samples
    assert hits[0].labels == (("bucket", "8"),)
    hd = fams["xtpu_serve_stage_latency_seconds"].samples[0].value
    assert hd.count == 1 and hd.buckets[-1][1] == 1
    assert hd.buckets[-1][0] == float("inf")


def test_collective_counters_registered():
    from xgboost_tpu.parallel.resilience import ResilientCommunicator
    from xgboost_tpu.parallel.collective import NoOpCommunicator

    rc = ResilientCommunicator(NoOpCommunicator())
    rc.stats["retry"] = 3
    text = om.get_registry().render_prometheus()
    assert 'xtpu_collective_events_total{kind="retry"} 3' in text
    del rc
    gc.collect()
    text = om.get_registry().render_prometheus()
    assert 'kind="retry"' not in text
