"""xtpuflight: distributed flight recorder (docs/observability.md).

Four surfaces under test, mirroring the subsystem's four jobs:

1. the overlap kernel — ``hidden_fraction`` is THE one overlap formula
   in the repo (``streaming_overlap`` and ``tools/trace_analyze.py``
   both route through it), so its arithmetic is pinned bit-for-bit
   against the formula it replaced;
2. rank-merged timelines — N per-rank rings, clocks aligned by the
   barrier-timestamp handshake, merge into ONE Perfetto trace with one
   monotone process track per rank;
3. straggler analysis — an artificial straggler (FaultPlan latency on
   one rank) shows up as collective-wait skew on the OTHER ranks, the
   classic signature, crossing the warning threshold;
4. crash forensics — postmortem bundles round-trip through CRC
   verification, render, and detect corruption.
"""

import io
import json
import os
import threading
import time
import warnings

import numpy as np
import pytest

from tools.trace_analyze import (overlap_hidden_pct, overlap_rows,
                                 stage_rank_seconds, straggler_report)
from xgboost_tpu.obs import flight, memory, trace
from xgboost_tpu.obs import metrics as obs_metrics
from xgboost_tpu.obs.flight import (RING_KIND, RING_VERSION, BlackBox,
                                    BundleCorrupt, FlightRecorder,
                                    StragglerWarning, covered_seconds,
                                    hidden_fraction, interval_union,
                                    load_ring, merge_rings,
                                    render_postmortem, verify_bundle)
from xgboost_tpu.parallel.collective import InMemoryCommunicator
from xgboost_tpu.parallel.resilience import (FaultPlan, FaultyCommunicator,
                                             ResilientCommunicator)


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    yield
    trace.disable()


# ------------------------------------------------------------ overlap kernel

def test_hidden_fraction_matches_the_binned_formula_bitwise():
    # the formula streaming_overlap used before it was rerouted here:
    # None when nothing uploaded, else the compute-hidden fraction
    def old(upload_s, blocked_s):
        if upload_s <= 0:
            return None
        return max(0.0, 1.0 - blocked_s / upload_s)

    cases = [(0.0, 0.0), (-1.0, 0.5), (1.0, 0.0), (1.0, 1.0), (1.0, 2.0),
             (0.3, 0.1), (1e-9, 1e-10), (7.25, 3.125), (2.0, 1.9999999)]
    for upload, blocked in cases:
        assert hidden_fraction(upload, blocked) == old(upload, blocked), \
            (upload, blocked)


def test_interval_union_and_covered_seconds():
    assert interval_union([]) == []
    assert interval_union([(0, 1), (2, 3)]) == [(0, 1), (2, 3)]
    assert interval_union([(0, 2), (1, 3), (3, 4)]) == [(0, 4)]
    # degenerate / inverted intervals contribute nothing
    assert interval_union([(1, 1), (2, 1)]) == []
    assert covered_seconds([(0, 10)], [(2, 4), (3, 6), (20, 30)]) == 4.0
    assert covered_seconds([(0, 1), (5, 6)], [(0.5, 5.5)]) == 1.0
    assert covered_seconds([(0, 1)], []) == 0.0


def _span(name, t0, t1, tid=0, **kw):
    d = {"name": name, "cat": "", "t0": t0, "t1": t1, "dur": t1 - t0,
         "depth": 0, "tid": tid}
    d.update(kw)
    return d


def _ring(rank, world, spans, offset=0.0):
    return {"kind": RING_KIND, "version": RING_VERSION, "rank": rank,
            "world": world,
            "clock": {"offset_s": offset, "err_s": 0.0, "pings": 1},
            "epoch": 0.0, "dropped": 0, "spans": spans}


def test_overlap_rows_count_cross_thread_cover_only():
    spans = [
        _span("collective/hist", 0.0, 1.0, tid=1),
        _span("paged/upload-wait", 0.2, 0.7, tid=1),   # same tid: no cover
        _span("hist/build", 0.25, 0.75, tid=2),        # covers 0.5 s
        _span("hist/build", 0.5, 0.9, tid=2),          # overlaps the first
    ]
    rows = overlap_rows(spans)
    assert [r["name"] for r in rows] == ["collective/hist"]
    assert rows[0]["hidden_s"] == pytest.approx(0.65)
    assert rows[0]["hidden_pct"] == pytest.approx(65.0)
    # aggregate over a whole ring
    pct = overlap_hidden_pct([_ring(0, 1, spans)])
    assert pct == pytest.approx(65.0)
    assert overlap_hidden_pct([_ring(0, 1, [_span("hist/build", 0, 1)])]) \
        is None


# ----------------------------------------------- rank-merged timelines

def _thread_world(world, body):
    """Run ``body(rank, comm)`` on one thread per rank; return results."""
    comms = InMemoryCommunicator.make_world(world)
    out = [None] * world
    errs = []

    def run(r):
        try:
            out[r] = body(r, comms[r])
        except BaseException as e:  # pragma: no cover - surfaced below
            errs.append((r, e))

    ts = [threading.Thread(target=run, args=(r,)) for r in range(world)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
    assert not errs, errs
    return out


def test_multi_rank_rings_merge_into_one_aligned_timeline(tmp_path):
    WORLD = 4

    def body(rank, comm):
        rec = FlightRecorder(comm=comm, tracer=trace.Tracer(1024))
        clk = rec.sync_clocks(pings=4)
        for i in range(3):
            with rec.span("hist/build", "train", {"i": i}):
                time.sleep(0.002)
            with rec.span("round/update"):
                pass
        path = os.path.join(str(tmp_path), f"ring_{rank}.json")
        rec.export_ring(path)
        return path, clk

    results = _thread_world(WORLD, body)
    paths = [p for p, _ in results]
    clocks = [c for _, c in results]

    # clock handshake: rank 0 is the reference; thread ranks share one
    # physical clock so every offset is tiny but the uncertainty is real
    assert clocks[0].offset_s == 0.0
    for c in clocks:
        assert abs(c.offset_s) < 0.5 and c.err_s >= 0.0 and c.pings == 4

    # every exported span carries its rank identity
    for r, p in enumerate(paths):
        doc = load_ring(p)
        assert doc["rank"] == r and doc["world"] == WORLD
        assert doc["spans"], "rank exported an empty ring"
        assert all(s["rank"] == r and s["world"] == WORLD
                   for s in doc["spans"])

    merged = merge_rings(paths)
    ev = merged["traceEvents"]
    # one named process track per rank
    names = {e["args"]["name"] for e in ev if e["name"] == "process_name"}
    assert names == {f"rank {r}/{WORLD}" for r in range(WORLD)}
    xs = [e for e in ev if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == set(range(WORLD))
    assert len(xs) == sum(len(load_ring(p)["spans"]) for p in paths)
    # all timestamps on rank 0's clock, non-negative, monotone per track
    by_track = {}
    for e in xs:
        assert e["ts"] >= 0.0 and e["dur"] >= 0.0
        assert e["args"]["rank"] == e["pid"]
        by_track.setdefault((e["pid"], e["tid"]), []).append(e["ts"])
    for track, ts in by_track.items():
        assert ts == sorted(ts), f"track {track} not monotone"
    # the merged doc is valid Perfetto JSON
    json.dumps(merged)


def test_merge_unaligned_keeps_raw_timestamps():
    spans = [_span("hist/build", 1.0, 2.0)]
    shifted = merge_rings([_ring(0, 2, spans),
                           _ring(1, 2, spans, offset=0.5)])
    raw = merge_rings([_ring(0, 2, spans),
                       _ring(1, 2, spans, offset=0.5)], align=False)
    ts_by_pid = lambda doc: {e["pid"]: e["ts"]
                             for e in doc["traceEvents"] if e["ph"] == "X"}
    shifted_ts, raw_ts = ts_by_pid(shifted), ts_by_pid(raw)
    assert shifted_ts[1] == pytest.approx(shifted_ts[0] - 0.5e6)
    assert raw_ts[0] == raw_ts[1]


# --------------------------------------------------------- straggler skew

def test_faultplan_straggler_crosses_warning_threshold():
    """One rank slowed by FaultPlan(latency_s=...) — the classic straggler
    signature: the OTHER ranks burn that latency waiting inside their
    ``collective/*`` spans while the straggler's own collective time is
    ~zero, so the cohort's collective-stage skew crosses the threshold."""
    WORLD, LAT = 4, 0.04
    tr = trace.enable(capacity=4096)

    def body(rank, comm):
        rc = ResilientCommunicator(comm)
        use = FaultyCommunicator(rc, FaultPlan(latency_s=LAT,
                                               max_failures=0)) \
            if rank == WORLD - 1 else rc
        rec = FlightRecorder(comm=comm)
        rec.adopt_current_thread()
        rec.sync_clocks(pings=2)
        for _ in range(4):
            use.allreduce(np.ones(64, np.float32))
        return rec.ring_doc()

    rings = _thread_world(WORLD, body)
    table = stage_rank_seconds(rings)
    assert "collective" in table
    # the straggler waits the least: everyone else absorbs its latency
    waits = table["collective"]
    assert min(waits, key=waits.get) == WORLD - 1
    with pytest.warns(StragglerWarning) as rec_w:
        rep = straggler_report(rings, threshold_pct=25.0)
    assert rep["straggler_stage"] == "collective"
    assert rep["straggler_skew_pct"] > 25.0
    w = rec_w.list[-1].message
    assert w.stage == "collective" and w.skew_pct > 25.0
    snap = obs_metrics.get_registry().snapshot()
    assert any(k.startswith("xtpu_straggler_skew_pct") for k in snap)


def test_balanced_world_raises_no_straggler_warning():
    rings = [_ring(r, 2, [_span("hist/build", 0.0, 1.0)]) for r in range(2)]
    with warnings.catch_warnings():
        warnings.simplefilter("error", StragglerWarning)
        rep = straggler_report(rings, threshold_pct=25.0, publish=False)
    assert rep["straggler_skew_pct"] == pytest.approx(0.0)


# --------------------------------------------------------- crash forensics

def test_blackbox_bundle_roundtrip_and_render(tmp_path):
    t = trace.enable(capacity=256)
    with trace.span("round/fused"):
        pass
    mon = memory.enable()
    try:
        mon.book("carry/margin", 4096)
        mon.sample("round")
        box = BlackBox(str(tmp_path), rank=2, world=8)
        try:
            raise ValueError("synthetic crash")
        except ValueError as e:
            path = box.write("test-crash", exc=e, extra={"epoch": 3})
        assert path is not None and os.path.exists(path)
        assert os.path.exists(path + ".crc")
        doc = verify_bundle(path)
        assert doc["reason"] == "test-crash"
        assert doc["rank"] == 2 and doc["world"] == 8
        assert doc["exception"]["type"] == "ValueError"
        assert "synthetic crash" in doc["exception"]["traceback"]
        assert doc["extra"] == {"epoch": 3}
        assert any(s["name"] == "round/fused"
                   for s in doc["trace"]["spans"])
        assert doc["memory"]["live_bytes"] == 4096
        assert isinstance(doc["programs"], dict)
        buf = io.StringIO()
        render_postmortem(path, file=buf)
        text = buf.getvalue()
        assert "test-crash" in text and "rank 2/8" in text
        assert "ValueError" in text and "round/fused" in text
    finally:
        memory.disable()


def test_blackbox_detects_corruption(tmp_path):
    box = BlackBox(str(tmp_path))
    path = box.write("ok")
    with open(path, "r+b") as fh:
        fh.seek(10)
        fh.write(b"X")
    with pytest.raises(BundleCorrupt):
        verify_bundle(path)
    # a missing sidecar is corruption too
    path2 = box.write("ok2")
    os.remove(path2 + ".crc")
    with pytest.raises(BundleCorrupt):
        verify_bundle(path2)
    # and so is a non-bundle document
    stray = os.path.join(str(tmp_path), "stray.json")
    payload = b'{"kind": "something-else"}'
    with open(stray, "wb") as fh:
        fh.write(payload)
    import zlib
    with open(stray + ".crc", "w") as fh:
        fh.write(f"{zlib.crc32(payload):08x} {len(payload)}\n")
    with pytest.raises(BundleCorrupt):
        verify_bundle(stray)


def test_arm_excepthook_writes_bundle_then_chains(tmp_path):
    seen = []
    prev, threading_prev = flight.sys.excepthook, threading.excepthook
    flight.sys.excepthook = lambda *a: seen.append(a)
    threading.excepthook = lambda a: seen.append(a)
    try:
        box = flight.arm(directory=str(tmp_path), rank=1, world=4)
        assert flight.armed() is box
        # idempotent
        assert flight.arm(directory="elsewhere") is box
        try:
            raise RuntimeError("boom")
        except RuntimeError as e:
            flight._excepthook(RuntimeError, e, e.__traceback__)
        assert box.last_bundle is not None
        doc = verify_bundle(box.last_bundle)
        assert doc["reason"] == "unhandled-exception"
        assert doc["rank"] == 1 and doc["world"] == 4
        assert "boom" in doc["exception"]["message"]
        assert len(seen) == 1  # chained to the previous hook
        # worker-thread hook: same bundle path, thread name in the reason
        class HA:
            exc_type, thread = RuntimeError, threading.current_thread()
            exc_value = RuntimeError("worker boom")
            exc_traceback = None
        flight._threading_hook(HA())
        doc2 = verify_bundle(box.last_bundle)
        assert doc2["reason"].startswith("unhandled-thread-exception:")
        assert len(seen) == 2  # both hooks chained to their predecessors
    finally:
        flight.disarm()
        flight.sys.excepthook = prev
        threading.excepthook = threading_prev
    assert flight.armed() is None
    assert flight.write_postmortem("after-disarm") is None


def test_postmortem_cli_renders_and_flags_corruption(tmp_path):
    import subprocess
    import sys as _sys
    box = BlackBox(str(tmp_path))
    good = box.write("cli-check")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [_sys.executable, "-m", "xgboost_tpu.obs", "postmortem", good],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert p.returncode == 0, p.stderr
    assert "cli-check" in p.stdout
    with open(good, "r+b") as fh:
        fh.write(b"Z")
    p2 = subprocess.run(
        [_sys.executable, "-m", "xgboost_tpu.obs", "postmortem", good],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert p2.returncode == 1


# ----------------------------------------------------------- HBM accounting

def test_memory_monitor_booked_fallback_and_rounds():
    mon = memory.enable()
    try:
        assert memory.enabled()
        mon._device_bytes = lambda: None  # force the CPU fallback path
        memory.book("carry/margin", 1000)
        memory.book("page_cache", 500)
        memory.sample("round")
        memory.note_round()
        memory.book("page_cache", 2000)   # replace, not accumulate
        memory.sample("round")
        memory.note_round()
        memory.unbook("page_cache")
        memory.sample("tail")
        snap = mon.snapshot()
        assert snap["source"] == "booked"
        assert snap["live_bytes"] == 1000
        assert snap["peak_bytes"] == 3000
        assert snap["hbm_peak_bytes_per_round"] == 3000
        assert mon.peak_per_round() == 3000
        assert snap["rounds"] == 2
        assert snap["bookings"] == {"carry/margin": 1000}
        # registry exposition is wired
        fams = {f.name for f in obs_metrics.get_registry().collect()}
        assert {"xtpu_hbm_bytes_in_use", "xtpu_hbm_peak_bytes",
                "xtpu_hbm_samples_total"} <= fams
    finally:
        memory.disable()
    assert not memory.enabled()
    # disabled module-level hooks are inert no-ops
    memory.sample("x")
    memory.book("k", 1)
    memory.unbook("k")
    memory.note_round()
