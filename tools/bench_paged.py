"""Paged (external-memory) tier throughput at the north-star shape.

11M x 28, depth 6, XTPU_PAGE_ROWS=4M (3 pages), HBM page cache on —
the configuration BASELINE.md's external-memory paragraph records.
Prints cold and steady (slope) seconds/round, plus the FORCED-STREAMING
tier's H2D overlap-%: the fraction of page-upload wall time hidden
behind compute (distinguishes "the H2D link is the floor" from "the
ring is serializing transfers"). Run on the TPU.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("XTPU_PAGE_ROWS", "4000000")

import numpy as np  # noqa: E402

N = int(os.environ.get("BENCH_PAGED_ROWS", 11_000_000))
F = 28


def main():
    import jax

    import xgboost_tpu as xgb
    from xgboost_tpu.data.dmatrix import DataIter

    print("devices:", jax.devices(), flush=True)
    rng = np.random.RandomState(42)
    X = rng.randn(N, F).astype(np.float32)
    w = rng.randn(F).astype(np.float32)
    y = (X @ w + rng.randn(N).astype(np.float32) > 0).astype(np.float32)

    class It(DataIter):
        def __init__(self):
            super().__init__()
            self.parts = np.array_split(np.arange(N), 11)
            self.i = 0

        def next(self, input_data):
            if self.i >= len(self.parts):
                return 0
            idx = self.parts[self.i]
            input_data(data=X[idx], label=y[idx])
            self.i += 1
            return 1

        def reset(self):
            self.i = 0

    it = It()
    it.cache_prefix = os.environ.get("BENCH_PAGED_CACHE", "/tmp/paged_bench")
    t0 = time.perf_counter()
    dm = xgb.QuantileDMatrix(it, max_bin=256)
    print(f"ingest: {time.perf_counter() - t0:.1f} s", flush=True)
    binned = dm.binned(256)
    print("pages:", binned.n_pages(), flush=True)

    params = {"objective": "binary:logistic", "max_depth": 6, "eta": 0.1,
              "max_bin": 256}

    def timed(rounds):
        t0 = time.perf_counter()
        bst = xgb.train(params, dm, rounds, verbose_eval=False)
        for st in bst._caches.values():
            jax.block_until_ready(st["margin"])
            float(np.asarray(st["margin"][0, 0]))
        return time.perf_counter() - t0

    print(f"first 2 rounds (compiles): {timed(2):.1f} s", flush=True)
    t5 = min(timed(5) for _ in range(2))
    print(f"t5: {t5:.1f} s", flush=True)
    t15 = min(timed(15) for _ in range(2))
    print(f"t15: {t15:.1f} s", flush=True)
    print(f"steady: {(t15 - t5) / 10:.2f} s/round "
          f"({10 / (t15 - t5):.2f} rounds/s)", flush=True)

    # ---- forced-streaming overlap: how much H2D hides behind compute ----
    # zero cache budget => every page re-uploads every visit, the pure
    # streaming regime; the ring stats separate upload wall time from the
    # consumer's blocked time (data/binned.py ring_stats) and count the
    # transport bytes, reported as MATRIX-EQUIVALENTS per round — the
    # page-major schedule's accounting unit (r8: one visit per page per
    # level boundary => depth+1 equivalents at depth 6, was ~2*depth+1;
    # u4 packing halves the bytes again when max_bin <= 16)
    os.environ["XTPU_PAGED_COLLAPSE"] = "0"
    prior_budget = binned.cache_budget_bytes
    binned.cache_budget_bytes = 0
    binned._device_cache.clear()
    try:
        timed(1)  # compile the streaming programs at this cache state
        binned.reset_ring_stats()
        t_stream = timed(3)
        # one overlap formula in the repo: streaming_overlap routes
        # through xgboost_tpu.obs.flight.hidden_fraction, the same kernel
        # tools/trace_analyze.py applies to exported span intervals — so
        # this line, bench.py's paged11m_streaming_overlap_pct and the
        # analyzer's overlap_hidden_pct can never disagree on arithmetic
        rs = binned.ring_stats
        ov = binned.streaming_overlap()
        from xgboost_tpu.obs.flight import hidden_fraction
        assert ov == hidden_fraction(rs["upload_s"], rs["blocked_s"])
        meq = rs["bytes"] / 3.0 / max(binned.bins_host.nbytes, 1)
        print(f"streaming (no cache): {t_stream / 3:.2f} s/round; "
              f"uploads/round={rs['uploads'] / 3:.1f} "
              f"bytes/round={rs['bytes'] / 3 / 2**20:.0f} MiB "
              f"({meq:.2f} matrix-equivalents, "
              f"pack={'on' if binned.packed else 'off'}) "
              f"upload={rs['upload_s']:.1f}s "
              f"blocked={rs['blocked_s']:.1f}s "
              f"overlap={'n/a' if ov is None else f'{100 * ov:.0f}%'}",
              flush=True)
    finally:
        binned.cache_budget_bytes = prior_budget
        os.environ.pop("XTPU_PAGED_COLLAPSE", None)


if __name__ == "__main__":
    main()
