"""A/B: one-pass 256-bin histogram vs two-level coarse->refine (16x16).

VERDICT r3 #4: the packed-SWAR kernel's level cost is VPU-bound on the
one-hot build (F*B*n element writes at B=256). A two-level scheme does
TWO passes at B=16 — the coarse pass over ``bins >> 4`` and a refine pass
over ``bins - 16*span`` where ``span`` is a per-(row, feature) coarse-bin
choice gathered from the row's node — cutting one-hot writes ~8x (16-bin
one-hots still pad to int8's 32-sublane tile). This script measures the
KERNEL-LEVEL ceiling of that formulation: coarse pass + span gather +
refine pass vs the single 256-bin pass, at the bench shape (1M x 28,
N=32 nodes, the widest depth-6 level). Exactness caveat measured
separately: the refined span is chosen from coarse data, so the fine
argmax can be missed when the best fine split lies outside the best
coarse span — quality A/B in the companion training experiment.

Run on the TPU; uses the slope method (tools/benchlib.py).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp

    from xgboost_tpu.ops.pallas.histogram import build_hist_pallas

    n, F, N = 1_000_000, 28, 32
    rng = np.random.RandomState(0)
    bins = rng.randint(0, 256, (n, F)).astype(np.uint8)
    bins_t = jnp.asarray(np.ascontiguousarray(bins.T))
    gpair = jnp.asarray(rng.randn(n, 2).astype(np.float32))
    pos = jnp.asarray(rng.randint(0, N, n).astype(np.int32))
    spans = jnp.asarray(rng.randint(0, 16, (N, F)).astype(np.float32))

    @jax.jit
    def one_pass(bt, gp, p):
        return build_hist_pallas(bt, gp, p, N, 256, precision="int8x2")

    @jax.jit
    def coarse16(bt, gp, p):
        return build_hist_pallas(bt // 16, gp, p, N, 16,
                                 precision="int8x2")

    @jax.jit
    def refine16(bt, gp, p, sp):
        # span gather: row r's node one-hot picks its (node, feature)
        # span in ONE [n, N] @ [N, F] MXU matmul, then the relative bin
        # (out-of-span rows land >= 16 and match no one-hot slot)
        oh_node = (p[:, None] == jnp.arange(N, dtype=jnp.int32)[None, :]
                   ).astype(jnp.float32)
        c_row = jax.lax.dot_general(
            oh_node, sp, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST)           # [n, F]
        rel = bt.astype(jnp.int32) - 16 * c_row.T.astype(jnp.int32)
        rel = jnp.where((rel >= 0) & (rel < 16), rel, 16)
        return build_hist_pallas(rel.astype(jnp.uint8), gp, p, N, 16,
                                 precision="int8x2")

    @jax.jit
    def two_level(bt, gp, p, sp):
        return coarse16(bt, gp, p), refine16(bt, gp, p, sp)

    def sync(r):
        # sync by a scalar device_get (tools/benchlib.py)
        leaf = jax.tree_util.tree_leaves(r)[-1]
        float(np.asarray(leaf.ravel()[0]))

    def timeit(tag, fn, *args):
        """SLOPE between two repetition counts (tools/benchlib rule): a
        total/reps with one end-of-loop sync shares an additive sync
        constant between both sides of the A/B and biases the ratio
        toward 1."""
        sync(fn(*args))

        def total(reps):
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(reps):
                    r = fn(*args)
                sync(r)
                best = min(best, time.perf_counter() - t0)
            return best

        lo, hi = 10, 40
        ms = (total(hi) - total(lo)) / (hi - lo) * 1e3
        print(f"{tag}: {ms:.2f} ms/iter (slope)", flush=True)
        return ms

    t1 = timeit("one-pass 256-bin       ", one_pass, bins_t, gpair, pos)
    tc = timeit("coarse 16-bin pass     ", coarse16, bins_t, gpair, pos)
    tr = timeit("refine 16-bin + gather ", refine16, bins_t, gpair, pos,
                spans)
    t2 = timeit("two-level fused        ", two_level, bins_t, gpair, pos,
                spans)
    print(f"speedup (fused two-level vs one-pass): {t1 / t2:.2f}x")
    print(f"sum of parts: coarse {tc:.2f} + refine {tr:.2f} ms")


if __name__ == "__main__":
    main()
