"""The two histogram kernels that need more than the default scoped VMEM at
2,000 dense features, alone on the chip: the shipped form, which states
its own limit, against the form that fits the default, bit for bit.

    python3 tools/bench_wide_kernels.py [--rows N] [--features F]
        [--reps R] [--interpret] [--out FILE.json]

At the PASCAL epsilon job's shape (400,000 x 2,000 one-byte ids, benchmark
cell ``pascal-epsilon.wide-train``):

    build_hist_int8  the refine build at 128 nodes and 36 slots: shipped,
                     200 features a block under a stated limit; ``fits``,
                     ``feat_block=136`` (80 features a block) under the
                     default
    advance_leaf     the last level's advance below 128 and 256 nodes:
                     shipped, 2,048 rows a block under a stated limit;
                     ``fits``, 1,024 rows a block under the default

A row of the report: kernel, nodes, form, ``ms`` (the least of ``--reps``
timed calls, ``block_until_ready`` around each), ``compile_s``, the scoped
VMEM it states (``xtpu_kernel_vmem_bytes``) and ``equal`` (against the
shipped form, every bit). Fails unless jax's default backend is a TPU and
every form is equal; ``--interpret`` runs the kernels in the Pallas
interpreter off a TPU, for a rehearsal on the CPU at a small ``--rows``.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402


def timed(fn, args, reps):
    """(outputs, least ms of ``reps`` calls, compile seconds)."""
    import jax

    t = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    compile_s = time.perf_counter() - t
    best = float("inf")
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t)
    return out, 1e3 * best, compile_s


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=400_000)
    ap.add_argument("--features", type=int, default=2000)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=2147510041)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from xgboost_tpu.obs import metrics as obs_metrics
    from xgboost_tpu.ops.pallas import histogram as ph

    if jax.default_backend() != "tpu" and not args.interpret:
        print("jax's default backend is not a TPU (--interpret for a "
              "rehearsal)", file=sys.stderr)
        return 3
    n, F = args.rows, args.features
    rng = np.random.default_rng(args.seed)
    bins_t = jnp.asarray(rng.integers(0, 256, (F, n), dtype=np.uint8))
    gpair = rng.standard_normal((n, 2), dtype=np.float32)
    gpair[:, 1] = np.abs(gpair[:, 1])
    gpair = jnp.asarray(gpair)
    rows = []

    def vmem(kernel):
        return obs_metrics.kernel_vmem_bytes().get(kernel)

    def compare(kernel, nodes, forms, args_):
        shipped = None
        for form, fn in forms:
            obs_metrics.get_registry().set_gauge(
                "xtpu_kernel_vmem_bytes", 0, (("kernel", kernel),))
            out, ms, compile_s = timed(jax.jit(fn), args_, args.reps)
            got = [np.ascontiguousarray(o) for o in jax.tree.leaves(out)]
            if shipped is None:
                shipped = got
            row = {"kernel": kernel, "nodes": nodes, "form": form,
                   "ms": round(ms, 4), "compile_s": round(compile_s, 2),
                   "vmem_bytes": vmem(kernel),
                   "equal": all(np.array_equal(a.view(np.uint8),
                                               b.view(np.uint8))
                                for a, b in zip(got, shipped))}
            print(json.dumps(row), flush=True)
            rows.append(row)

    N, B = 128, 36
    rel = jnp.asarray(rng.integers(0, N + 1, n, dtype=np.int32))
    compare("build_hist_int8", N, [
        ("shipped", lambda b, g, r: ph.build_hist_pallas(
            b, g, r, N, B, interpret=args.interpret)),
        ("fits", lambda b, g, r: ph.build_hist_pallas(
            b, g, r, N, B, feat_block=136, interpret=args.interpret)),
    ], (bins_t % B, gpair, rel))

    for nodes in (128, 256):
        lo = nodes - 1
        positions = jnp.asarray(rng.integers(lo, lo + nodes, n,
                                             dtype=np.int32))
        feat = jnp.asarray(rng.integers(0, F, nodes, dtype=np.int32))
        thr = jnp.asarray(rng.integers(0, 255, nodes, dtype=np.int32))
        dleft = jnp.zeros((nodes,), bool)
        split = jnp.asarray(rng.random(nodes) < 0.9)
        leaf = jnp.asarray(rng.standard_normal(4 * nodes - 1,
                                               dtype=np.float32))
        compare("advance_leaf", nodes, [
            (form, lambda b, p, f, t, d, c, v, R=R: ph.advance_leaf_pallas(
                b, p, f, t, d, c, v, n_prev=nodes, missing_bin=256,
                block_rows=R, interpret=args.interpret))
            for form, R in (("shipped", 2048), ("fits", 1024))
        ], (bins_t, positions, feat, thr, dleft, split, leaf))

    if args.out:
        with open(args.out, "w") as fh:
            json.dump(rows, fh, indent=1)
    return 0 if all(r["equal"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
