"""The refine build of the two-level search alone on the chip: the kernel
that remaps the raw bin tile into each node's window in VMEM, against the
form that remaps in XLA first, bit for bit.

    python3 tools/bench_refine_remap.py [--shapes F:DTYPE:ROWS,...]
        [--nodes 1,32,128] [--reps R] [--interpret] [--out FILE.json]

Forms, each a jitted call over the same raw ``[F, n]`` tile, gradient
pairs, level positions and window table:

    vmem      ``build_hist_pallas(window=...)``: the raw tile and the
              ``[N, F]`` table; the remap is one step a grid step
    xla       the per-row window by an ``[F, N+1] @ [N+1, n]`` matmul,
              ``refine_bin_ids`` over the whole tile, then the kernel over
              that ``[F, n]`` copy: the refine build before the remap
              moved into the kernel
    kernel    the same kernel over a copy remapped ahead of the timing:
              what ``xla`` pays for its kernel alone

The default shapes are the benchmark cells' widths at their rows:
28 x 10,500,000 and 220 x 7,325,625 one-byte ids, 968 x 1,183,747
two-byte ids with the missing slot at 256, 2,000 x 400,000 one-byte ids.
A row of the report: shape, nodes, form, ``ms`` (the least of ``--reps``
calls, ``block_until_ready`` around each), ``compile_s``, and ``equal``
(against ``vmem``, every bit). Fails unless jax's default backend is a TPU
and every form is equal; ``--interpret`` runs the kernels in the Pallas
interpreter off a TPU, for a rehearsal on the CPU at small rows.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

SHAPES = ("28:uint8:10500000,220:uint8:7325625,968:uint16:1183747,"
          "2000:uint8:400000")


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
    ap.add_argument("--shapes", default=SHAPES)
    ap.add_argument("--nodes", default="1,32,128")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=2147510042)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from xgboost_tpu.ops.pallas import histogram as ph
    from xgboost_tpu.ops.split import WINDOW, refine_bin_ids

    if jax.default_backend() != "tpu" and not args.interpret:
        print("jax's default backend is not a TPU (--interpret for a "
              "rehearsal)", file=sys.stderr)
        return 3
    B = WINDOW + 4
    rows, ok = [], True
    for shape in args.shapes.split(","):
        F, dtype, n = shape.split(":")
        F, n, dtype = int(F), int(n), jnp.dtype(dtype)
        missing = 256 if dtype == jnp.uint16 else 255
        key = jax.random.key(args.seed + F)
        k_bins, k_g, k_rel, k_win = jax.random.split(key, 4)
        bins_t = jax.random.randint(k_bins, (F, n), 0, missing + 1,
                                    jnp.int32).astype(dtype)
        gpair = jax.random.normal(k_g, (n, 2), jnp.float32)
        gpair = gpair.at[:, 1].set(jnp.abs(gpair[:, 1]))
        for N in (int(x) for x in args.nodes.split(",")):
            # rows on every node of the level, and some outside it
            rel = jax.random.randint(k_rel, (n,), 0, N + 1, jnp.int32)
            window = jax.random.randint(k_win, (N, F), 0, 15, jnp.int32)
            build = dict(n_nodes=N, max_nbins=B, interpret=args.interpret)

            def vmem(bt, gp, r, w):
                return ph.build_hist_pallas(bt, gp, r, window=w,
                                            missing_bin=missing, **build)

            def remap(bt, r, w, N=N):
                # the round program's form before the remap moved into the
                # kernel, [F, n] throughout
                span_pad = jnp.concatenate(
                    [w.astype(jnp.float32),
                     jnp.zeros((1, F), jnp.float32)]).T       # [F, N+1]
                oh_rel = (r[None, :] == jnp.arange(
                    N + 1, dtype=jnp.int32)[:, None]).astype(jnp.float32)
                c_row_t = jax.lax.dot_general(
                    span_pad, oh_rel, (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST)      # [F, n]
                return refine_bin_ids(bt.astype(jnp.int32),
                                      c_row_t.astype(jnp.int32), missing)

            def xla(bt, gp, r, w):
                return ph.build_hist_pallas(remap(bt, r, w), gp, r, **build)

            def kernel(rb_t, gp, r, w):
                return ph.build_hist_pallas(rb_t, gp, r, **build)

            remapped = jax.block_until_ready(
                jax.jit(remap)(bins_t, rel, window))
            ref = None
            for form, fn, tile in (("vmem", vmem, bins_t),
                                   ("xla", xla, bins_t),
                                   ("kernel", kernel, remapped)):
                out, ms, compile_s = timed(jax.jit(fn),
                                           (tile, gpair, rel, window),
                                           args.reps)
                out = np.asarray(out)
                row = {"features": F, "dtype": str(dtype), "rows": n,
                       "nodes": N, "form": form, "ms": ms,
                       "compile_s": compile_s}
                if ref is None:
                    ref = out
                else:
                    row["equal"] = bool(np.array_equal(
                        out.view(np.int32), ref.view(np.int32)))
                    ok &= row["equal"]
                rows.append(row)
                print(json.dumps(row), flush=True)
            del remapped
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
