"""The ranking gradient's ways between row order and its padded ``[Gp, L]``
layout, alone on the chip: device time and every bit of the shipped kernels
against the per-group forms they replaced.

    python3 tools/bench_rank_layout.py [--rows N] [--groups G] [--chunk C]
        [--long-group R] [--seed S] [--reps R] [--interpret]
        [--out FILE.json]

The group sizes are the ranking cell's (``benchmark/lib/data_rank.py``:
7,325,625 rows in 23,219 groups of 8 to 1,024, padded up to whole chunks of
512 groups); ``--long-group R`` adds one group of R rows after them, so that
a longest group past ``rank_layout.PIECE`` moves as pieces. Scores, weights
and padded sums are seeded. Forms, each way:

    in    gather  one ``dynamic_slice`` of L rows a group (``vmap``: a loop)
          span    ``ops/pallas/rank_layout.py rows_in``, as the gradient
                  calls it (``objective/ranking.py _padded_layout``)
    back  gather  one element a row, ``pad[group, slot]``, for both sums
          span    ``rows_out`` for both sums (``ranking._gpairs_of``)

A way back takes both sums to the weighted ``[n, 1, 2]`` gradient pairs. A
row of the report: way, form, ``ms`` (the least of ``--reps`` timed calls,
``block_until_ready`` around each), ``compile_s`` and ``equal`` (against the
``gather`` form, every bit; a zero's sign aside). Fails unless jax's default
backend is a TPU and every form is equal; ``--interpret`` lets it run off a
TPU, where the kernels run in the Pallas interpreter, for a rehearsal on
the CPU at a small ``--rows``.

Four other forms were timed at the cell's shape before ``span`` was chosen
(an XLA tile gather with seven rolls, the same with a Pallas lane rotate, a
DMA a group, an ascending ``dynamic_update_slice`` loop back): their table
is in PERF.md §6.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import numpy as np  # noqa: E402


def gather_in(rows, starts, sizes, L):
    import jax
    import jax.numpy as jnp

    ext = jnp.concatenate([rows, jnp.zeros((L,), rows.dtype)])
    block = jax.vmap(lambda p: jax.lax.dynamic_slice(ext, (p,), (L,)))(starts)
    return jnp.where(jnp.arange(L)[None, :] < sizes[:, None], block, -jnp.inf)


def gather_back(g_pad, h_pad, qidx, slot, w_row):
    import jax.numpy as jnp

    return jnp.stack([g_pad[qidx, slot] * w_row, h_pad[qidx, slot] * w_row],
                     axis=-1)[:, None, :]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=7_325_625)
    ap.add_argument("--groups", type=int, default=23_219)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--long-group", type=int, default=0)
    ap.add_argument("--seed", type=int, default=2147510040)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--interpret", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from lib.data_rank import group_sizes
    from xgboost_tpu.objective import ranking as rk
    from xgboost_tpu.ops.pallas import rank_layout

    if jax.default_backend() != "tpu" and not args.interpret:
        raise SystemExit(f"no TPU: jax's backend is {jax.default_backend()}")
    sizes = group_sizes(args.rows, args.groups, args.seed)
    if args.long_group:
        sizes = np.append(sizes, args.long_group)
    ptr = np.concatenate([[0], np.cumsum(sizes)])
    G, L, n = len(sizes), int(sizes.max()), int(ptr[-1])
    Gp = -(-G // args.chunk) * args.chunk
    tiles = jax.tree.map(jnp.asarray, rk._tile_tables(ptr, Gp))
    pieces = -(-L // rank_layout.PIECE)
    print(f"n {n}, G {G} padded to {Gp}, L {L} in {pieces} piece(s) of at "
          f"most {rank_layout.PIECE} slots, {rank_layout.GROUPS} a grid "
          f"step", flush=True)
    qidx = jnp.asarray(np.repeat(np.arange(G), sizes), jnp.int32)
    slot = jnp.asarray(np.arange(n) - np.repeat(ptr[:-1], sizes), jnp.int32)
    starts = jnp.asarray(np.pad(ptr[:-1], (0, Gp - G)), jnp.int32)
    rng = np.random.RandomState(args.seed % (2 ** 31))
    rows = jnp.asarray(rng.randn(n).astype(np.float32))
    w_row = jnp.asarray(rng.rand(n).astype(np.float32) + 0.5)
    g_pad = jnp.asarray(rng.randn(Gp, L).astype(np.float32))
    h_pad = jnp.asarray(rng.rand(Gp, L).astype(np.float32))

    forms = {
        ("in", "gather"): (lambda r, st, sz: gather_in(r, st, sz, L),
                           (rows, starts, tiles.sizes)),
        ("in", "span"): (lambda r, t: rk._padded_layout(r, t, L, -jnp.inf),
                         (rows, tiles)),
        ("back", "gather"): (gather_back, (g_pad, h_pad, qidx, slot, w_row)),
        ("back", "span"): (lambda g, h, t, w: rk._gpairs_of(
            g, h, None, None, t, w, L, 0)[0], (g_pad, h_pad, tiles, w_row)),
    }
    report, want, ok = [], {}, True
    for (way, form), (fn, arrays) in forms.items():
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*arrays).compile()
        compile_s = time.perf_counter() - t0
        got = np.asarray(jax.block_until_ready(compiled(*arrays)))
        best = float("inf")
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(compiled(*arrays))
            best = min(best, time.perf_counter() - t)
        if form == "gather":
            want[way] = got
        equal = bool(np.array_equal(got, want[way]))
        ok &= equal
        row = dict(way=way, form=form, ms=round(1e3 * best, 3),
                   compile_s=round(compile_s, 2), equal=equal)
        report.append(row)
        print(json.dumps(row), flush=True)
    dev = jax.devices()[0]
    summary = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                          "count": jax.device_count()},
               "shape": {"rows": n, "groups": G, "padded_groups": Gp,
                         "longest": L, "pieces": pieces,
                         "step_groups": rank_layout.GROUPS},
               "forms": report, "ok": ok}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps(summary), flush=True)
    if not ok:
        raise SystemExit("a form's bits differ from the gather form's")


if __name__ == "__main__":
    main()
