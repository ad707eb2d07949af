"""The histogram kernels in feature groups against their straight-line bodies,
alone on the chip: bit for bit, device time, and the host's time to lower and
compile each.

``ops/pallas/histogram.py FEATURE_GROUP`` bounds what a kernel body unrolls
(PR 36). Before a round runs the grouped kernels, this script runs each of
them beside the body it replaced (the group lifted past F) on one seeded
matrix a shape, at the benchmark's four widths (28 and 67 and 220 in bytes,
968 in two-byte ids with the missing slot):

    python3 tools/bench_hist_groups.py [--shapes 28,67,220,968] [--rows N]
        [--nodes 1,32,128] [--widths 20,36] [--groups 128] [--blocks 248]
        [--out FILE.json]

``--groups`` times the grouped kernels under other group sizes too;
``--blocks`` times ``build_hist_pallas`` at 968 under explicit feature
blocks. ``--forms`` (PR 37) times instead the candidate forms of the
histogram dot (``tools/hist_dot_forms.py``: ``stacked:pad:128`` is G
features' one-hots, each padded to whole vregs, 128 rows a dot;
``stacked:dense:8`` eight features' unpadded; ``held:pad:128`` the one-hot
as the operand the MXU holds; a fourth field is the row block, timed and
not compared) beside the shipped kernels, ``fused_advance_coarse`` among
them, and beside the dot a feature, every histogram compared with the dot
a feature's bit for bit.

A row of the report: kernel, F, ids, nodes, group, the body's features,
``lower_s`` and ``compile_s`` (host), ``ms`` (the least of ``--reps`` timed
calls, ``block_until_ready`` around each) and ``equal`` (against the
straight-line kernel's output, or the dot a feature's, every bit). Fails
unless jax's default backend is a TPU and every comparison is equal.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

STRAIGHT = 1 << 20


def matrix(F, ids, rows, seed=36):
    missing = 256 if ids == "uint16" else 255
    rng = np.random.RandomState(seed + F)
    bins = rng.randint(0, missing, (F, rows)).astype(ids)
    if ids == "uint16":
        bins[rng.rand(F, rows) < 0.81] = missing
    gpair = rng.randn(rows, 2).astype(np.float32)
    gpair[:, 1] = np.abs(gpair[:, 1])
    return bins, gpair, missing


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default="28,67,220,968")
    ap.add_argument("--rows", type=int, default=1_183_747)
    ap.add_argument("--groups", default="")
    ap.add_argument("--blocks", default="")
    ap.add_argument("--nodes", default="1,32,128")
    ap.add_argument("--widths", default="20,36",
                    help="slots of build_hist_pallas: the two-level "
                         "search's coarse and refine widths")
    ap.add_argument("--forms", default="",
                    help="candidate dot forms, form:pack:rows-or-G[:R], "
                         "comma-separated; times them and nothing else")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default=None)
    ap.add_argument("--rehearse", action="store_true",
                    help="sandbox only: the CPU, kernels interpreted")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from xgboost_tpu.obs import metrics as obs_metrics
    from xgboost_tpu.ops.pallas import histogram as ph

    if jax.default_backend() != "tpu" and not args.rehearse:
        print(f"jax's default backend is {jax.default_backend()!r}, not a "
              "TPU", file=sys.stderr)
        return 3
    shipped = ph.FEATURE_GROUP
    registry = obs_metrics.get_registry()
    report = []

    def run(kernel, F, ids, nodes, group, make, arrays, want=None, **note):
        """Lower, compile and time ``make()`` under ``group``; -> outputs.
        ``make()`` calls the wrapper's body (``__wrapped__``) under a jit of
        its own: the module's jit would serve the trace it cached for
        another group."""
        ph.FEATURE_GROUP = group
        registry.set_gauge("xtpu_hist_body_features", 0)
        fn = jax.jit(make())
        t0 = time.perf_counter()
        lowered = fn.lower(*arrays)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        out = jax.block_until_ready(compiled(*arrays))
        best = float("inf")
        for _ in range(args.reps):
            t = time.perf_counter()
            jax.block_until_ready(compiled(*arrays))
            best = min(best, time.perf_counter() - t)
        host = [np.asarray(o) for o in jax.tree_util.tree_leaves(out)]
        row = dict(kernel=kernel, F=F, ids=ids, nodes=nodes,
                   group="straight" if group == STRAIGHT else group,
                   body=obs_metrics.hist_body_features(), lower_s=t1 - t0,
                   compile_s=t2 - t1, ms=1e3 * best, **note)
        if want is not None:
            row["equal"] = all(np.array_equal(a, b)
                               for a, b in zip(host, want))
        report.append(row)
        print(json.dumps(row), flush=True)
        return host

    if args.forms:
        forms(args, run, report, jnp, ph)
        return finish(args, report)
    groups = [shipped] + [int(g) for g in args.groups.split(",") if g]
    for F in [int(f) for f in args.shapes.split(",")]:
        ids = "uint16" if F == 968 else "uint8"
        bins, gpair, missing = matrix(F, ids, args.rows)
        rng = np.random.RandomState(F)
        bins_d, gpair_d = jnp.asarray(bins), jnp.asarray(gpair)
        for nodes in [int(n) for n in args.nodes.split(",")]:
            made = boundary(args, ph, jnp, rng, bins_d, gpair_d, F, nodes,
                            missing)
            if made:
                fused, arrays = made
                want = run("fused_advance_coarse", F, ids, nodes, STRAIGHT,
                           fused, arrays)
                for g in groups:
                    if F > g or g == shipped:
                        run("fused_advance_coarse", F, ids, nodes, g, fused,
                            arrays, want)
            # the coarse and the refine build of a level
            rel = jnp.asarray(rng.randint(0, nodes + 1, args.rows)
                              .astype(np.int32))
            for width in [int(w) for w in args.widths.split(",")]:
                local = jnp.asarray(np.where(
                    bins == missing, width - 1,
                    bins.astype(np.int64) * (width - 1) // missing)
                    .astype(ids))
                arrays = (local, gpair_d, rel)

                def build(width=width, nodes=nodes, block=None):
                    return lambda *a: ph.build_hist_pallas.__wrapped__(
                        *a, nodes, width, feat_block=block,
                        interpret=args.rehearse)
                want = run("build_hist_int8", F, ids, nodes, STRAIGHT, build,
                           arrays, width=width)
                for g in groups:
                    if F > g or g == shipped:
                        run("build_hist_int8", F, ids, nodes, g, build,
                            arrays, want, width=width)
                if F == 968:
                    for block in [int(b) for b in args.blocks.split(",")
                                  if b]:
                        # this block and no other: the wrapper would
                        # choose its own under a cap
                        choose = ph._feature_block
                        ph._feature_block = lambda F, cap, step=8, b=block: b
                        try:
                            run("build_hist_int8", F, ids, nodes, shipped,
                                build, arrays, want, width=width, block=block)
                        finally:
                            ph._feature_block = choose
                del local
        del bins_d, gpair_d
    ph.FEATURE_GROUP = shipped
    return finish(args, report)


def boundary(args, ph, jnp, rng, bins_d, gpair_d, F, nodes, missing):
    """The boundary sweep below ``nodes // 2`` splitting nodes, where its
    gate's two limits admit it -> (make, arrays) for ``run``, or None."""
    prev = nodes // 2
    if not (1 <= prev <= 64 and F * 20 * 2 * nodes * 4 <= 8 * 2 ** 20):
        return None
    lo_prev = prev - 1
    pos = jnp.asarray(rng.randint(lo_prev, lo_prev + prev, args.rows)
                      .astype(np.int32))
    # the first split reads the last feature: the last group
    feat = jnp.asarray(np.r_[F - 1, rng.randint(0, F, prev - 1)]
                       .astype(np.int32))
    thr = jnp.asarray(rng.randint(0, missing, prev).astype(np.int32))
    dleft = jnp.asarray(rng.rand(prev) < 0.5)
    can = jnp.asarray(np.ones(prev, bool))

    def fused():
        return lambda *a: ph.fused_advance_coarse_pallas.__wrapped__(
            *a, lo_prev=lo_prev, n_prev=prev, lo=nodes - 1, n_level=nodes,
            missing_bin=missing, interpret=args.rehearse)
    return fused, (bins_d, gpair_d, pos, feat, thr, dleft, can)


def forms(args, run, report, jnp, ph):
    """The candidate dot forms at every (F, nodes, width) asked for: the dot
    a feature (the rule patched to 1 feature a dot), the shipped rule, then
    each candidate, all against the first."""
    from tools.hist_dot_forms import hist_form, slots

    rule = ph._dot_features
    for F in [int(f) for f in args.shapes.split(",")]:
        ids = "uint16" if F == 968 else "uint8"
        bins, gpair, missing = matrix(F, ids, args.rows)
        rng = np.random.RandomState(F)
        bins_d, gpair_d = jnp.asarray(bins), jnp.asarray(gpair)

        def under(features, *a, **kw):
            """``run`` under the rule patched to ``features`` a dot."""
            ph._dot_features = features or rule
            try:
                return run(*a, **kw)
            finally:
                ph._dot_features = rule
        for nodes in [int(n) for n in args.nodes.split(",")]:
            made = boundary(args, ph, jnp, rng, bins_d, gpair_d, F, nodes,
                            missing)
            if made:
                want = under(lambda B, N: 1, "fused_advance_coarse", F, ids,
                             nodes, ph.FEATURE_GROUP, *made, width=20,
                             form="feature")
                under(None, "fused_advance_coarse", F, ids, nodes,
                      ph.FEATURE_GROUP, *made, want, width=20, form="shipped",
                      dot_features=rule(20, nodes))
        del bins_d
        for width in [int(w) for w in args.widths.split(",")]:
            local = jnp.asarray(np.where(
                bins == missing, width - 1,
                bins.astype(np.int64) * (width - 1) // missing).astype(ids))
            for nodes in [int(n) for n in args.nodes.split(",")]:
                rel = jnp.asarray(rng.randint(0, nodes + 1, args.rows)
                                  .astype(np.int32))
                arrays = (local, gpair_d, rel)

                def build(nodes=nodes, width=width):
                    return lambda *a: ph.build_hist_pallas.__wrapped__(
                        *a, nodes, width, interpret=args.rehearse)
                want = under(lambda B, N: 1, "build_hist_int8", F, ids,
                             nodes, ph.FEATURE_GROUP, build, arrays,
                             width=width, form="feature")
                if rule(width, nodes) != 1:
                    run("build_hist_int8", F, ids, nodes, ph.FEATURE_GROUP,
                        build, arrays, want, width=width, form="shipped",
                        dot_features=rule(width, nodes))
                for spec in args.forms.split(","):
                    form, pack, size, *rest = spec.split(":")
                    G = (max(int(size) // slots(width, pack), 1)
                         if pack == "pad" else int(size))
                    R = int(rest[0]) if rest else 2048

                    def candidate(form=form, pack=pack, G=G, R=R,
                                  nodes=nodes, width=width):
                        return lambda *a: hist_form.__wrapped__(
                            *a, nodes, width, form=form, pack=pack, group=G,
                            block_rows=R, interpret=args.rehearse)
                    try:
                        # another row block sums a feature's float32
                        # partial histograms in another grouping: timed,
                        # not compared
                        run("hist_form", F, ids, nodes, ph.FEATURE_GROUP,
                            candidate, arrays, want if R == 2048 else None,
                            width=width, form=spec, dot_features=G)
                    except Exception as e:    # Mosaic refused the form
                        row = dict(kernel="hist_form", F=F, ids=ids,
                                   nodes=nodes, width=width, form=spec,
                                   refused=str(e)[:300])
                        report.append(row)
                        print(json.dumps(row), flush=True)
            del local


def finish(args, report):
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as fh:
            json.dump(report, fh, indent=1)
    bad = [r for r in report if r.get("equal") is False]
    print(json.dumps({"ok": not bad, "rows": len(report),
                      "unequal": bad}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
