"""Phase breakdown of one boosting round on the real chip.

Each phase (histogram, split-eval, position-advance, gradient) is timed as
ONE jitted program containing the same 6-level loop as the real fused round,
repeated REPS times via fori_loop with per-iteration input perturbation
(defeats CSE) and a scalar carry device_get'd at the end as the sync. One
compilation per phase keeps total compile time bounded. Run on the TPU:

    python tools/profile_round.py            # 1M x 28 (bench config)
    BENCH_ROWS=4000000 python tools/profile_round.py
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

ROWS = int(os.environ.get("BENCH_ROWS", 1_000_000))
COLS = 28
DEPTH = 6
MAX_BIN = 256
REPS = int(os.environ.get("PROFILE_REPS", 5))
PHASES = set(os.environ.get("PROFILE_PHASES",
                            "hist,coarse,eval,adv,grad,full").split(","))


from benchlib import slope_bench  # noqa: E402


def bench(body, label, *args):
    """body(i, acc, *args) -> array; slope-measured (see benchlib)."""
    ms, compile_s = slope_bench(body, *args, reps_lo=REPS)
    print(f"  {label}: {ms:8.2f} ms/round-equivalent "
          f"(compile {compile_s:.0f}s)", flush=True)
    return ms


def main():
    print(f"backend={jax.default_backend()}", flush=True)
    rng = np.random.RandomState(42)
    X = rng.randn(ROWS, COLS).astype(np.float32)
    w = rng.randn(COLS).astype(np.float32)
    y = (X @ w + rng.randn(ROWS).astype(np.float32) > 0).astype(np.float32)

    import xgboost_tpu as xgb
    from xgboost_tpu.ops.histogram import build_hist
    from xgboost_tpu.ops.partition import advance_positions_level
    from xgboost_tpu.ops.split import evaluate_splits
    from xgboost_tpu.tree.param import TrainParam

    t0 = time.perf_counter()
    dm = xgb.DMatrix(X, label=y)
    binned = dm.binned(MAX_BIN)
    print(f"dmatrix+binning: {time.perf_counter() - t0:.2f}s", flush=True)

    bins = jnp.asarray(binned.bins)
    max_nbins = binned.max_nbins
    n_real = jnp.asarray(binned.n_real_bins())
    param = TrainParam()
    param.update_allow_unknown({"max_depth": DEPTH, "eta": 0.1,
                                "max_bin": MAX_BIN})

    gpair = jnp.stack([jnp.asarray(y) - 0.5,
                       jnp.full((ROWS,), 0.25, jnp.float32)], axis=1)
    bins_t = bins.T
    row_iota = jnp.arange(ROWS, dtype=jnp.int32)

    # ---- phase: histogram, all 6 levels per rep (arrays passed as args —
    # a closed-over matrix would be captured as a program constant).
    # "hist" measures the production auto path (Pallas int8x2 via
    # build_hist).
    def hist_body(i, acc, bt, gpr, iota):
        gp = gpr * (1.0 + i.astype(jnp.float32) * 1e-7 + acc * 1e-30)
        g = jnp.float32(0.0)
        for d in range(DEPTH):
            h = build_hist(bt.T, gp, iota % (2 ** d), 2 ** d, max_nbins,
                           method="auto", bins_t=bt)
            g = g + jnp.sum(h).astype(jnp.float32)
        return g

    ms_hist = (bench(hist_body, "hist auto/pallas (6 levels)",
                     bins_t, gpair, row_iota)
               if "hist" in PHASES else 0.0)

    # ---- phase: two-level coarse->refine histogram, all 6 levels per rep
    # (the DEFAULT production path at scale since round 5: coarse pass +
    # window choice + refine pass + assemble — mirrors tree/grow.py)
    if "coarse" in PHASES:
        from xgboost_tpu.ops.split import (WINDOW, assemble_two_level,
                                           choose_refine_window,
                                           coarse_bin_ids)
        from xgboost_tpu.ops.split import COARSE_B

        has_missing = binned.has_missing
        missing_bin = max_nbins - 1 if has_missing else max_nbins

        def coarse_body(i, acc, bt, gpr, iota):
            gp = gpr * (1.0 + i.astype(jnp.float32) * 1e-7 + acc * 1e-30)
            cb_t = coarse_bin_ids(bt.astype(jnp.int32), missing_bin)
            g = jnp.float32(0.0)
            for d in range(DEPTH):
                N = 2 ** d
                rel = iota % N
                hist_c = build_hist(cb_t.T, gp, rel, N, COARSE_B,
                                    method="auto", bins_t=cb_t)
                parent = jnp.sum(hist_c[:, 0], axis=1)
                span = choose_refine_window(hist_c, parent, n_real, param,
                                            has_missing)
                hist_r = build_hist(bt.T, gp, rel, N, WINDOW + 4,
                                    method="auto", bins_t=bt, window=span,
                                    missing_bin=missing_bin)[:, :, :WINDOW, :]
                hist, _ = assemble_two_level(hist_c, hist_r, span, n_real,
                                             has_missing)
                g = g + jnp.sum(hist).astype(jnp.float32)
            return g

        bench(coarse_body, "hist two-level coarse (6 levels)",
              bins_t, gpair, row_iota)

    # ---- phase: split evaluation, all 6 levels per rep (args, not
    # closures: a closed-over histogram becomes a program constant).
    # hist32 comes from the production Pallas path.
    hist32 = (jax.jit(lambda bt, gp, it: build_hist(
        bt.T, gp, it % 32, 32, max_nbins, method="auto", bins_t=bt))(
            bins_t, gpair, row_iota)
        if "eval" in PHASES else None)
    fmask = jnp.ones((1, COLS), bool)

    def eval_body(i, acc, h32):
        pert = 1.0 + i.astype(jnp.float32) * 1e-7 + acc * 1e-30
        g = jnp.float32(0.0)
        for d in range(DEPTH):
            h = h32[: 2 ** d] * pert
            ps = jnp.sum(h, axis=(1, 2)) / COLS
            r = evaluate_splits(h, ps, n_real, param,
                                feature_mask=fmask, has_missing=True)
            g = g + jnp.sum(r.gain).astype(jnp.float32)
        return g

    ms_eval = (bench(eval_body, "split eval (6 levels)", hist32)
               if "eval" in PHASES else 0.0)

    # ---- phase: position advance, all 6 levels per rep
    bins_f32 = bins.astype(jnp.float32)

    def adv_body(i, acc, bf32, iota):
        bump = jnp.minimum(i, 0) + (acc > 1e30).astype(jnp.int32)
        g = jnp.float32(0.0)
        for d in range(DEPTH):
            nl = 2 ** d
            rel = iota % nl
            pos = (nl - 1) + rel + bump
            feats = jnp.arange(nl, dtype=jnp.int32) % COLS
            sbins = jnp.full((nl,), 100, jnp.int32)
            p = advance_positions_level(
                bf32, pos, rel, feats, sbins,
                jnp.zeros((nl,), bool), jnp.ones((nl,), bool),
                max_nbins - 1)
            g = g + jnp.sum(p).astype(jnp.float32) * 1e-9
        return g

    ms_adv = (bench(adv_body, "advance positions (6 levels)",
                    bins_f32, row_iota)
              if "adv" in PHASES else 0.0)

    # ---- phase: gradient
    from xgboost_tpu.objective import get_objective
    import types
    obj = get_objective("binary:logistic", {})
    sinfo = types.SimpleNamespace(labels=jnp.asarray(y), weights=None)
    margin0 = jnp.zeros((ROWS, 1), jnp.float32)

    def grad_body(i, acc, m0, lab):
        import types as _t
        si = _t.SimpleNamespace(labels=lab, weights=None)
        m = m0 + i.astype(jnp.float32) * 1e-7 + acc * 1e-30
        return obj.get_gradient(m, si, 0)

    ms_grad = (bench(grad_body, "gradient (binary:logistic)",
                     margin0, sinfo.labels)
               if "grad" in PHASES else 0.0)

    # ---- full fused round, amortised over 10 rounds
    if "full" not in PHASES:
        print(f"partial totals: hist {ms_hist:.1f} eval {ms_eval:.1f} "
              f"adv {ms_adv:.1f} grad {ms_grad:.1f}", flush=True)
        return
    params = {"objective": "binary:logistic", "max_depth": DEPTH,
              "eta": 0.1, "max_bin": MAX_BIN}
    xgb.train(params, dm, 2, verbose_eval=False)  # warm-up/compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        bst = xgb.train(params, dm, 10, verbose_eval=False)
        st = next(iter(bst._caches.values()))
        float(jnp.sum(st["margin"]))  # force the whole chain
        best = min(best, time.perf_counter() - t0)
    per_round = best / 10 * 1e3
    print(f"\nfull fused round: {per_round:.1f} ms/round "
          f"({10 / best:.2f} rounds/s)", flush=True)
    accounted = ms_hist + ms_eval + ms_adv + ms_grad
    print(f"accounted: {accounted:.1f} ms/round (hist {ms_hist:.1f} + "
          f"eval {ms_eval:.1f} + advance {ms_adv:.1f} + grad {ms_grad:.1f})"
          f"; unaccounted {per_round - accounted:.1f} ms = delta "
          f"accumulation + host dispatch + fusion differences", flush=True)


if __name__ == "__main__":
    main()
