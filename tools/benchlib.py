"""Shared benchmarking helpers for tools/ scripts.

Per-iteration cost is the SLOPE between two repetition counts of a jitted
fori_loop, so a fixed per-sync cost cancels, and the sync is a scalar
device_get. Both were chosen for an earlier, remote-attached chip; whether
the directly attached one needs them is unverified (ROADMAP A1 replaces
them with block_until_ready medians).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def slope_bench(make_body, *args, reps_lo: int = 5, ratio: int = 5):
    """make_body: (i, acc, *args) -> array, perturbed by ``i``/``acc`` so XLA
    cannot hoist it out of the loop. Returns (ms_per_iter, compile_s)."""
    def total(reps):
        @jax.jit
        def run(*a):
            def body(i, acc):
                out = make_body(i, acc, *a)
                return acc + jnp.sum(out).astype(jnp.float32)
            return jax.lax.fori_loop(0, reps, body, jnp.float32(0.0))
        t0 = time.perf_counter()
        float(run(*args))  # compile + warm; scalar get = real sync
        compile_s = time.perf_counter() - t0
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            float(run(*args))
            best = min(best, time.perf_counter() - t0)
        return best * 1e3, compile_s
    lo, hi = reps_lo, reps_lo * ratio
    t_lo, c1 = total(lo)
    t_hi, c2 = total(hi)
    return (t_hi - t_lo) / (hi - lo), c1 + c2
