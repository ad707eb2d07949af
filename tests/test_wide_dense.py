"""A dense matrix 2,000 features wide on the default path (the PASCAL epsilon
job, benchmark cell ``pascal-epsilon.wide-train``).

At F = 2,000 with one-byte ids two Mosaic kernels of the ``fused`` schedule
need more than the default scoped VMEM: the refine build at 128 nodes and
36 slots (``build_hist_pallas``: 200 features a block) and the last level's
advance over the whole-F tile (``advance_leaf_pallas``). Each states its
own limit there, and nowhere else. Here, on the CPU:

- the kernels of the five accepted cells' shapes keep their feature block,
  row block and compiler parameters (the block rule of the parent), and
  only the wide shapes state a limit, which the gauge
  ``xtpu_kernel_vmem_bytes`` reads;
- the resized kernels in interpret mode against their XLA bodies, bit for
  bit, on a few thousand rows;
- ``xgb.train`` at 4,096 x 2,000, depth 8, against the benchmark's plain
  blockwise reference (``benchmark/lib/reference_blocks.py``).

Compiling them for the chip is ``tests/test_tpu_compile.py``'s."""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from xgboost_tpu.obs import metrics as obs_metrics
from xgboost_tpu.ops.histogram import build_hist_segment
from xgboost_tpu.ops.pallas import histogram as ph
from xgboost_tpu.ops.partition import update_positions

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import data_wide  # noqa: E402
from lib import reference_blocks as rb  # noqa: E402

R = 2048
NODES = [2 ** d for d in range(8)]              # 1 .. 128, depth 8


def pallas_eqn(fn, *args):
    """The ``pallas_call`` equation of ``fn``'s jaxpr."""
    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                hit = find(sub)
                if hit is not None:
                    return hit
        return None
    return find(jax.make_jaxpr(fn)(*args).jaxpr)


def kernel_shape(eqn):
    """(grid, block shapes, the Mosaic compiler parameters or None)."""
    gm = eqn.params["grid_mapping"]
    blocks = [tuple(getattr(d, "block_size", d) for d in bm.block_shape)
              for bm in gm.block_mappings]
    return gm.grid, blocks, eqn.params["compiler_params"].get("mosaic_tpu")


def build_shape(F, B, N, dtype=jnp.uint8, n=2 * R):
    return kernel_shape(pallas_eqn(
        lambda b, g, p: ph.build_hist_pallas(b, g, p, N, B),
        jnp.zeros((F, n), dtype), jnp.zeros((n, 2), jnp.float32),
        jnp.zeros((n,), jnp.int32)))


def leaf_shape(F, N, dtype, n=2 * R):
    return kernel_shape(pallas_eqn(
        lambda b, p, f, t, d, c, v: ph.advance_leaf_pallas(
            b, p, f, t, d, c, v, n_prev=N, missing_bin=256),
        jnp.zeros((F, n), dtype), jnp.zeros((n,), jnp.int32),
        jnp.zeros((N,), jnp.int32), jnp.zeros((N,), jnp.int32),
        jnp.zeros((N,), bool), jnp.zeros((N,), bool),
        jnp.zeros((4 * N - 1,), jnp.float32)))


def parent_feature_block(F, B, N):
    """The parent's rule, written out: the accumulator's 8 MiB budget, no
    body past ``FEATURE_GROUP`` features."""
    cap = max((8 * 2 ** 20) // (B * 2 * N * 4), 8)
    return ph._feature_block(F, min(cap, ph.FEATURE_GROUP))


# the widths of higgs-11m (both cells), criteo-ctr, istella-letor and
# bosch-line; the two-level search's coarse and refine widths
@pytest.mark.parametrize("B", [20, 36])
@pytest.mark.parametrize("F", [28, 67, 220, 968])
def test_block_rule_keeps_the_cells_kernels(F, B):
    for N in NODES:
        F_blk = parent_feature_block(F, B, N)
        grid, blocks, params = build_shape(F, B, N)
        F_pad = -(-F // F_blk) * F_blk
        assert grid == (F_pad // F_blk, 2), (F, B, N)
        assert blocks[0] == (F_blk, R) and blocks[3] == (F_blk * B, 2 * N)
        assert params is None, (F, B, N)     # the default scoped VMEM


@pytest.mark.parametrize("F,dtype", [(28, jnp.uint8), (67, jnp.uint8),
                                     (220, jnp.uint8), (968, jnp.uint16)])
def test_advance_leaf_keeps_the_cells_kernels(F, dtype):
    # a depth-8 cell's last level, the one past DENSE_LEVEL_MAX
    grid, blocks, params = leaf_shape(F, 128, dtype)
    assert grid == (2,) and blocks[1] == (F, R)
    assert params is None, F


def test_wide_kernels_state_their_limit():
    """At F = 2,000 the refine build at 128 nodes and 36 slots, and the last
    level's advance, state what they hold with the default's room beside
    it; the narrower levels and the coarse width keep the default. The
    gauge keeps each kernel's largest."""
    reg = obs_metrics.get_registry()
    for kernel in ("build_hist_int8", "advance_leaf"):
        reg.set_gauge("xtpu_kernel_vmem_bytes", 0, (("kernel", kernel),))
    grid, blocks, params = build_shape(2000, 36, 128)
    assert grid == (10, 2) and blocks[0] == (200, R)
    want = ph._int8_vmem_bytes(200, 2000, 36, 128, R, 200, 1, True)
    assert want > ph.SCOPED_VMEM
    assert params.vmem_limit_bytes == want + ph.SCOPED_VMEM
    for B, N in ((36, 64), (20, 128)):
        grid, blocks, params = build_shape(2000, B, N)
        assert blocks[0] == (200, R) and params is None, (B, N)
    for N in (128, 256):
        grid, blocks, params = leaf_shape(2000, N, jnp.uint8)
        assert blocks[1] == (2000, R)
        assert params.vmem_limit_bytes == 2000 * R * 6 + ph.SCOPED_VMEM
    got = obs_metrics.kernel_vmem_bytes()
    assert got["build_hist_int8"] == want + ph.SCOPED_VMEM
    assert got["advance_leaf"] == 2000 * R * 6 + ph.SCOPED_VMEM


def test_boundary_sweep_keeps_its_limit_rule():
    """``fused_advance_coarse_pallas`` is as it was: the default scoped VMEM
    for one group, its tile's stated limit for several."""
    def sweep(F, dtype, N=32):
        prev, n = N // 2, 2 * R
        return kernel_shape(pallas_eqn(
            lambda b, g, p, f, t, d, c: ph.fused_advance_coarse_pallas(
                b, g, p, f, t, d, c, lo_prev=prev - 1, n_prev=prev,
                lo=N - 1, n_level=N, missing_bin=256),
            jnp.zeros((F, n), dtype), jnp.zeros((n, 2), jnp.float32),
            jnp.zeros((n,), jnp.int32), jnp.zeros((prev,), jnp.int32),
            jnp.zeros((prev,), jnp.int32), jnp.zeros((prev,), bool),
            jnp.zeros((prev,), bool)))
    for F in (28, 67, 220):
        assert sweep(F, jnp.uint8)[2] is None
    assert sweep(968, jnp.uint16)[2].vmem_limit_bytes \
        == 968 * R * (4 + 2 * 2) + 16 * 2 ** 20
    assert sweep(2000, jnp.uint8, 16)[2].vmem_limit_bytes \
        == 2000 * R * (4 + 2) + 16 * 2 ** 20


# ---- the resized kernels against their XLA bodies ---------------------------

def test_refine_build_at_2000_features_equals_the_xla_build():
    """``build_hist_pallas`` in ten blocks of 200 features, 128 nodes and 36
    slots, against the XLA build of the same 15-bit fixed-point pairs. Every
    sum of a few rows is an exact integer on both sides: the kernel's sums,
    scaled back, are the XLA build's bit for bit; the dequantised values
    agree to two ulps (each program computes the scale's reciprocal)."""
    F, n, N, B = 2000, 2 * R, 128, 36
    rng = np.random.RandomState(41)
    bins = rng.randint(0, B, (F, n)).astype(np.uint8)
    gpair = rng.randn(n, 2).astype(np.float32)
    gpair[:, 1] = np.abs(gpair[:, 1])
    rel = rng.randint(0, N + 1, n).astype(np.int32)   # N: inactive rows
    got = np.asarray(ph.build_hist_pallas(
        jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(rel), N, B,
        interpret=True))
    g = jnp.asarray(gpair)
    scale = np.asarray(32512.0 / jnp.maximum(jnp.max(jnp.abs(g), axis=0),
                                             1e-30))
    q = jnp.round(g * scale).astype(jnp.float32)       # integers, exact
    sums = np.asarray(build_hist_segment(jnp.asarray(bins.T), q,
                                         jnp.asarray(rel), N, B))
    assert got.shape == sums.shape == (N, F, B, 2)
    np.testing.assert_array_equal(np.rint(got * scale), sums)
    np.testing.assert_array_max_ulp(got, sums * (1.0 / scale), maxulp=2)
    assert float(np.abs(sums[:, F - 1]).sum()) > 0


@pytest.mark.parametrize("n_prev", [128, 256])
def test_advance_leaf_at_2000_features_equals_the_walk(n_prev):
    """The last level's kernel over the whole 2,000-feature tile against
    ``update_positions`` + ``leaf_value[positions]``, bit for bit; splits
    on the first and the last feature among them."""
    F, n = 2000, 2 * R
    rng = np.random.RandomState(n_prev)
    lo, max_nodes = n_prev - 1, 4 * n_prev - 1
    bins = rng.randint(0, 256, (n, F)).astype(np.uint8)
    positions = rng.randint(lo, lo + n_prev, n).astype(np.int32)
    stopped = rng.rand(n) < 0.15
    positions[stopped] = rng.randint(0, lo, stopped.sum())
    can_split = rng.rand(n_prev) < 0.8
    sf = np.full(max_nodes, -1, np.int32)
    feats = rng.randint(0, F, n_prev)
    feats[:2] = (0, F - 1)
    sf[lo:lo + n_prev] = np.where(can_split, feats, -1)
    sb = np.zeros(max_nodes, np.int32)
    sb[lo:lo + n_prev] = np.where(can_split, rng.randint(0, 255, n_prev), 0)
    dl = np.zeros(max_nodes, bool)
    isf = np.zeros(max_nodes, bool)
    isf[lo:lo + n_prev] = can_split
    leaf = rng.randn(max_nodes).astype(np.float32)
    arrs = tuple(jnp.asarray(a) for a in (sf, sb, dl, isf))
    pos_k, delta_k = ph.advance_leaf_pallas(
        jnp.asarray(bins.T), jnp.asarray(positions),
        *(a[lo:lo + n_prev] for a in arrs), jnp.asarray(leaf),
        n_prev=n_prev, missing_bin=256, interpret=True)
    pos_ref = update_positions(jnp.asarray(bins), jnp.asarray(positions),
                               *arrs, 256)
    np.testing.assert_array_equal(np.asarray(pos_k), np.asarray(pos_ref))
    np.testing.assert_array_equal(np.asarray(delta_k).view(np.int32),
                                  leaf[np.asarray(pos_ref)].view(np.int32))
    assert (np.asarray(pos_ref) != positions).any()


# ---- the booster against the plain reference --------------------------------

PARAMS = {"objective": "binary:logistic", "max_depth": 8, "eta": 0.1,
          "max_bin": 256, "tree_method": "hist"}
LIMITS = os.path.join(BENCH, "limits", "pascal-epsilon.wide-train.json")


@pytest.fixture(scope="module")
def wide():
    return data_wide.epsilon_like(4096, 2000, seed=2 ** 31 + 41)


def stated(bst):
    model = json.loads(bytes(bst.save_raw("json")))["learner"]
    return [{"left": np.asarray(t["left_children"], np.int64),
             "right": np.asarray(t["right_children"], np.int64),
             "feat": np.asarray(t["split_indices"], np.int64),
             "thr": np.asarray(t["split_conditions"], np.float32),
             "value": np.asarray(t["split_conditions"], np.float32),
             "sum_hess": np.asarray(t["sum_hessian"], np.float64),
             "gain": np.asarray(t["loss_changes"], np.float64)}
            for t in model["gradient_booster"]["trees"]], float(
                model["learner_model_param"]["base_score"][0])


# the CPU's ``auto`` (the exact one-pass build) and the two-level fused
# schedule a TPU's ``auto`` runs, its boundaries on the XLA body here
@pytest.mark.parametrize("hist_method", ["auto", "fused"])
def test_train_at_2000_features_against_the_plain_reference(wide,
                                                             hist_method):
    import xgboost_tpu as xgb

    X, y = wide
    dtrain = xgb.DMatrix(X, label=y)
    bst = xgb.train(dict(PARAMS, hist_method=hist_method), dtrain, 4)
    trees, base = stated(bst)
    margin = np.asarray(bst._state_of(dtrain, is_train=True)["margin"],
                        np.float32).reshape(-1)
    outputs = {"trees": trees, "base_margin": base, "margin": margin,
               "warm_rounds": 2, "replica_gap": 0.0, "rounds_claimed": 4}
    # every row on the sample: the exact search sees what the program saw
    values = rb.numbers(outputs, rb.array_source(X, y, data_wide.BLOCK),
                        PARAMS, follow_rounds=2, stride_blocks=1,
                        top_levels=3)
    with open(LIMITS) as fh:
        limits = json.load(fh)
    over = {k: v for k, v in values.items() if not v <= limits[k]}
    assert not over, (over, values)
    assert max(t["feat"].max() for t in trees) > 1000   # the width is used
    assert sum((t["left"] >= 0).sum() for t in trees) > 4 * 64
