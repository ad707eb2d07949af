"""Split evaluation — vectorized enumeration over (node, feature, bin, direction).

Reference: ``HistEvaluator::EnumerateSplit`` forward/backward scans
(``src/tree/hist/evaluate_splits.h:218``), one-hot categorical (``:69``),
sorted-partition categorical (``EnumeratePart:146``), and the GPU block-scan +
ArgMax version (``src/tree/gpu_hist/evaluate_splits.cu:47-130``). TPU
formulation: because the histogram carries an explicit per-feature missing slot
(data/binned.py), both missing directions come from ONE cumulative sum —
``left = cumsum(present)`` for missing-right and ``left + missing`` for
missing-left — instead of two scans. Categorical features reuse the same dense
[nodes, features, dirs, bins] gain tensor (bin axis MINOR — see the layout
note in evaluate_splits): one-hot treats each category as the
right child; sorted-partition sorts categories by g/(h+lambda) and scans
prefixes (the winning prefix is packed into a uint32 bitmask in-kernel).
Everything ends in a flat argmax per node: pure VPU work that XLA fuses.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..tree.param import TrainParam, calc_gain

_EPS = 1e-6  # reference kRtEps


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["is_cat", "is_onehot"],
                   meta_fields=["cols", "numeric_bins"])
@dataclasses.dataclass(frozen=True, eq=False)
class CatInfo:
    """Categorical feature descriptors (bitmask word count is derived from the
    bin count where needed). ``is_cat`` and ``is_onehot`` are the pytree's
    arrays; ``cols`` and ``numeric_bins`` are static, part of the traced
    program's key: the categorical columns' indices and the most real bins
    a numeric column has, set by a grower that knows them from its cuts
    (``tree/grow.py TreeGrower``) and read by the two-level schedules,
    which build the categorical columns' histograms apart. None where
    the grower does not say."""

    is_cat: jnp.ndarray     # [F] bool
    is_onehot: jnp.ndarray  # [F] bool — cat with n_real <= max_cat_to_onehot
    cols: Optional[tuple] = None
    numeric_bins: Optional[int] = None


class SplitResult(NamedTuple):
    gain: jnp.ndarray          # [N] loss_chg of best split (-inf if none valid)
    feature: jnp.ndarray       # [N] int32
    bin: jnp.ndarray           # [N] int32 local threshold bin (go left if <=)
    default_left: jnp.ndarray  # [N] bool — direction for missing values
    left_sum: jnp.ndarray      # [N, 2]
    right_sum: jnp.ndarray     # [N, 2]
    is_cat: jnp.ndarray        # [N] bool — categorical split chosen
    cat_words: jnp.ndarray     # [N, W] uint32 — categories going LEFT


def _pack_mask(mask: jnp.ndarray, n_words: int) -> jnp.ndarray:
    """[N, B-1] bool -> [N, W] uint32 little-endian bit words."""
    N, nb = mask.shape
    pad = n_words * 32 - nb
    if pad:
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    m = mask.reshape(N, n_words, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[None, None]
    return jnp.sum(m * weights, axis=2, dtype=jnp.uint32)


def evaluate_splits(hist: jnp.ndarray, parent_sum: jnp.ndarray,
                    n_real_bins: jnp.ndarray, param: TrainParam,
                    feature_mask: Optional[jnp.ndarray] = None,
                    monotone: Optional[jnp.ndarray] = None,
                    node_lower: Optional[jnp.ndarray] = None,
                    node_upper: Optional[jnp.ndarray] = None,
                    cat: Optional[CatInfo] = None,
                    has_missing: bool = True) -> SplitResult:
    """hist: [N, F, B, 2] with missing mass in slot B-1 when ``has_missing``
    (all B slots are real bins otherwise); parent_sum: [N, 2];
    n_real_bins: [F]; feature_mask: [F] or [N, F] bool (colsample /
    interaction constraints), True = usable.

    With ``monotone`` ([F] in {-1,0,1}) set, gains are computed from child
    weights clamped into the node's [node_lower, node_upper] interval and
    sign-violating splits are rejected (reference ``TreeEvaluator``,
    ``src/tree/split_evaluator.h:28``)."""
    # LAYOUT NOTE: every dense plane here keeps the BIN axis minor
    # ([N, F, dirs, bins] / [N, F, dirs, 2, bins]). With the (dirs, 2) pair
    # minor instead, XLA tiles each (8, 128) vector register around 1-2
    # valid elements — a 64x physical blow-up that made this function cost
    # 22 ms/round at depth 6 (profiled; see docs/performance.md).
    N, F, B, _ = hist.shape
    nb = B - 1 if has_missing else B                      # real-bin slots
    # [N, F, 2, nb]: (g,h) ahead of the bin axis
    present = jnp.moveaxis(hist[:, :, :nb, :], 3, 2)
    if has_missing:
        miss = hist[:, :, B - 1, :]                       # [N,F,2]
    else:
        miss = jnp.zeros(hist.shape[:2] + (2,), hist.dtype)
    cum = jnp.cumsum(present, axis=3)                     # left sums, missing->right
    bins_idx = jnp.arange(nb, dtype=jnp.int32)

    # dir 0 = missing right (default_left=False), dir 1 = missing left;
    # without missing values both directions coincide, so only dir 0 is built
    n_dirs = 2 if has_missing else 1
    dir_stack = [cum, cum + miss[:, :, :, None]][:n_dirs]
    left = jnp.stack(dir_stack, axis=2)                   # [N,F,dirs,2,nb]
    base_valid = bins_idx[None, None, :] < n_real_bins[:, None, None]  # [F,1,nb]
    base_valid = jnp.broadcast_to(base_valid[None], (N, F, n_dirs, nb))

    if cat is not None:
        ic4 = cat.is_cat[None, :, None, None]          # vs [N,F,dirs,nb]
        ic5 = cat.is_cat[None, :, None, None, None]    # vs [N,F,dirs,2,nb]
        oh4 = cat.is_onehot[None, :, None, None]
        oh5 = cat.is_onehot[None, :, None, None, None]
        # sorted-partition order: categories ascending by g/(h+lambda)
        # (reference evaluator sorts by weight, evaluate_splits.h:146)
        ratio = present[:, :, 0] / (present[:, :, 1] + param.reg_lambda + 1e-10)
        empty = present[:, :, 1] <= 0.0
        ratio = jnp.where(empty, jnp.inf, ratio)  # empty cats sort last
        # the (g, h) planes ride the stable sort of their keys, and the
        # ranks are the order's inverse by a second sort: no gather by the
        # order, which XLA:TPU lowers as a one-hot of every slot against
        # every slot (380 MB at 128 nodes x 3 columns x 351 categories)
        at = jax.lax.broadcasted_iota(jnp.int32, ratio.shape, 2)
        _, sg, sh, order = jax.lax.sort(
            (ratio, present[:, :, 0], present[:, :, 1], at), dimension=2,
            is_stable=True, num_keys=1)                    # [N,F,nb] each
        _, ranks = jax.lax.sort((order, at), dimension=2, is_stable=True,
                                num_keys=1)
        sorted_hist = jnp.stack([sg, sh], axis=2)         # [N,F,2,nb]
        cums = jnp.cumsum(sorted_hist, axis=3)
        left_sorted = jnp.stack(
            [cums, cums + miss[:, :, :, None]][:n_dirs], axis=2)
        # one-hot: right child = {category c}; missing follows the default
        # direction: dir 0 -> right = hist[c] + miss (missing right),
        # dir 1 -> right = hist[c] (missing left); left is the rest
        rest_oh = cum[..., -1:] - present                 # [N,F,2,nb]
        left_oh = jnp.stack(
            [rest_oh, rest_oh + miss[:, :, :, None]][:n_dirs], axis=2)
        left = jnp.where(ic5, jnp.where(oh5, left_oh, left_sorted), left)
        # validity: sorted prefixes capped by max_cat_threshold
        cat_valid = jnp.where(
            oh4, base_valid,
            base_valid & (bins_idx[None, None, None, :]
                          < param.max_cat_threshold))
        base_valid = jnp.where(ic4, cat_valid, base_valid)

    # right sums from THIS histogram's own total (the last prefix), not from
    # the inherited ``parent_sum``: float32 sums of millions of rows drift
    # by 1e-4 of their size between a level's histogram and the one its
    # parent's sums came from, so ``parent - left`` gave a right child of a
    # few rows beside a sibling of millions sums off by whole rows' worth
    # (a leaf stated at 2.26 where its 215 rows' sums give 0.04, and a
    # spurious gain for the split that isolates it: PERF.md section 6,
    # PR 33). Prefix and total of one histogram round together.
    # The categorical arms take theirs the same way (below).
    rest = cum[..., -1:] - cum                            # real bins right
    right = jnp.stack([rest + miss[:, :, :, None], rest][:n_dirs],
                      axis=2)                             # [N,F,dirs,2,nb]
    if cat is not None:
        # the categorical arms' right children from the same histogram, as
        # the numeric ones': the rest of the sorted order, or the one
        # category (with the missing mass where it goes right)
        rest_s = cums[..., -1:] - cums
        right_sorted = jnp.stack(
            [rest_s + miss[:, :, :, None], rest_s][:n_dirs], axis=2)
        right_oh = jnp.stack(
            [present + miss[:, :, :, None], present][:n_dirs], axis=2)
        right = jnp.where(ic5, jnp.where(oh5, right_oh, right_sorted),
                          right)

    lg, lh = left[:, :, :, 0, :], left[:, :, :, 1, :]     # [N,F,dirs,nb]
    rg, rh = right[:, :, :, 0, :], right[:, :, :, 1, :]
    if monotone is None:
        pgain = calc_gain(parent_sum[:, 0], parent_sum[:, 1], param)  # [N]
        loss_chg = (calc_gain(lg, lh, param) + calc_gain(rg, rh, param)
                    - pgain[:, None, None, None])
        mono_ok = True
    else:
        from ..tree.param import calc_gain_given_weight, calc_weight

        lo = node_lower[:, None, None, None]
        hi = node_upper[:, None, None, None]
        wl = jnp.clip(calc_weight(lg, lh, param), lo, hi)
        wr = jnp.clip(calc_weight(rg, rh, param), lo, hi)
        wp = jnp.clip(calc_weight(parent_sum[:, 0], parent_sum[:, 1], param),
                      node_lower, node_upper)
        pgain = calc_gain_given_weight(parent_sum[:, 0], parent_sum[:, 1],
                                       wp, param)
        loss_chg = (calc_gain_given_weight(lg, lh, wl, param)
                    + calc_gain_given_weight(rg, rh, wr, param)
                    - pgain[:, None, None, None])
        mc = monotone[None, :, None, None]
        mono_ok = (mc == 0) | (mc * (wr - wl) >= 0)

    valid = base_valid & (lh >= param.min_child_weight) \
        & (rh >= param.min_child_weight) & mono_ok
    if feature_mask is not None:
        fm = feature_mask if feature_mask.ndim == 2 else feature_mask[None, :]
        valid = valid & fm[:, :, None, None]
    loss_chg = jnp.where(valid, loss_chg, -jnp.inf)

    # flat layout (f, d, b); ties resolve to the lowest flat index, which
    # prefers missing-right then lower bins — same preference order as the
    # previous (f, b, d) layout for the common single-direction case
    flat = loss_chg.reshape(N, -1)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    f_idx = (best // (nb * n_dirs)).astype(jnp.int32)
    rem = best % (nb * n_dirs)
    d_idx = (rem // nb).astype(jnp.int32)
    b_idx = (rem % nb).astype(jnp.int32)

    nn = jnp.arange(N)
    best_left = jnp.stack(
        [left[nn, f_idx, d_idx, 0, b_idx],
         left[nn, f_idx, d_idx, 1, b_idx]], axis=1)       # [N,2]
    best_right = jnp.stack(
        [right[nn, f_idx, d_idx, 0, b_idx],
         right[nn, f_idx, d_idx, 1, b_idx]], axis=1)      # [N,2]

    if cat is None:
        w = 1
        return SplitResult(
            gain=best_gain, feature=f_idx, bin=b_idx,
            default_left=d_idx.astype(bool), left_sum=best_left,
            right_sum=best_right, is_cat=jnp.zeros((N,), bool),
            cat_words=jnp.zeros((N, w), jnp.uint32))

    chosen_cat = cat.is_cat[f_idx]
    chosen_oh = cat.is_onehot[f_idx]
    # left-set mask over real bins of the winning feature
    real = bins_idx[None, :] < n_real_bins[f_idx][:, None]        # [N,nb]
    oh_mask = (bins_idx[None, :] != b_idx[:, None]) & real
    win_rank = ranks[nn, f_idx]                                    # [N,nb]
    sort_mask = (win_rank <= b_idx[:, None]) & real
    mask = jnp.where(chosen_oh[:, None], oh_mask, sort_mask) \
        & chosen_cat[:, None]
    n_words = (nb - 1) // 32 + 1
    return SplitResult(
        gain=best_gain, feature=f_idx, bin=b_idx,
        default_left=d_idx.astype(bool), left_sum=best_left,
        right_sum=best_right, is_cat=chosen_cat,
        cat_words=_pack_mask(mask, n_words))


class MultiSplitResult(NamedTuple):
    gain: jnp.ndarray          # [N] summed-over-targets loss_chg
    feature: jnp.ndarray       # [N] int32
    bin: jnp.ndarray           # [N] int32
    default_left: jnp.ndarray  # [N] bool
    left_sum: jnp.ndarray      # [N, K, 2]
    right_sum: jnp.ndarray     # [N, K, 2]


def evaluate_splits_multi(hist: jnp.ndarray, parent_sum: jnp.ndarray,
                          n_real_bins: jnp.ndarray, param: TrainParam,
                          feature_mask: Optional[jnp.ndarray] = None,
                          has_missing: bool = True) -> MultiSplitResult:
    """Split enumeration for vector-leaf trees (reference ``HistMultiEvaluator``,
    ``src/tree/hist/evaluate_splits.h:478``): one split is shared by all K
    targets and scored by the SUM of per-target gains. ``min_child_weight``
    is tested against the hessian summed over targets (reduces to the scalar
    rule at K=1).

    hist: [N, F, B, K, 2] per-target (g, h) sums; parent_sum: [N, K, 2].
    """
    # same LAYOUT NOTE as evaluate_splits: keep the bin axis MINOR — the
    # (K, 2) pair in the minor position tiles vector registers around a
    # handful of valid elements
    N, F, B, K, _ = hist.shape
    nb = B - 1 if has_missing else B
    # [N, F, K, 2, nb]
    present = jnp.moveaxis(hist[:, :, :nb], 2, 4)
    if has_missing:
        miss = hist[:, :, B - 1]                           # [N,F,K,2]
    else:
        miss = jnp.zeros((N, F, K, 2), hist.dtype)
    cum = jnp.cumsum(present, axis=4)
    bins_idx = jnp.arange(nb, dtype=jnp.int32)

    n_dirs = 2 if has_missing else 1
    left = jnp.stack([cum, cum + miss[..., None]][:n_dirs],
                     axis=2)                               # [N,F,dirs,K,2,nb]
    # right sums from this histogram's own total, as ``evaluate_splits``
    # takes them (and for its reason): not ``parent_sum - left``
    rest = cum[..., -1:] - cum
    right = jnp.stack([rest + miss[..., None], rest][:n_dirs], axis=2)

    lg, lh = left[..., 0, :], left[..., 1, :]              # [N,F,dirs,K,nb]
    rg, rh = right[..., 0, :], right[..., 1, :]
    pgain = jnp.sum(calc_gain(parent_sum[..., 0], parent_sum[..., 1], param),
                    axis=1)                                # [N]
    loss_chg = (jnp.sum(calc_gain(lg, lh, param), axis=3)
                + jnp.sum(calc_gain(rg, rh, param), axis=3)
                - pgain[:, None, None, None])              # [N,F,dirs,nb]

    base_valid = bins_idx[None, None, :] < n_real_bins[:, None, None]
    valid = jnp.broadcast_to(base_valid[None], (N, F, n_dirs, nb)) \
        & (jnp.sum(lh, axis=3) >= param.min_child_weight) \
        & (jnp.sum(rh, axis=3) >= param.min_child_weight)
    if feature_mask is not None:
        fm = feature_mask if feature_mask.ndim == 2 else feature_mask[None, :]
        valid = valid & fm[:, :, None, None]
    loss_chg = jnp.where(valid, loss_chg, -jnp.inf)

    flat = loss_chg.reshape(N, -1)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    f_idx = (best // (nb * n_dirs)).astype(jnp.int32)
    rem = best % (nb * n_dirs)
    d_idx = (rem // nb).astype(jnp.int32)
    b_idx = (rem % nb).astype(jnp.int32)

    nn = jnp.arange(N)
    # [N,F,dirs,K,2,nb] -> advanced indices (nn, f, d, b) with slices at
    # (K, 2): separated advanced indices put the broadcast dim first
    best_left = jnp.moveaxis(left, 5, 3)[nn, f_idx, d_idx, b_idx]  # [N,K,2]
    best_right = jnp.moveaxis(right, 5, 3)[nn, f_idx, d_idx, b_idx]
    return MultiSplitResult(
        gain=best_gain, feature=f_idx, bin=b_idx,
        default_left=d_idx.astype(bool), left_sum=best_left,
        right_sum=best_right)


# ---- two-level coarse->refine histogram (hist_method="coarse") -------------
# The packed-SWAR one-pass kernel is VPU-bound on the 256-wide one-hot
# build; a coarse pass over ``bins >> 4`` plus a refine pass over a 32-bin
# fine window measures ~2.8x cheaper at the kernel level
# (docs/performance.md round-4 section, tools/bench_hist_coarse.py — a
# 32-wide int8 one-hot fills the same 32-sublane tile a 16-wide one pads
# to, so the window costs nothing extra).
# Exactness: gains at every coarse boundary stay exact, and the refine
# window covers BOTH spans adjacent to the best coarse boundary, so the
# chosen split is never worse than a max_bin=16 split and equals the
# exact max_bin=256 one whenever the best fine split lies within a span
# of the best coarse boundary.

COARSE_SPAN = 16   # fine bins per coarse bin
COARSE_B = 20      # coarse hist slots: 16 real + 3 pad + missing at 19
WINDOW = 32        # refined fine bins: the 2 spans around the boundary
SYN_B = 46         # synthetic slots: 14 lower + 32 fine + (upper folded)
# the ids a numeric column's bins take under the two-level search (16 coarse
# spans of 16), and the coarse pad slot that ids past them land on
COARSE_NUMERIC_IDS = 16 * COARSE_SPAN
COARSE_PAD_SLOT = 16
# features past which ``assemble_two_level`` takes its slots by selects and
# not by a gather (its docstring)
SELECT_TAKE_FEATURES = 256


def coarse_bin_ids(bins_i32: jnp.ndarray, missing_bin: int) -> jnp.ndarray:
    """Coarse-pass slot per element: ``bins >> log2(COARSE_SPAN)`` with the
    missing slot remapped to ``COARSE_B - 1``. Orientation-agnostic
    (elementwise); shared by the resident and paged growers so the layout
    has exactly one definition. When the matrix has no missing slot,
    ``missing_bin`` is an out-of-range sentinel and the remap never fires."""
    shift = COARSE_SPAN.bit_length() - 1
    if missing_bin > COARSE_NUMERIC_IDS:
        # ids past the numeric range (a categorical column's, which the
        # two-level search does not read) land on a pad slot, never on
        # the missing one or past the last
        return jnp.where(bins_i32 == missing_bin, COARSE_B - 1,
                         jnp.minimum(bins_i32 >> shift, COARSE_PAD_SLOT)
                         ).astype(jnp.uint8)
    return jnp.where(bins_i32 == missing_bin, COARSE_B - 1,
                     bins_i32 >> shift).astype(jnp.uint8)


def refine_slots(bins_i32: jnp.ndarray, span_sel_i32: jnp.ndarray,
                 missing_bin: int) -> jnp.ndarray:
    """Refine-pass slot per element given each element's window start (in
    coarse units), int32: in-window elements land on [0, WINDOW);
    everything else (out of window / missing) on the discarded pad slot
    WINDOW + 3, which keeps the kernel width WINDOW + 4 a multiple of 4
    for the packed SWAR build. The one definition of the remap: the XLA
    build (``refine_bin_ids``) and the refine kernel's in-VMEM remap
    (``ops/pallas/histogram.py _remap_window``) both apply it."""
    rb = bins_i32 - COARSE_SPAN * span_sel_i32
    ok = (rb >= 0) & (rb < WINDOW) & (bins_i32 != missing_bin)
    return jnp.where(ok, rb, WINDOW + 3)


def refine_bin_ids(bins_i32: jnp.ndarray, span_sel_i32: jnp.ndarray,
                   missing_bin: int) -> jnp.ndarray:
    """``refine_slots`` as the uint8 ids a histogram build reads."""
    return refine_slots(bins_i32, span_sel_i32, missing_bin).astype(
        jnp.uint8)


def refine_from_fine(fine: jnp.ndarray, window: jnp.ndarray,
                     missing_bin: int) -> jnp.ndarray:
    """Refine-pass histogram recovered by WINDOW-slicing a full fine
    histogram — the page-major streaming schedule's replacement for the
    second page sweep: a streamed page's single visit accumulates its
    full ``[N, F, max_nbins, 2]`` fine partial, and once the window is
    chosen (after the global coarse reduction) this slice stands in for
    the direct ``refine_bin_ids`` build of the same rows.

    Exactness: refine slot ``w`` of (node, feature) with window start
    ``c`` is the sum over rows with fine bin ``16c + w`` — the SAME row
    set, summed in the same row order, as fine bin ``16c + w`` of the
    full build (only the segment numbering differs), so the slice is
    bit-equal per page. Out-of-range slices (windows clamped near the
    feature's last real coarse bin) and the missing slot — which the
    direct build routes to the discarded pad — are zeroed."""
    N, F, B, _ = fine.shape
    idx = (COARSE_SPAN * window[:, :, None]
           + jnp.arange(WINDOW, dtype=jnp.int32)[None, None, :])  # [N,F,W]
    out = jnp.take_along_axis(fine, jnp.clip(idx, 0, B - 1)[..., None],
                              axis=2)
    ok = (idx < B) & (idx != missing_bin)
    return jnp.where(ok[..., None], out, 0.0)


def choose_refine_window(hist_c: jnp.ndarray, parent_sum: jnp.ndarray,
                         n_real_bins: jnp.ndarray, param: TrainParam,
                         has_missing: bool) -> jnp.ndarray:
    """[N, F] int32 window start w: the refine window covers coarse spans
    w and w+1 — both sides of the best coarse-boundary gain — clamped per
    FEATURE to the real coarse-bin count (without the clamp, a degenerate
    all-left boundary past the data could shift the window off the
    occupied bins and break the max_bin<=32 bit-exactness guarantee).
    Heuristic chooser (no monotone clamp; both missing directions;
    min_child_weight gate) — the FINAL split is scored exactly by
    ``evaluate_splits`` on the assembled synthetic histogram."""
    present = jnp.moveaxis(hist_c[:, :, :16, :], 3, 2)     # [N,F,2,16]
    if has_missing:
        miss = hist_c[:, :, COARSE_B - 1, :]               # [N,F,2]
    else:
        miss = jnp.zeros(hist_c.shape[:2] + (2,), hist_c.dtype)
    cum = jnp.cumsum(present, axis=3)
    parent5 = parent_sum[:, None, None, :, None]
    n_dirs = 2 if has_missing else 1
    left = jnp.stack([cum, cum + miss[:, :, :, None]][:n_dirs], axis=2)
    right = parent5 - left                                 # [N,F,dirs,2,16]
    lg, lh = left[:, :, :, 0, :], left[:, :, :, 1, :]
    rg, rh = right[:, :, :, 0, :], right[:, :, :, 1, :]
    g = calc_gain(lg, lh, param) + calc_gain(rg, rh, param)
    ok = (lh >= param.min_child_weight) & (rh >= param.min_child_weight)
    g = jnp.max(jnp.where(ok, g, -jnp.inf), axis=2)        # [N,F,16]
    best = jnp.argmax(g, axis=2).astype(jnp.int32)         # boundary id
    c_cnt = (n_real_bins.astype(jnp.int32) + COARSE_SPAN - 1) // COARSE_SPAN
    w_max = jnp.maximum(c_cnt - 2, 0)[None, :]             # [1, F]
    return jnp.clip(best, 0, jnp.minimum(w_max, 14))


def assemble_two_level(hist_c: jnp.ndarray, hist_r: jnp.ndarray,
                       window: jnp.ndarray, n_real_bins: jnp.ndarray,
                       has_missing: bool):
    """Order-preserving synthetic histogram -> (hist_syn, n_real_syn).

    Slot layout per (node, feature) with window start w: slots [0, w)
    carry the merged coarse bins below the window, slots [w, w+32) the
    window's fine bins, slots [w+32, 46) the coarse bins above it, and
    the last slot the missing mass. Cumulative sums over this layout are
    exact, so ``evaluate_splits`` scores every coarse boundary and every
    in-window fine boundary exactly.

    A wide matrix takes its slots by a select-and-sum over the source's
    slots, in integers over the entries' bit patterns (one hit a slot, so
    the sum IS the entry, bit for bit, a signed zero too): the TPU
    compiler's time for the gather grows with nodes x features, 21.8 s for
    this function alone at 64 nodes x 968 features against 1.1 s for the
    selects, 47 s over a depth-8 tree's levels (PERF.md section 6, PR 36).
    Up to ``SELECT_TAKE_FEATURES`` features the gather stays, and with it
    the programs those matrices compile to."""
    s = jnp.arange(SYN_B, dtype=jnp.int32)[None, None, :]
    w = window[:, :, None]
    in_fine = (s >= w) & (s < w + WINDOW)
    c_idx = jnp.clip(jnp.where(s < w, s, s - 30), 0, 15)
    f_idx = jnp.clip(s - w, 0, WINDOW - 1)

    def take(h, idx):
        if h.shape[1] <= SELECT_TAKE_FEATURES:
            return jnp.take_along_axis(h, idx[..., None], axis=2)
        hit = idx[..., None] == jnp.arange(h.shape[2], dtype=jnp.int32)
        bits = jax.lax.bitcast_convert_type(h, jnp.int32)[:, :, None, :, :]
        return jax.lax.bitcast_convert_type(
            jnp.sum(jnp.where(hit[..., None], bits, 0), axis=3),
            h.dtype)                                   # [N, F, slots, 2]

    syn = jnp.where(in_fine[..., None], take(hist_r, f_idx),
                    take(hist_c, c_idx))
    if has_missing:
        syn = jnp.concatenate(
            [syn, hist_c[:, :, COARSE_B - 1:COARSE_B, :]], axis=2)
    c_cnt = (n_real_bins + COARSE_SPAN - 1) // COARSE_SPAN
    n_real_syn = jnp.clip(c_cnt + 30, 1, SYN_B).astype(jnp.int32)
    return syn, n_real_syn


def decode_two_level_bin(slot: jnp.ndarray,
                         window_sel: jnp.ndarray) -> jnp.ndarray:
    """Synthetic slot id -> FINE split bin, given each node's window start
    for its winning feature."""
    lower = 16 * slot + 15
    fine = 16 * window_sel + (slot - window_sel)
    upper = 16 * (slot - 30) + 15
    return jnp.where(slot < window_sel, lower,
                     jnp.where(slot < window_sel + WINDOW, fine,
                               upper)).astype(jnp.int32)


def merge_splits(res_num: SplitResult, res_cat: SplitResult, num_cols,
                 cat_cols) -> SplitResult:
    """A level's best split a node from the two arms of a two-level search
    over a matrix with categorical columns: ``res_num`` from the numeric
    columns' synthetic histogram, ``res_cat`` from the categorical columns'
    full one, each with features numbered within its arm (``num_cols`` and
    ``cat_cols``, static tuples, give their matrix columns). The larger
    gain wins; a tie goes to the lower matrix column, the order the
    one-pass search's flat argmax prefers."""
    f_num = jnp.asarray(num_cols, jnp.int32)[res_num.feature]
    f_cat = jnp.asarray(cat_cols, jnp.int32)[res_cat.feature]
    take = (res_cat.gain > res_num.gain) | (
        (res_cat.gain == res_num.gain) & (f_cat < f_num))

    def pick(c, n):
        t = take.reshape(take.shape + (1,) * (c.ndim - 1))
        return jnp.where(t, c, n)

    return SplitResult(
        gain=pick(res_cat.gain, res_num.gain),
        feature=pick(f_cat, f_num),
        bin=pick(res_cat.bin, res_num.bin),
        default_left=pick(res_cat.default_left, res_num.default_left),
        left_sum=pick(res_cat.left_sum, res_num.left_sum),
        right_sum=pick(res_cat.right_sum, res_num.right_sum),
        is_cat=take & res_cat.is_cat,
        cat_words=jnp.where(take[:, None], res_cat.cat_words, jnp.uint32(0)))
