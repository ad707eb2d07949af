"""Depth-wise tree growing under ``jit`` — the TPU hot loop.

Reference call stack being re-designed here: ``QuantileHistMaker::UpdateTree``
(``src/tree/updater_quantile_hist.cc:54-111``) / GPU ``GPUHistMakerDevice``
(``src/tree/updater_gpu_hist.cu:679-731``). TPU-native shape: the whole tree is a
fixed-capacity heap (node i -> children 2i+1/2i+2), one Python loop over depths
inside a single jitted function (each depth has static shapes: 2^d nodes), and
per depth exactly four fused stages — build histogram, psum across the mesh's
data axis, evaluate splits, advance row positions. The only cross-device
communication is the one histogram psum + root-sum psum per level, matching the
reference's "one allreduce per node batch" (``src/tree/hist/histogram.h:183-190``).

Feature subsampling follows ``common::ColumnSampler`` nesting
(bytree ⊃ bylevel ⊃ bynode, ``src/common/random.h:123``) with rank-based
without-replacement draws from a shared key (all mesh ranks use the same key,
like the broadcast seed at ``src/tree/updater_gpu_hist.cu:786-789``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import (count_grow_epilogue, count_grow_schedule,
                           count_mesh_dispatch)
from ..obs.trace import mesh_scope, stage
from ..ops.histogram import advance_leaf, build_hist, fused_advance_coarse
from ..ops.partition import advance_positions_level, update_positions
from ..ops.split import CatInfo, evaluate_splits
from ..registry import TREE_UPDATERS
from .param import TrainParam, calc_weight
from .tree import TreeModel

_EPS = 1e-6


class GrownTree(NamedTuple):
    """Device-side tree arrays (heap layout) plus per-row results."""

    split_feature: jnp.ndarray  # [max_nodes] int32
    split_bin: jnp.ndarray      # [max_nodes] int32
    default_left: jnp.ndarray   # [max_nodes] bool
    is_leaf: jnp.ndarray        # [max_nodes] bool
    active: jnp.ndarray         # [max_nodes] bool
    leaf_value: jnp.ndarray     # [max_nodes] f32 (eta applied)
    node_sum: jnp.ndarray       # [max_nodes, 2] f32
    gain: jnp.ndarray           # [max_nodes] f32
    positions: jnp.ndarray      # [n_rows] int32 final heap leaf per row
    delta: jnp.ndarray          # [n_rows] f32 leaf value per row (margin update)
    is_cat_split: jnp.ndarray   # [max_nodes] bool
    cat_words: jnp.ndarray      # [max_nodes, W] uint32 — categories going LEFT
    base_weight: Optional[jnp.ndarray] = None  # [max_nodes] f32 node weight*eta
    # raw split thresholds, set only by growers whose local cuts cannot
    # resolve every feature (vertical federated: the winner exchange
    # carries the owner's threshold)
    split_value: Optional[np.ndarray] = None


def _sample_features(key: jax.Array, base_mask: jnp.ndarray,
                     frac: float) -> jnp.ndarray:
    """Without-replacement draw of ceil(frac * |base|) features from base_mask."""
    if frac >= 1.0:
        return base_mask
    F = base_mask.shape[0]
    u = jax.random.uniform(key, (F,))
    u = jnp.where(base_mask, u, jnp.inf)
    count = jnp.sum(base_mask.astype(jnp.int32))
    k = jnp.clip(jnp.ceil(frac * count).astype(jnp.int32), 1, F)
    thr = jnp.sort(u)[k - 1]
    return base_mask & (u <= thr)


# The accepted ``hist_method`` names, each list written once. The
# two-level names are SCHEDULES of the depthwise and lossguide growers
# (``Schedule`` below); every other name but "auto" is a one-pass kernel
# of ``ops/histogram.py build_hist`` ("pallas:<precision>": the Pallas
# kernel's precision ladder, ops/pallas/histogram.py).
TWO_LEVEL_METHODS = ("coarse", "fused")
HIST_METHODS = ("auto",) + TWO_LEVEL_METHODS + (
    "segment", "onehot", "pallas", "pallas:int8x2", "pallas:bf16x2",
    "pallas:bf16", "pallas:f32")

# hist_method="auto" -> two-level coarse histogram promotion rule.
# Engages only where the two-level search is BOTH supported and cheaper
# than the one-pass exact kernel: TPU backend (on CPU the segment-sum
# kernel's cost is bin-width-independent, so two passes are a strict
# loss), numeric features, row split (with F/world features per shard the
# second pass amortises worse), wide bins (the win scales with bin count;
# below ~128 slots the one-pass kernel is already cheap), and enough local
# rows that the second pass + window choice amortise
# (tools/bench_hist_coarse.py measures the crossover).
# Quality: the two-level search is bit-exact for max_bin <= 32 and scores
# every coarse boundary exactly, so the promotion changes argmax choices
# only among near-tie fine splits inside unrefined windows.
AUTO_COARSE_MIN_ROWS = 1 << 16
AUTO_COARSE_MIN_BINS = 128

def auto_selects_coarse(n_rows: int, max_nbins: int, has_missing: bool, *,
                        numeric: bool, col_split: bool,
                        backend: Optional[str] = None) -> bool:
    """True when ``hist_method='auto'`` should route to the two-level
    coarse->refine histogram (depthwise scalar resident/paged growers)."""
    if backend is None:
        backend = jax.default_backend()
    return (backend == "tpu" and numeric and not col_split
            and max_nbins <= 256 + int(has_missing)
            and max_nbins - int(has_missing) >= AUTO_COARSE_MIN_BINS
            and n_rows >= AUTO_COARSE_MIN_ROWS)


def exchange_best_split(res, axis_name, F: int, *, with_cat: bool = False):
    """Column-split best-split exchange, shared by every grower family
    (depthwise scalar, lossguide, and their vector-leaf mirrors):
    all-gather the per-shard best gains, pick the winning shard per
    node, and psum-select the winner's split fields with its feature
    index globalised by the shard offset (equal shard widths are
    guaranteed by feature padding — ``data/binned.py
    pad_features_for_mesh``). Mirrors the reference's evaluator
    allgather (``src/tree/hist/evaluate_splits.h:294-409``). Returns
    ``(exchanged_res, mine)`` — ``mine`` marks the nodes this shard
    owns, which the callers' owner-local row advance needs.

    The select mask broadcasts to each field's rank, so scalar [N]
    ids, [N, 2] sums and [N, K, 2] vector-leaf sums all ride the same
    closure. ``with_cat``: also exchange the categorical fields; the
    uint32 bitmask words cross the psum via bitcast (not astype) so
    the winner's words arrive bit-exactly (only one shard contributes
    a nonzero term per node)."""
    my = jax.lax.axis_index(axis_name)
    gains = jax.lax.all_gather(res.gain, axis_name)          # [P, N]
    mine = jnp.argmax(gains, axis=0).astype(jnp.int32) == my

    def sel(x):
        m = mine.reshape(mine.shape + (1,) * (x.ndim - mine.ndim))
        return jax.lax.psum(jnp.where(m, x, jnp.zeros_like(x)), axis_name)

    repl = dict(
        gain=jnp.max(gains, axis=0),
        feature=sel(res.feature + my * F),
        bin=sel(res.bin),
        default_left=sel(res.default_left.astype(jnp.int32)) > 0,
        left_sum=sel(res.left_sum),
        right_sum=sel(res.right_sum))
    if with_cat:
        repl["is_cat"] = sel(res.is_cat.astype(jnp.int32)) > 0
        repl["cat_words"] = jax.lax.bitcast_convert_type(
            sel(jax.lax.bitcast_convert_type(res.cat_words, jnp.int32)),
            jnp.uint32)
    return res._replace(**repl), mine


# The gather-free level ops materialise [n, n_level] intermediates; past
# this level width the memory cost outweighs the gather cost, so deeper
# levels fall back to the per-row gather walk (``update_positions``: five
# one-element gathers over all rows, 545 ms a round at 10.5M rows on a
# v5e, PERF.md section 6, PR 30). What still walks, and where:
# - the LAST level of a fused program (128 nodes at max_depth 8) does
#   not on a TPU: one kernel sweep routes the rows and writes their
#   leaf delta (``ops/histogram.py advance_leaf``, up to 512 nodes). It
#   walks on the CPU, under column split (the decisions' psum), and past
#   max_depth 10;
# - the IN-LOOP boundaries behind levels of 128 and 256 nodes (max_depth
#   9 and 10: ``fused_advance_coarse`` falls to its XLA body there), and
#   every deep level of the one-pass schedules and of explicit ``coarse``
#   (which advance inside the level loop), walk on every backend;
# - without ``dense_delta`` (2^max_depth past this constant) the leaf
#   values come by ``leaf_value[positions]``, one more gather, wherever
#   the kernel did not write them.
DENSE_LEVEL_MAX = 64


class Schedule(NamedTuple):
    """What ``hist_method`` resolves to for one grow program — every
    field is a trace-time constant of ``_grow``."""

    kernel: str        # the one-pass build_hist method where not coarse
    coarse: bool       # two-level coarse->refine search space
    fused: bool        # ... scheduled as the cross-level fused sweep

    @property
    def name(self) -> str:
        """The schedule that runs: ``fused``/``coarse``, or the one-pass
        build ``kernel`` names."""
        if self.fused:
            return "fused"
        return "coarse" if self.coarse else self.kernel


def resolve_schedule(hist_method: str, n: int, max_nbins: int,
                     has_missing: bool, *, numeric: bool,
                     col_split: bool = False) -> Schedule:
    """The histogram schedule every grower runs for ``hist_method`` at
    this shape: ``n`` local rows, ``numeric`` = no categorical feature.
    Reads the backend (``auto_selects_coarse``), nothing else.

    Two-level coarse->refine search ("coarse"): a 20-slot pass over
    bins >> 4, a span choice per (node, feature) from the coarse boundary
    gains, a 16-bin refine pass over the chosen span, and an exact
    ``evaluate_splits`` over the order-preserving synthetic layout. Every
    coarse boundary and every in-span fine boundary is scored exactly;
    fine splits OUTSIDE the chosen span are not searched. "auto" takes it
    where ``auto_selects_coarse`` holds and keeps the exact one-pass
    kernel everywhere else.

    "fused" is a rescheduling of that search, not another search space:
    per level boundary the row advance below level L's decoded splits and
    level L+1's coarse accumulation share one read of the bin tile
    (``ops/histogram.py fused_advance_coarse``). Bit-exact with "coarse"
    (tests/test_fused_hist.py), so "auto" runs the fused schedule wherever
    it promotes; explicit "coarse" keeps the two-pass schedule as the
    reference that test holds it to."""
    use_coarse = hist_method in TWO_LEVEL_METHODS
    if hist_method == "auto":
        use_coarse = auto_selects_coarse(
            n, max_nbins, has_missing, numeric=numeric,
            col_split=col_split)
    use_fused = hist_method == "fused" or (hist_method == "auto"
                                           and use_coarse)
    return Schedule(kernel=hist_method, coarse=use_coarse, fused=use_fused)


@functools.partial(
    jax.jit,
    static_argnames=("param", "max_nbins", "hist_method", "axis_name",
                     "has_missing", "split_mode"))
@stage("grow")      # its own root where it IS the program (the general path)
def _grow(bins: jnp.ndarray, gpair: jnp.ndarray, n_real_bins: jnp.ndarray,
          tree_mask: jnp.ndarray, key: jax.Array,
          monotone: Optional[jnp.ndarray] = None,
          constraint_sets: Optional[jnp.ndarray] = None,
          cat: Optional[CatInfo] = None, *,
          param: TrainParam, max_nbins: int, hist_method: str = "auto",
          axis_name: Optional[str] = None,
          has_missing: bool = True,
          split_mode: str = "row") -> GrownTree:
    """``split_mode="row"``: rows sharded over ``axis_name``, histograms
    psum'd (reference ``DataSplitMode::kRow``). ``split_mode="col"``:
    FEATURES sharded, rows replicated — split finding is local per feature
    shard, the best split is all-gathered and the owner's row decisions are
    broadcast via psum, mirroring the reference's column-split protocol
    (``src/tree/hist/evaluate_splits.h:399-409`` best-split allgather +
    ``common_row_partitioner.h`` decision-bitvector sync)."""
    n, F = bins.shape
    col_split = split_mode == "col"
    max_depth = param.max_depth
    max_nodes = 2 ** (max_depth + 1) - 1
    # out-of-range sentinel when the matrix carries no missing slot
    missing_bin = max_nbins - 1 if has_missing else max_nbins

    def allreduce(x, what="hist_psum"):
        # column split: every shard already sees all rows -> no hist psum
        if axis_name is None or col_split:
            return x
        with mesh_scope(what):
            return jax.lax.psum(x, axis_name)

    split_feature = jnp.full((max_nodes,), -1, jnp.int32)
    split_bin = jnp.zeros((max_nodes,), jnp.int32)
    default_left = jnp.zeros((max_nodes,), bool)
    is_leaf = jnp.ones((max_nodes,), bool)
    active = jnp.zeros((max_nodes,), bool).at[0].set(True)
    gain = jnp.zeros((max_nodes,), jnp.float32)
    node_sum = jnp.zeros((max_nodes, 2), jnp.float32)
    root_sum = allreduce(jnp.sum(gpair, axis=0), "root_psum")
    node_sum = node_sum.at[0].set(root_sum)
    positions = jnp.zeros((n,), jnp.int32)
    if monotone is not None:
        # per-node weight bounds (reference TreeEvaluator lower/upper arrays)
        node_lower = jnp.full((max_nodes,), -jnp.inf, jnp.float32)
        node_upper = jnp.full((max_nodes,), jnp.inf, jnp.float32)
    if constraint_sets is not None:
        # features used on the path to each node (interaction constraints);
        # GLOBAL feature width — under column split every shard tracks the
        # replicated path with global ids
        F_cons = constraint_sets.shape[1]
        node_path = jnp.zeros((max_nodes, F_cons), bool)
    n_real_slots = max_nbins - 1 if has_missing else max_nbins
    n_words = (n_real_slots - 1) // 32 + 1 if cat is not None else 1
    is_cat_split = jnp.zeros((max_nodes,), bool)
    cat_words = jnp.zeros((max_nodes, n_words), jnp.uint32)

    bins_t = bins.T  # loop-invariant; feeds the fused Pallas hist kernel
    # f32 copy of the bin matrix: the level-wise position advance fetches each
    # node's split-feature column with one [n, F] @ [F, N] MXU matmul (bin ids
    # are < 2^24 so the f32 values are exact).
    bins_f32 = bins.astype(jnp.float32)

    if col_split:
        # this shard's bins columns are global features [off, off + F);
        # constraint/cat arrays arrive GLOBAL (padded to world * F by the
        # grower) — local split evaluation uses the shard's slice, while
        # post-exchange bookkeeping (node bounds, interaction paths) keeps
        # indexing the global arrays with the winner's global feature id
        feat_off = jax.lax.axis_index(axis_name) * F
        mono_loc = (None if monotone is None else
                    jax.lax.dynamic_slice(monotone, (feat_off,), (F,)))
        cat_loc = (None if cat is None else CatInfo(
            is_cat=jax.lax.dynamic_slice(cat.is_cat, (feat_off,), (F,)),
            is_onehot=jax.lax.dynamic_slice(cat.is_onehot, (feat_off,),
                                            (F,))))
    else:
        feat_off = None
        mono_loc, cat_loc = monotone, cat

    # per-level delta accumulation touches the deepest level (2^max_depth
    # nodes); all levels must be dense for it to cover every row exactly once
    dense_delta = 2 ** max_depth <= DENSE_LEVEL_MAX

    # per-row margin delta, accumulated level by level as nodes become leaves
    # (avoids a data-dependent [n] gather from the leaf table at the end)
    delta = jnp.zeros((n,), jnp.float32)

    def level_weight(lo, n_level):
        s = node_sum[lo:lo + n_level]
        w = calc_weight(s[:, 0], s[:, 1], param)
        if monotone is not None:
            w = jnp.clip(w, node_lower[lo:lo + n_level],
                         node_upper[lo:lo + n_level])
        return w * param.eta

    sched = resolve_schedule(hist_method, n, max_nbins, has_missing,
                             numeric=cat is None, col_split=col_split)
    count_grow_schedule(sched.name)
    hist_kernel, use_coarse, use_fused = sched
    if use_coarse:
        if cat is not None or max_nbins > 256 + int(has_missing):
            raise NotImplementedError(
                f"hist_method='{hist_kernel}' supports numeric features "
                "and max_bin <= 256")
        # col split composes: the scheme is feature-local end to end
        # (coarse hist, window choice, refine, assembly all run on this
        # shard's features over replicated rows; the existing best-split
        # allgather exchanges the winner after the synthetic eval). The
        # "auto" rule still skips col split — with F/world features per
        # shard the two-pass overhead amortises worse, so coarse there
        # is explicit opt-in.
        from ..ops.split import (assemble_two_level, choose_refine_window,
                                 coarse_bin_ids, decode_two_level_bin,
                                 refine_bin_ids)
        cb_t = coarse_bin_ids(bins_t.astype(jnp.int32), missing_bin)
        cb = cb_t.T

    pending_adv = None  # fused: splits awaiting the next boundary sweep
    for depth in range(max_depth):
        lo = 2 ** depth - 1
        n_level = 2 ** depth
        idx = lo + jnp.arange(n_level)

        hist_c = None
        if use_fused and pending_adv is not None:
            # cross-level fused sweep: advance rows below the previous
            # level's decoded splits AND build this level's coarse
            # histogram from the same bin-tile read
            row_axis = axis_name if not col_split else None
            with stage("advance_hist"):
                positions, hist_c = fused_advance_coarse(
                    bins, gpair, positions, pending_adv, lo, n_level,
                    missing_bin, bins_t=bins_t, method="auto",
                    axis_name=row_axis,
                    decision_axis=axis_name if col_split else None)
            with stage("exchange"):
                hist_c = allreduce(hist_c)
            pending_adv = None

        in_level = (positions >= lo) & (positions < lo + n_level)
        rel = jnp.where(in_level, positions - lo, n_level).astype(jnp.int32)
        span = None
        if use_coarse:
            row_axis = axis_name if not col_split else None
            if hist_c is None:
                with stage("hist"):
                    hist_c = allreduce(build_hist(
                        cb, gpair, rel, n_level, 20, method="auto",
                        bins_t=cb_t, axis_name=row_axis))
            with stage("window"):
                span = choose_refine_window(hist_c,
                                            node_sum[lo:lo + n_level],
                                            n_real_bins, param,
                                            has_missing)          # [N, F]
            # per-row window of the row's node, via one [F,N+1]@[N+1,n]
            # MXU matmul (rows outside the level hit the zero pad row;
            # their kernel contribution is dropped by rel == n_level)
            with stage("refine"):
                span_pad = jnp.concatenate(
                    [span.astype(jnp.float32),
                     jnp.zeros((1, F), jnp.float32)]).T  # [F, N+1]
                oh_rel = (rel[None, :] == jnp.arange(
                    n_level + 1,
                    dtype=jnp.int32)[:, None]).astype(jnp.float32)
                c_row_t = jax.lax.dot_general(
                    span_pad, oh_rel, (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST)    # [F, n]
                # out-of-window sentinel (refine_bin_ids) must be a
                # VALID slot of the kernel — the flat-index segment
                # path would bleed an out-of-range id into the next
                # feature's bins; the pad slots of the WINDOW+4-wide
                # pass are discarded
                from ..ops.split import WINDOW
                rb_t = refine_bin_ids(bins_t.astype(jnp.int32),
                                      c_row_t.astype(jnp.int32),
                                      missing_bin)
                hist_r = allreduce(build_hist(
                    rb_t.T, gpair, rel, n_level, WINDOW + 4,
                    method="auto", bins_t=rb_t,
                    axis_name=row_axis))[:, :, :WINDOW, :]
            hist, n_real_eval = assemble_two_level(
                hist_c, hist_r, span, n_real_bins, has_missing)
        else:
            with stage("hist"):
                hist = build_hist(
                    bins, gpair, rel, n_level, max_nbins,
                    method=hist_kernel, bins_t=bins_t,
                    # int8x2 quantisation scale must be pmax'd across
                    # row shards so every shard quantises identically
                    # (col split replicates rows — local scale is
                    # already global)
                    axis_name=axis_name if not col_split else None)
            with stage("exchange"):
                hist = allreduce(hist)

        level_key = jax.random.fold_in(key, depth)
        level_mask = _sample_features(level_key, tree_mask,
                                      param.colsample_bylevel)
        if param.colsample_bynode < 1.0:
            node_keys = jax.random.split(jax.random.fold_in(level_key, 1),
                                         n_level)
            fmask = jax.vmap(
                lambda k: _sample_features(k, level_mask,
                                           param.colsample_bynode))(node_keys)
        else:
            fmask = level_mask[None, :]

        if constraint_sets is not None:
            path = node_path[lo:lo + n_level]                    # [N,Fc]
            allowed = interaction_allowed_dev(path, constraint_sets)
            if col_split:  # local feature-mask slice of the global allowance
                allowed = jax.lax.dynamic_slice(
                    allowed, (0, feat_off), (n_level, F))
            fmask = fmask & allowed

        parent_sum = node_sum[lo:lo + n_level]
        with stage("eval"):
            res = evaluate_splits(
                hist, parent_sum,
                n_real_eval if use_coarse else n_real_bins, param,
                feature_mask=fmask, monotone=mono_loc,
                node_lower=node_lower[lo:lo + n_level]
                if monotone is not None else None,
                node_upper=node_upper[lo:lo + n_level]
                if monotone is not None else None,
                cat=cat_loc, has_missing=has_missing)
        if use_coarse:
            # synthetic slot -> fine bin, per node's span for its feature
            span_sel = jnp.take_along_axis(
                span, jnp.maximum(res.feature, 0)[:, None], axis=1)[:, 0]
            res = res._replace(
                bin=decode_two_level_bin(res.bin, span_sel))

        if col_split:
            local_feat, local_bin = res.feature, res.bin
            local_dl = res.default_left
            local_is_cat, local_words = res.is_cat, res.cat_words
            with stage("exchange"):
                res, mine = exchange_best_split(res, axis_name, F,
                                                with_cat=cat is not None)

        # a node exists at this level iff its parent split; it expands unless
        # the best gain fails the gamma / kRtEps test (reference prune rule).
        can_split = (active[lo:lo + n_level]
                     & (res.gain > max(param.gamma, _EPS))
                     & jnp.isfinite(res.gain))

        split_feature = split_feature.at[idx].set(
            jnp.where(can_split, res.feature, -1))
        split_bin = split_bin.at[idx].set(jnp.where(can_split, res.bin, 0))
        default_left = default_left.at[idx].set(can_split & res.default_left)
        is_leaf = is_leaf.at[idx].set(~can_split)
        gain = gain.at[idx].set(jnp.where(can_split, res.gain, 0.0))
        if cat is not None:
            is_cat_split = is_cat_split.at[idx].set(can_split & res.is_cat)
            cat_words = cat_words.at[idx].set(
                jnp.where((can_split & res.is_cat)[:, None], res.cat_words,
                          jnp.uint32(0)))

        li, ri = 2 * idx + 1, 2 * idx + 2
        active = active.at[li].set(can_split).at[ri].set(can_split)
        zero2 = jnp.zeros_like(res.left_sum)
        node_sum = node_sum.at[li].set(
            jnp.where(can_split[:, None], res.left_sum, zero2))
        node_sum = node_sum.at[ri].set(
            jnp.where(can_split[:, None], res.right_sum, zero2))
        if monotone is not None:
            plo = node_lower[lo:lo + n_level]
            phi = node_upper[lo:lo + n_level]
            wl = jnp.clip(calc_weight(res.left_sum[:, 0], res.left_sum[:, 1],
                                      param), plo, phi)
            wr = jnp.clip(calc_weight(res.right_sum[:, 0],
                                      res.right_sum[:, 1], param), plo, phi)
            mid = (wl + wr) * 0.5
            mc = monotone[jnp.maximum(res.feature, 0)]
            # c=+1: left must stay <= mid, right >= mid; c=-1 mirrored
            l_hi = jnp.where(mc > 0, mid, phi)
            r_lo = jnp.where(mc > 0, mid, plo)
            l_lo = jnp.where(mc < 0, mid, plo)
            r_hi = jnp.where(mc < 0, mid, phi)
            node_lower = node_lower.at[li].set(jnp.where(can_split, l_lo, 0))
            node_upper = node_upper.at[li].set(
                jnp.where(can_split, l_hi, 0))
            node_lower = node_lower.at[ri].set(jnp.where(can_split, r_lo, 0))
            node_upper = node_upper.at[ri].set(
                jnp.where(can_split, r_hi, 0))
        if constraint_sets is not None:
            path = node_path[lo:lo + n_level]
            fsel = (jnp.arange(F_cons, dtype=jnp.int32)[None, :]
                    == jnp.maximum(res.feature, 0)[:, None]) \
                & can_split[:, None]
            child_path = path | fsel
            node_path = node_path.at[li].set(child_path)
            node_path = node_path.at[ri].set(child_path)

        if dense_delta:
            # rows whose node just became a terminal leaf take its value now
            with stage("delta"):
                leaf_now = active[idx] & ~can_split
                w_level = jnp.where(leaf_now, level_weight(lo, n_level), 0.0)
                rel_oh = (rel[:, None]
                          == jnp.arange(n_level, dtype=jnp.int32)[None, :])
                delta = delta + jnp.sum(
                    jnp.where(rel_oh, w_level[None, :], 0.0), axis=1)

        if use_fused:
            # defer this level's advance to the NEXT boundary's fused
            # sweep; categorical args never arise (coarse is numeric-only)
            if col_split and n_level <= DENSE_LEVEL_MAX:
                pending_adv = {
                    "kind": "dense", "lo": lo, "n_level": n_level,
                    "arrs": (jnp.where(can_split & mine, local_feat, -1),
                             jnp.where(can_split & mine, local_bin, 0),
                             can_split & mine & local_dl, can_split)}
            elif n_level <= DENSE_LEVEL_MAX:
                pending_adv = {
                    "kind": "dense", "lo": lo, "n_level": n_level,
                    "arrs": (jnp.where(can_split, res.feature, -1),
                             jnp.where(can_split, res.bin, 0),
                             can_split & res.default_left, can_split)}
            else:  # deep level: the boundary sweep runs the gather walk
                is_split_full = jnp.zeros((max_nodes,), bool).at[idx].set(
                    can_split)
                pending_adv = {
                    "kind": "walk", "lo": lo, "n_level": n_level,
                    "arrs": (split_feature, split_bin, default_left,
                             is_split_full),
                    "feat_offset": feat_off}
        elif col_split and n_level <= DENSE_LEVEL_MAX:
            # only the owning shard can route rows at each node; its local
            # decisions reach every shard through one boolean psum (the
            # reference's partition-bitvector broadcast). Categorical
            # routing stays owner-local: the owner's bins hold the split
            # feature, so its local cat bitmask words decide
            positions = advance_positions_level(
                bins_f32, positions, rel,
                jnp.where(can_split & mine, local_feat, -1),
                jnp.where(can_split & mine, local_bin, 0),
                can_split & mine & local_dl, can_split, missing_bin,
                is_cat=(can_split & mine & local_is_cat)
                if cat is not None else None,
                cat_words=jnp.where(
                    (mine & local_is_cat)[:, None], local_words,
                    jnp.uint32(0)) if cat is not None else None,
                decision_axis=axis_name)
        elif n_level <= DENSE_LEVEL_MAX:
            positions = advance_positions_level(
                bins_f32, positions, rel,
                jnp.where(can_split, res.feature, -1),
                jnp.where(can_split, res.bin, 0),
                can_split & res.default_left, can_split, missing_bin,
                is_cat=(can_split & res.is_cat)
                if cat is not None else None,
                cat_words=res.cat_words if cat is not None else None)
        else:  # deep level: per-row gather walk bounds memory to O(n);
            # under col split the walk resolves only owned nodes and one
            # psum broadcasts the decisions (update_positions docstring)
            is_split_full = jnp.zeros((max_nodes,), bool).at[idx].set(
                can_split)
            positions = update_positions(
                bins, positions, split_feature, split_bin, default_left,
                is_split_full, missing_bin,
                is_cat_split=is_cat_split if cat is not None else None,
                cat_words=cat_words if cat is not None else None,
                decision_axis=axis_name if col_split else None,
                feat_offset=feat_off)

    with stage("leaf"):
        w = calc_weight(node_sum[:, 0], node_sum[:, 1], param)
        if monotone is not None:
            w = jnp.clip(w, node_lower, node_upper)
        w = w * param.eta
        leaf_value = jnp.where(active & is_leaf, w, 0.0).astype(jnp.float32)
        base_weight = jnp.where(active, w, 0.0).astype(jnp.float32)

    # what advances the rows below the last level (xtpu_grow_epilogue_total):
    # nothing where every level advanced itself
    epilogue, leaf_delta = "none", None
    if pending_adv is not None:
        # epilogue: route rows below the deepest level's splits — there is
        # no next coarse pass left to fuse with. Past DENSE_LEVEL_MAX, on
        # a TPU, one kernel sweep also looks each row's leaf up
        positions, leaf_delta, epilogue = advance_leaf(
            bins, positions, pending_adv, leaf_value, missing_bin,
            bins_t=bins_t, decision_axis=axis_name if col_split else None)
    count_grow_epilogue(epilogue)

    with stage("leaf"):
        if dense_delta:
            # deepest level: every surviving node is a leaf
            lo = 2 ** max_depth - 1
            n_level = 2 ** max_depth
            w_last = jnp.where(active[lo:lo + n_level],
                               level_weight(lo, n_level), 0.0)
            rel = jnp.where(positions >= lo, positions - lo,
                            n_level).astype(jnp.int32)
            rel_oh = (rel[:, None]
                      == jnp.arange(n_level, dtype=jnp.int32)[None, :])
            delta = delta + jnp.sum(
                jnp.where(rel_oh, w_last[None, :], 0.0), axis=1)
        elif leaf_delta is not None:
            delta = leaf_delta
        else:
            delta = leaf_value[positions]
    return GrownTree(split_feature=split_feature, split_bin=split_bin,
                     default_left=default_left, is_leaf=is_leaf, active=active,
                     leaf_value=leaf_value, node_sum=node_sum, gain=gain,
                     positions=positions, delta=delta,
                     is_cat_split=is_cat_split, cat_words=cat_words,
                     base_weight=base_weight)


def select_max_leaves(active: np.ndarray, is_leaf: np.ndarray,
                      max_leaves: int):
    """Simulate the reference Driver's depth-wise schedule under a
    ``max_leaves`` cap over a fully grown level tree (``CPUExpandEntry::
    IsValid``): pop same-depth nodes in insertion (heap BFS) order, stop
    splitting once the leaf count hits the cap. Splits are
    order-independent, so this reproduces it exactly. Returns
    ``(exists, selected, changed)`` — heap masks of surviving nodes and
    retained splits; ``changed`` False means the cap never bound."""
    cap = len(is_leaf)
    exists = np.zeros(cap, bool)
    exists[0] = True
    selected = np.zeros(cap, bool)
    n_leaves = 1
    for nid in range(cap):
        if not exists[nid] or is_leaf[nid] or not active[nid]:
            continue
        if n_leaves >= max_leaves:
            continue
        selected[nid] = True
        n_leaves += 1
        exists[2 * nid + 1] = exists[2 * nid + 2] = True
    was_split = active & ~is_leaf
    return exists, selected, not (selected == was_split).all()


def interaction_allowed_dev(path_level: jnp.ndarray,
                            cons: jnp.ndarray) -> jnp.ndarray:
    """allowed(n) = union of constraint sets containing path(n) — the ONE
    in-jit encoding of the constraint-set algebra (reference
    ``FeatureInteractionConstraintHost``), shared by the scalar,
    vector-leaf and paged level evaluators. path_level: [N, Fc];
    cons: [S, Fc]."""
    compat = ~jnp.any(path_level[:, None, :] & ~cons[None, :, :], axis=2)
    return jnp.any(compat[:, :, None] & cons[None, :, :], axis=1)


def interaction_allowed_host(path_level: np.ndarray,
                             cons: np.ndarray) -> np.ndarray:
    """allowed(n) = union of constraint sets containing path(n) — the numpy
    mirror of `_grow`'s in-jit set algebra (reference
    ``FeatureInteractionConstraintHost``), shared by the host-loop growers
    (paged, vertical federated). path_level: [N, Fc]; cons: [S, Fc]."""
    compat = ~np.any(path_level[:, None, :] & ~cons[None, :, :], axis=2)
    return np.any(compat[:, :, None] & cons[None, :, :], axis=1)


def monotone_child_bounds_host(ls: np.ndarray, rs: np.ndarray,
                               feat: np.ndarray, plo: np.ndarray,
                               phi: np.ndarray, mono: np.ndarray, param):
    """Child weight-bound propagation (reference ``TreeEvaluator``), the
    numpy mirror of `_grow`'s in-jit update: clip child weights into the
    parent interval, split it at their midpoint by the constraint sign.
    Returns ((l_lo, l_hi), (r_lo, r_hi)). Shared by the host-loop growers;
    ``calc_weight`` runs through jnp so the f32 arithmetic matches the
    pooled path bit-for-bit."""
    from .param import calc_weight

    wl = np.clip(np.asarray(calc_weight(
        jnp.asarray(ls[:, 0]), jnp.asarray(ls[:, 1]), param)), plo, phi)
    wr = np.clip(np.asarray(calc_weight(
        jnp.asarray(rs[:, 0]), jnp.asarray(rs[:, 1]), param)), plo, phi)
    mid = (wl + wr) * 0.5
    mc = mono[np.maximum(feat, 0)]
    # c=+1: left must stay <= mid, right >= mid; c=-1 mirrored
    l_hi = np.where(mc > 0, mid, phi)
    r_lo = np.where(mc > 0, mid, plo)
    l_lo = np.where(mc < 0, mid, plo)
    r_hi = np.where(mc < 0, mid, phi)
    return (l_lo, l_hi), (r_lo, r_hi)


@functools.lru_cache(maxsize=None)
def _mesh_program(mesh, param: TrainParam, max_nbins: int, hist_method: str,
                  has_missing: bool, split_mode: str):
    """``_grow`` under ``shard_map`` over the mesh's ``data`` axis, jitted:
    ``(bins, gpair, n_real_bins, tree_mask, key, monotone, constraint_sets,
    cat) -> GrownTree``. Cached by what the trace closes over, so growers
    that come and go (``Booster.set_param`` rebinds one a ``train`` call)
    share the traced and compiled program."""
    from ..context import DATA_AXIS

    P = jax.sharding.PartitionSpec

    def _grow_mesh(b, g, nr, tm, k, monotone, constraint_sets, cat):
        return _grow(b, g, nr, tm, k, monotone, constraint_sets, cat,
                     param=param, max_nbins=max_nbins,
                     hist_method=hist_method, axis_name=DATA_AXIS,
                     has_missing=has_missing, split_mode=split_mode)

    tree = dict(split_feature=P(), split_bin=P(), default_left=P(),
                is_leaf=P(), active=P(), leaf_value=P(), node_sum=P(),
                gain=P(), is_cat_split=P(), cat_words=P(), base_weight=P())
    if split_mode == "col":
        # features sharded over the axis, rows replicated; every
        # output (positions/delta included) is replicated
        in_specs = (P(None, DATA_AXIS), P(), P(DATA_AXIS), P(DATA_AXIS),
                    P(), P(), P(), P())
        out_specs = GrownTree(positions=P(), delta=P(), **tree)
    else:
        in_specs = (P(DATA_AXIS, None), P(DATA_AXIS, None), P(), P(), P(),
                    P(), P(), P())
        out_specs = GrownTree(positions=P(DATA_AXIS), delta=P(DATA_AXIS),
                              **tree)
    # col mode: outputs ARE replicated (every split field passes
    # through a psum / all_gather), but the static replication
    # checker cannot prove it through the owner-shard select chain
    return jax.jit(jax.shard_map(
        _grow_mesh, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=split_mode != "col"))


_COLLECTIVE_PRIMS = ("psum", "pmax", "psum_invariant", "pmax_invariant")
_mesh_ledgers: dict = {}


def _walk_collectives(jaxpr, times: int, out: dict) -> None:
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in _COLLECTIVE_PRIMS:
            scopes = [p for p in str(eqn.source_info.name_stack).split("/")
                      if p.startswith("mesh.")]
            what = scopes[-1][len("mesh."):] if scopes else "unscoped"
            nbytes = sum(int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                         for v in eqn.invars if hasattr(v, "aval"))
            n, b = out.get(what, (0, 0))
            out[what] = (n + times, b + times * nbytes)
        inner_times = times * int(eqn.params.get("length", 1)) \
            if eqn.primitive.name == "scan" else times
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _walk_collectives(sub, inner_times, out)


def _mesh_collectives(fn, args) -> dict:
    """``{what: (count, bytes)}`` of the collectives one dispatch of the mesh
    program ``fn`` runs at these argument shapes, by the innermost ``mesh.``
    scope on each one's name stack (``obs.trace.MESH_SCOPES``; anything else
    reads ``unscoped``). Read off the traced program once a shape: the
    second trace finds ``_grow``'s own in jax's cache. Bytes are one shard's
    operand: what a chip hands to the exchange."""
    key = (fn, tuple((tuple(a.shape), str(a.dtype))
                     for a in jax.tree_util.tree_leaves(args)
                     if hasattr(a, "shape")))
    if key not in _mesh_ledgers:
        out: dict = {}
        _walk_collectives(fn.trace(*args).jaxpr.jaxpr, 1, out)
        _mesh_ledgers[key] = out
    return _mesh_ledgers[key]


@TREE_UPDATERS.register("grow_quantile_histmaker", "grow_gpu_hist",
                        "grow_histmaker")
class TreeGrower:
    """Host-side wrapper: sampling keys, colsample_bytree, device->TreeModel.

    With ``mesh`` set, the whole grow step runs under ``shard_map`` over the
    mesh's ``data`` axis: rows are sharded, tree arrays replicate, and the
    in-step ``psum`` is the reference's histogram allreduce."""

    def __init__(self, param: TrainParam, max_nbins: int, cuts,
                 hist_method: str = "auto",
                 mesh: Optional[jax.sharding.Mesh] = None,
                 monotone: Optional[np.ndarray] = None,
                 constraint_sets: Optional[np.ndarray] = None,
                 has_missing: bool = True,
                 split_mode: str = "row") -> None:
        if split_mode == "col" and mesh is None:
            raise ValueError("data_split_mode=col requires a mesh")
        self.param = param
        self.max_nbins = max_nbins
        self.has_missing = has_missing
        self.split_mode = split_mode
        self.cuts = cuts
        self.hist_method = hist_method
        self.mesh = mesh
        self.monotone = (None if monotone is None
                         else jnp.asarray(monotone, jnp.int32))
        self.constraint_sets = (None if constraint_sets is None
                                else jnp.asarray(constraint_sets, bool))
        is_cat = cuts.is_cat()
        if is_cat.any():
            n_real = cuts.n_real_bins()
            self.cat = CatInfo(
                is_cat=jnp.asarray(is_cat),
                is_onehot=jnp.asarray(
                    is_cat & (n_real <= param.max_cat_to_onehot)))
        else:
            self.cat = None
        if split_mode == "col":
            # bins pad the feature axis to a multiple of the mesh width;
            # the replicated GLOBAL constraint/cat arrays must match so
            # each shard's dynamic slice [off, off + F_loc) stays in range
            # (padding columns have n_real == 0 and can never win a split)
            from ..context import DATA_AXIS

            world = mesh.shape.get(DATA_AXIS, 1)
            F = int(np.asarray(is_cat).shape[0])
            from ..data.binned import feature_pad_for_mesh

            pad = feature_pad_for_mesh(F, world)
            if pad:
                if self.monotone is not None:
                    self.monotone = jnp.pad(self.monotone, (0, pad))
                if self.constraint_sets is not None:
                    self.constraint_sets = jnp.pad(
                        self.constraint_sets, ((0, 0), (0, pad)))
                if self.cat is not None:
                    self.cat = CatInfo(
                        is_cat=jnp.pad(self.cat.is_cat, (0, pad)),
                        is_onehot=jnp.pad(self.cat.is_onehot, (0, pad)))

    def grow(self, bins: jnp.ndarray, gpair: jnp.ndarray,
             n_real_bins: jnp.ndarray, key: jax.Array) -> GrownTree:
        # features with no real bins (col-split padding columns) are never
        # candidates, so they must not consume colsample draws either
        base_mask = jnp.asarray(n_real_bins) > 0
        tree_mask = _sample_features(jax.random.fold_in(key, 0xC0),
                                     base_mask,
                                     self.param.colsample_bytree)
        key = jax.random.fold_in(key, 0x5EED)
        if self.mesh is None:
            g = _grow(bins, gpair, n_real_bins, tree_mask, key,
                      self.monotone, self.constraint_sets, self.cat,
                      param=self.param, max_nbins=self.max_nbins,
                      hist_method=self.hist_method, axis_name=None,
                      has_missing=self.has_missing)
        else:
            g = self._sharded(bins, gpair, n_real_bins, tree_mask, key)
        if self.param.max_leaves > 0:
            g = self._truncate_max_leaves(g)
        return g

    def _truncate_max_leaves(self, g: GrownTree) -> GrownTree:
        """Depth-wise growth under a ``max_leaves`` cap: the reference Driver
        pops same-depth nodes in insertion order and stops splitting once the
        leaf count hits the cap (``CPUExpandEntry::IsValid``). Splits are
        order-independent, so simulating that schedule over the fully grown
        level tree reproduces it exactly; rows in truncated subtrees are
        re-parked on their deepest surviving ancestor."""
        active = np.asarray(g.active)
        is_leaf = np.asarray(g.is_leaf)
        exists, selected, changed = select_max_leaves(
            active, is_leaf, self.param.max_leaves)
        if not changed:
            return g
        base_weight = np.asarray(g.base_weight)
        new_is_leaf = exists & ~selected
        leaf_value = np.where(new_is_leaf, base_weight, 0.0).astype(np.float32)
        pos = np.asarray(g.positions)
        for _ in range(self.param.max_depth):
            pos = np.where(exists[pos], pos, (pos - 1) // 2)
        return GrownTree(
            split_feature=np.where(selected, np.asarray(g.split_feature),
                                   -1).astype(np.int32),
            split_bin=np.where(selected, np.asarray(g.split_bin),
                               0).astype(np.int32),
            default_left=np.asarray(g.default_left) & selected,
            is_leaf=new_is_leaf, active=exists,
            leaf_value=leaf_value,
            node_sum=np.asarray(g.node_sum),
            gain=np.where(selected, np.asarray(g.gain), 0.0).astype(
                np.float32),
            positions=pos.astype(np.int32),
            delta=jnp.asarray(leaf_value[pos]),
            is_cat_split=np.asarray(g.is_cat_split) & selected,
            cat_words=np.where(selected[:, None], np.asarray(g.cat_words),
                               np.uint32(0)),
            base_weight=np.where(exists, base_weight, 0.0).astype(np.float32))

    def sharded_program(self):
        """The jitted shard_map grow program WITHOUT dispatching it — the
        traceable handle exported through ``xgboost_tpu/tree/programs.py``
        for the mesh row/col contract checks; ``_sharded`` below invokes
        the same object. One program a (mesh, configuration), shared by
        every grower of the process (``_mesh_program``): a continuation
        call rebinds its grower and must not trace the round again."""
        return _mesh_program(self.mesh, self.param, self.max_nbins,
                             self.hist_method, self.has_missing,
                             self.split_mode)

    def _sharded(self, bins, gpair, n_real_bins, tree_mask, key) -> GrownTree:
        fn = self.sharded_program()
        args = (bins, gpair, n_real_bins, tree_mask, key, self.monotone,
                self.constraint_sets, self.cat)
        if self.split_mode == "row":
            count_mesh_dispatch(_mesh_collectives(fn, args))
        return fn(*args)

    def to_tree_model(self, g: GrownTree) -> TreeModel:
        """Pull device arrays to host, compact the heap, attach raw split
        thresholds."""
        sf = np.asarray(g.split_feature)
        sb = np.asarray(g.split_bin)
        split_value = self.cuts.split_values(sf, sb)
        return TreeModel.from_heap(
            split_feature=sf, split_bin=sb, split_value=split_value,
            default_left=np.asarray(g.default_left),
            is_leaf=np.asarray(g.is_leaf), active=np.asarray(g.active),
            leaf_value=np.asarray(g.leaf_value),
            sum_hess=np.asarray(g.node_sum[:, 1]),
            gain=np.asarray(g.gain),
            is_cat_split=np.asarray(g.is_cat_split),
            cat_words=np.asarray(g.cat_words),
            base_weight=None if g.base_weight is None
            else np.asarray(g.base_weight),
        )
