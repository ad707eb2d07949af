"""Loss-guided (best-first) tree growing — ``grow_policy=lossguide``.

Reference: the ``Driver`` expansion scheduler with ``LossGuide`` ordering pops
ONE highest-``loss_chg`` candidate at a time (``src/tree/driver.h:29-107``,
used by both hist updaters); ``max_leaves`` caps the number of leaves and
``max_depth=0`` means unbounded depth.

TPU formulation: the tree lives in compact node arrays on the host (ids in
split order, so ``parent < child``); the device holds only ``positions [n]``
(compact node id per row) and runs two small jitted kernels per split —
``eval2`` (histogram of the two fresh children in one fused pass + split
enumeration) and ``apply1`` (advance the popped node's rows one level). Both
have fully static shapes (batch of exactly 2 nodes), so the whole greedy loop
reuses two compiled programs regardless of tree shape. Under a mesh the same
kernels run in ``shard_map`` over the data axis with an in-kernel ``psum`` —
one histogram allreduce per split, the lossguide analogue of the reference's
one-allreduce-per-node-batch rule (``src/tree/hist/histogram.h:183-190``).

Because a node's best split depends only on its row set (never on expansion
order), this greedy loop reproduces the reference's lossguide tree exactly,
including arbitrary-depth chains — the compact layout makes deep skewed trees
cheap (capacity ``2*max_leaves - 1``, not ``2^depth``).
"""

from __future__ import annotations

import heapq
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as _trace
from ..ops.histogram import build_hist
from ..ops.partition import cat_goes_right
from ..ops.split import CatInfo, evaluate_splits
from .grow import TWO_LEVEL_METHODS, exchange_best_split, resolve_schedule
from .param import TrainParam, calc_weight
from .tree import TreeModel

_EPS = 1e-6


class LossguideGrown(NamedTuple):
    """Mirror of grow.GrownTree's consumer surface for the gbtree layer."""

    positions: jnp.ndarray      # [n] compact leaf id per row
    delta: jnp.ndarray          # [n] f32 leaf value per row (margin update)
    tree: TreeModel


def _eval2(bins, gpair, positions, id0, id1, parent_sums, fmask,
           node_lower, node_upper, n_real_bins, bins_t, cb_t, monotone,
           cat, *, param: TrainParam, max_nbins: int, hist_method: str,
           axis_name: Optional[str], has_missing: bool = True,
           coarse: bool = False):
    """Histogram + split enumeration for (up to) two sibling nodes.
    ``bins_t`` is the loop-invariant [F, n] transpose, computed once per
    tree so every per-split program skips the relayout.

    ``coarse``: the two-level coarse->refine histogram (the same scheme
    the depthwise growers promote at scale — the per-split two-node
    build pays the full 256-wide one-hot cost exactly like a depthwise
    level did, so the same ~2.8x kernel win applies). Both passes psum
    under a mesh; the final enumeration is exact over the assembled
    synthetic layout and the winning slot decodes to a fine bin."""
    rel = jnp.where(positions == id0, 0,
                    jnp.where(positions == id1, 1, 2)).astype(jnp.int32)
    if not coarse:
        hist = build_hist(bins, gpair, rel, 2, max_nbins,
                          method=hist_method, bins_t=bins_t)
        if axis_name is not None:
            hist = jax.lax.psum(hist, axis_name)
        return evaluate_splits(hist, parent_sums, n_real_bins, param,
                               feature_mask=fmask, monotone=monotone,
                               node_lower=node_lower,
                               node_upper=node_upper,
                               cat=cat, has_missing=has_missing)
    from ..ops.split import (COARSE_B, WINDOW, assemble_two_level,
                             choose_refine_window, decode_two_level_bin,
                             refine_bin_ids)

    missing_bin = max_nbins - 1 if has_missing else max_nbins
    # cb_t is hoisted per TREE by the grower (loop-invariant, like
    # bins_t); the int32 view feeding refine_bin_ids stays in-jit so
    # XLA fuses the upcast into the consumer instead of materialising
    # [F,n]i32
    bt_i32 = bins_t.astype(jnp.int32)
    hist_c = build_hist(cb_t.T, gpair, rel, 2, COARSE_B, method="auto",
                        bins_t=cb_t)
    if axis_name is not None:
        hist_c = jax.lax.psum(hist_c, axis_name)
    span = choose_refine_window(hist_c, parent_sums, n_real_bins,
                                param, has_missing)       # [2, F]
    # per-row window of the row's node (N=2: two selects, no matmul)
    c_row_t = jnp.where(rel[None, :] == 0, span[0][:, None],
                        jnp.where(rel[None, :] == 1, span[1][:, None],
                                  0)).astype(jnp.int32)   # [F, n]
    rb_t = refine_bin_ids(bt_i32, c_row_t, missing_bin)
    hist_r = build_hist(rb_t.T, gpair, rel, 2, WINDOW + 4,
                        method="auto", bins_t=rb_t)[:, :, :WINDOW, :]
    if axis_name is not None:
        hist_r = jax.lax.psum(hist_r, axis_name)
    hist, n_real_eval = assemble_two_level(hist_c, hist_r, span,
                                           n_real_bins, has_missing)
    res = evaluate_splits(hist, parent_sums, n_real_eval, param,
                          feature_mask=fmask, monotone=monotone,
                          node_lower=node_lower, node_upper=node_upper,
                          cat=cat, has_missing=has_missing)
    span_sel = jnp.take_along_axis(
        span, jnp.maximum(res.feature, 0)[:, None], axis=1)[:, 0]
    return res._replace(bin=decode_two_level_bin(res.bin, span_sel))


def _eval2_col(bins, gpair, positions, id0, id1, parent_sums, fmask,
               node_lower, node_upper, n_real_bins, bins_t, cb_t,
               monotone, cat, *,
               param: TrainParam, max_nbins: int, hist_method: str,
               axis_name: str, has_missing: bool = True,
               coarse: bool = False):
    """Column-split ``_eval2``: this shard's bins hold global features
    [off, off + F); rows replicate so the two-node histogram needs no
    psum, each shard evaluates ITS features (local slices of the
    replicated global monotone/cat arrays), and the per-shard best goes
    through the scalar ``_grow`` best-split exchange — all-gather the
    gains, psum-select the winner's fields with its feature id globalised
    (reference ``HistEvaluator::EvaluateSplits`` column-split all-gather,
    src/tree/hist/evaluate_splits.h:294-409).

    ``coarse``: the two-level scheme is feature-local end to end (coarse
    hist, window choice, refine and synthetic assembly all run on this
    shard's features over the replicated rows), so it composes with col
    split exactly like the depthwise grower's (tree/grow.py) — the
    winning slot decodes to a fine bin BEFORE the exchange."""
    F = bins.shape[1]
    my = jax.lax.axis_index(axis_name)
    feat_off = my * F
    mono_loc = (None if monotone is None
                else jax.lax.dynamic_slice(monotone, (feat_off,), (F,)))
    cat_loc = (None if cat is None else CatInfo(
        is_cat=jax.lax.dynamic_slice(cat.is_cat, (feat_off,), (F,)),
        is_onehot=jax.lax.dynamic_slice(cat.is_onehot, (feat_off,), (F,))))
    # the local evaluation IS _eval2 on this shard's features with the
    # psums elided (axis_name=None — rows are replicated, nothing to
    # sum) and the sliced-local monotone/cat arrays; exact and coarse
    # branches both stay single-sourced there
    res = _eval2(bins, gpair, positions, id0, id1, parent_sums, fmask,
                 node_lower, node_upper, n_real_bins, bins_t, cb_t,
                 mono_loc, cat_loc, param=param, max_nbins=max_nbins,
                 hist_method=hist_method, axis_name=None,
                 has_missing=has_missing, coarse=coarse)
    res, _ = exchange_best_split(res, axis_name, F,
                                 with_cat=cat is not None)
    return res


def _apply_eval2(bins, gpair, positions, nid, feat_a, sbin_a, dleft_a,
                 iscat_a, words_a, left_id, right_id, mb, parent_sums,
                 fmask, node_lower, node_upper, n_real_bins, bins_t, cb_t,
                 monotone, cat, *, param: TrainParam, max_nbins: int,
                 hist_method: str, axis_name: Optional[str],
                 has_missing: bool = True, coarse: bool = False):
    """Cross-level fusion, lossguide form (hist_method="fused"): the popped
    node's one-column row advance and its fresh children's histogram +
    enumeration run as ONE jitted program — the greedy loop's two
    dispatches per split become one. Against a remote device the per-split
    dispatch RTT is the lossguide tier's dominant fixed cost
    (docs/performance.md round 5), and XLA additionally fuses the advance's
    column read into the same program as the coarse pass. Numerics are the
    sequential apply1 -> eval2 composition, op for op — bit-exact."""
    positions = _apply1(bins, positions, nid, feat_a, sbin_a, dleft_a,
                        iscat_a, words_a, left_id, right_id, mb)
    res = _eval2(bins, gpair, positions, left_id, right_id, parent_sums,
                 fmask, node_lower, node_upper, n_real_bins, bins_t, cb_t,
                 monotone, cat, param=param, max_nbins=max_nbins,
                 hist_method=hist_method, axis_name=axis_name,
                 has_missing=has_missing, coarse=coarse)
    return positions, res


def _apply_eval2_col(bins, gpair, positions, nid, feat_a, sbin_a, dleft_a,
                     iscat_a, words_a, left_id, right_id, mb, parent_sums,
                     fmask, node_lower, node_upper, n_real_bins, bins_t,
                     cb_t, monotone, cat, *, param: TrainParam,
                     max_nbins: int, hist_method: str, axis_name: str,
                     has_missing: bool = True, coarse: bool = False):
    """Column-split ``_apply_eval2``: the owner-decision advance
    (``_apply1_col``) and the feature-local eval + winner exchange
    (``_eval2_col``) composed into one program."""
    positions = _apply1_col(bins, positions, nid, feat_a, sbin_a, dleft_a,
                            iscat_a, words_a, left_id, right_id, mb,
                            axis_name=axis_name)
    res = _eval2_col(bins, gpair, positions, left_id, right_id,
                     parent_sums, fmask, node_lower, node_upper,
                     n_real_bins, bins_t, cb_t, monotone, cat, param=param,
                     max_nbins=max_nbins, hist_method=hist_method,
                     axis_name=axis_name, has_missing=has_missing,
                     coarse=coarse)
    return positions, res


def _apply1_col(bins, positions, nid, feat, sbin, dleft, is_cat, words,
                left_id, right_id, missing_bin, *, axis_name: str):
    """One-node advance under column split: only the shard owning the
    winning GLOBAL feature can read its bins; one boolean psum fans its
    routing decisions out (the reference partition-bitvector broadcast,
    src/tree/common_row_partitioner.h)."""
    F = bins.shape[1]
    my = jax.lax.axis_index(axis_name)
    lf = feat - my * F
    owned = (lf >= 0) & (lf < F)
    safe = jnp.clip(lf, 0, F - 1)
    at_node = positions == nid
    b = jnp.take_along_axis(
        bins, jnp.full((bins.shape[0], 1), safe, jnp.int32),
        axis=1)[:, 0].astype(jnp.int32)
    missing = b == missing_bin
    go_right = b > sbin
    go_right = jnp.where(is_cat,
                         cat_goes_right(b, jnp.broadcast_to(
                             words[None, :], (bins.shape[0],
                                              words.shape[0]))),
                         go_right)
    go_right = jnp.where(missing, ~dleft, go_right)
    contrib = at_node & owned & go_right
    go_right = jax.lax.psum(contrib.astype(jnp.int32), axis_name) > 0
    child = jnp.where(go_right, right_id, left_id)
    return jnp.where(at_node, child, positions)


def _apply1(bins, positions, nid, feat, sbin, dleft, is_cat, words,
            left_id, right_id, missing_bin):
    """Advance rows sitting at `nid` to its fresh children."""
    at_node = positions == nid
    b = jnp.take_along_axis(
        bins, jnp.full((bins.shape[0], 1), jnp.maximum(feat, 0),
                       jnp.int32), axis=1)[:, 0].astype(jnp.int32)
    missing = b == missing_bin
    go_right = b > sbin
    go_right = jnp.where(is_cat,
                         cat_goes_right(b, jnp.broadcast_to(
                             words[None, :], (bins.shape[0],
                                              words.shape[0]))),
                         go_right)
    go_right = jnp.where(missing, ~dleft, go_right)
    child = jnp.where(go_right, right_id, left_id)
    return jnp.where(at_node, child, positions)


def _root_sum(gpair, axis_name: Optional[str]):
    s = jnp.sum(gpair, axis=0)
    return jax.lax.psum(s, axis_name) if axis_name is not None else s


def col_masks(param: TrainParam, seed: int, F: int,
              base: Optional[np.ndarray] = None):
    """bytree mask + per-depth / per-node draw helpers (reference
    ColumnSampler nesting, src/common/random.h:123; same seed on every
    rank like the broadcast at updater_gpu_hist.cu:786-789). Shared by the
    scalar and vector-leaf lossguide growers.

    ``base``: bool [F] of sampleable columns (``n_real_bins > 0``). Under
    mesh column split the feature axis pads to a multiple of the mesh
    width; padding columns must not consume colsample draws, or sampling
    diverges from the single-device run whenever F % world != 0 (the
    depthwise TreeGrower already excludes them — ADVICE r5 #2)."""
    rng = np.random.RandomState(seed & 0x7FFFFFFF)

    def draw(base: np.ndarray, frac: float) -> np.ndarray:
        if frac >= 1.0:
            return base
        idx = np.nonzero(base)[0]
        k = max(1, int(math.ceil(frac * len(idx))))
        keep = rng.choice(idx, size=min(k, len(idx)), replace=False)
        out = np.zeros(F, bool)
        out[keep] = True
        return out

    tree_mask = draw(np.ones(F, bool) if base is None
                     else np.asarray(base, bool), param.colsample_bytree)
    level_cache = {}

    def node_mask(depth: int) -> np.ndarray:
        if depth not in level_cache:
            level_cache[depth] = draw(tree_mask, param.colsample_bylevel)
        return draw(level_cache[depth], param.colsample_bynode)

    return node_mask


class LossguideGrower:
    """Host-driven greedy grower; drop-in for grow.TreeGrower."""

    def __init__(self, param: TrainParam, max_nbins: int, cuts,
                 hist_method: str = "auto",
                 mesh: Optional[jax.sharding.Mesh] = None,
                 monotone: Optional[np.ndarray] = None,
                 constraint_sets: Optional[np.ndarray] = None,
                 has_missing: bool = True,
                 split_mode: str = "row") -> None:
        if param.max_leaves <= 0 and param.max_depth <= 0:
            raise ValueError(
                "grow_policy=lossguide needs max_leaves > 0 or max_depth > 0")
        if split_mode == "col" and mesh is None:
            raise ValueError("data_split_mode=col requires a mesh")
        self.split_mode = split_mode
        self.param = param
        self.max_nbins = max_nbins
        self.has_missing = has_missing
        self.cuts = cuts
        self.hist_method = hist_method
        self.mesh = mesh
        self.monotone = (None if monotone is None
                         else jnp.asarray(monotone, jnp.int32))
        self.constraint_sets = (None if constraint_sets is None
                                else np.asarray(constraint_sets, bool))
        is_cat = cuts.is_cat()
        if is_cat.any():
            n_real = cuts.n_real_bins()
            self.cat = CatInfo(
                is_cat=jnp.asarray(is_cat),
                is_onehot=jnp.asarray(
                    is_cat & (n_real <= param.max_cat_to_onehot)))
            n_real_slots = max_nbins - 1 if has_missing else max_nbins
            self.n_words = (n_real_slots - 1) // 32 + 1
        else:
            self.cat = None
            self.n_words = 1
        if hist_method in TWO_LEVEL_METHODS and (
                self.cat is not None
                or max_nbins > 256 + int(has_missing)):
            # warn-and-fall-back, matching the depthwise "auto" promotion
            # rule (which silently keeps the exact kernel outside coarse's
            # preconditions) — an explicit request on an unsupported shape
            # should degrade to the exact one-pass path, not kill the job
            # (VERDICT r6 Weak #6)
            import warnings

            why = ("categorical features" if self.cat is not None
                   else f"max_bin > 256 (max_nbins={max_nbins})")
            warnings.warn(
                f"hist_method='{hist_method}' with grow_policy=lossguide "
                f"supports numeric features and max_bin <= 256; got {why} "
                "— falling back to the exact one-pass histogram "
                "(hist_method='auto')", UserWarning, stacklevel=3)
            self.hist_method = "auto"
        # what hist_method runs (the two-level search, and its one-dispatch
        # apply + child eval schedule): decided at first grow, when n is
        # known (_resolve_schedule)
        self._coarse = None
        self._fused = None
        if split_mode == "col":
            # bins pad the feature axis to a multiple of the mesh width;
            # the replicated GLOBAL constraint/cat arrays must match so
            # each shard's slice [off, off + F_loc) stays in range
            # (padding columns have n_real == 0, never winning a split)
            from ..context import DATA_AXIS

            world = mesh.shape.get(DATA_AXIS, 1)
            F = int(np.asarray(cuts.is_cat()).shape[0])
            from ..data.binned import feature_pad_for_mesh

            pad = feature_pad_for_mesh(F, world)
            if pad:
                if self.monotone is not None:
                    self.monotone = jnp.pad(self.monotone, (0, pad))
                if self.constraint_sets is not None:
                    self.constraint_sets = np.pad(self.constraint_sets,
                                                  ((0, 0), (0, pad)))
                if self.cat is not None:
                    self.cat = CatInfo(
                        is_cat=jnp.pad(self.cat.is_cat, (0, pad)),
                        is_onehot=jnp.pad(self.cat.is_onehot, (0, pad)))
        self._fns = None

    # ------------------------------------------------------------- jit setup
    def _functions(self):
        if self._fns is not None:
            return self._fns
        import functools

        kw = dict(param=self.param, max_nbins=self.max_nbins,
                  hist_method=self.hist_method,
                  has_missing=self.has_missing)
        if self.mesh is None:
            ev = functools.partial(_eval2, monotone=self.monotone,
                                   cat=self.cat, axis_name=None,
                                   coarse=bool(self._coarse), **kw)
            ae = functools.partial(_apply_eval2, monotone=self.monotone,
                                   cat=self.cat, axis_name=None,
                                   coarse=bool(self._coarse), **kw)
            self._fns = (jax.jit(ev), jax.jit(_apply1),
                         jax.jit(functools.partial(_root_sum,
                                                   axis_name=None)),
                         jax.jit(lambda lv, pos: lv[pos]),
                         jax.jit(ae) if self._fused else None)
        elif self.split_mode == "col":
            from ..context import DATA_AXIS
            P = jax.sharding.PartitionSpec

            ev = functools.partial(_eval2_col, monotone=self.monotone,
                                   cat=self.cat, axis_name=DATA_AXIS,
                                   coarse=bool(self._coarse), **kw)
            # features sharded, rows replicated; outputs come out
            # replicated through the best-split exchange (the static
            # replication checker can't prove it — check_vma off, as in
            # the depthwise col grower). cb_t ([F, n] like bins_t) shards
            # on features when the coarse scheme is active, else it is
            # the None placeholder (empty pytree, spec unused).
            cb_spec = P(DATA_AXIS, None) if self._coarse else P()
            sharded_eval = jax.jit(jax.shard_map(
                ev, mesh=self.mesh,
                in_specs=(P(None, DATA_AXIS), P(), P(), P(), P(), P(),
                          P(None, DATA_AXIS), P(), P(), P(DATA_AXIS),
                          P(DATA_AXIS, None), cb_spec),
                out_specs=P(), check_vma=False))
            sharded_apply = jax.jit(jax.shard_map(
                functools.partial(_apply1_col, axis_name=DATA_AXIS),
                mesh=self.mesh,
                in_specs=(P(None, DATA_AXIS), P()) + (P(),) * 9,
                out_specs=P(), check_vma=False))
            sharded_ae = None
            if self._fused:
                ae = functools.partial(_apply_eval2_col,
                                       monotone=self.monotone,
                                       cat=self.cat, axis_name=DATA_AXIS,
                                       coarse=bool(self._coarse), **kw)
                sharded_ae = jax.jit(jax.shard_map(
                    ae, mesh=self.mesh,
                    in_specs=(P(None, DATA_AXIS), P(), P())
                    + (P(),) * 9
                    + (P(), P(None, DATA_AXIS), P(), P(), P(DATA_AXIS),
                       P(DATA_AXIS, None), cb_spec),
                    out_specs=(P(), P()), check_vma=False))
            # rows replicate: a local sum IS the global root sum, and the
            # leaf gather runs on replicated arrays
            sharded_root = jax.jit(lambda g: jnp.sum(g, axis=0))
            sharded_gather = jax.jit(lambda lv, pos: lv[pos])
            self._fns = (sharded_eval, sharded_apply, sharded_root,
                         sharded_gather, sharded_ae)
            return self._fns
        else:
            from ..context import DATA_AXIS
            P = jax.sharding.PartitionSpec

            ev = functools.partial(_eval2, monotone=self.monotone,
                                   cat=self.cat, axis_name=DATA_AXIS,
                                   coarse=bool(self._coarse), **kw)
            # SplitResult is a flat NamedTuple of replicated arrays
            sharded_eval = jax.jit(jax.shard_map(
                ev, mesh=self.mesh,
                in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None),
                          P(DATA_AXIS), P(), P(), P(), P(), P(), P(), P(),
                          P(None, DATA_AXIS), P(None, DATA_AXIS)),
                out_specs=P()))
            sharded_apply = jax.jit(jax.shard_map(
                _apply1, mesh=self.mesh,
                in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(), P(), P(),
                          P(), P(), P(), P(), P(), P()),
                out_specs=P(DATA_AXIS)))
            sharded_ae = None
            if self._fused:
                ae = functools.partial(_apply_eval2, monotone=self.monotone,
                                       cat=self.cat, axis_name=DATA_AXIS,
                                       coarse=bool(self._coarse), **kw)
                sharded_ae = jax.jit(jax.shard_map(
                    ae, mesh=self.mesh,
                    in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None),
                              P(DATA_AXIS)) + (P(),) * 9
                    + (P(), P(), P(), P(), P(), P(None, DATA_AXIS),
                       P(None, DATA_AXIS)),
                    out_specs=(P(DATA_AXIS), P())))
            sharded_root = jax.jit(jax.shard_map(
                functools.partial(_root_sum, axis_name=DATA_AXIS),
                mesh=self.mesh, in_specs=(P(DATA_AXIS, None),),
                out_specs=P()))
            sharded_gather = jax.jit(jax.shard_map(
                lambda lv, pos: lv[pos], mesh=self.mesh,
                in_specs=(P(), P(DATA_AXIS)), out_specs=P(DATA_AXIS)))
            self._fns = (sharded_eval, sharded_apply, sharded_root,
                         sharded_gather, sharded_ae)
        return self._fns

    def _init_positions(self, n: int) -> jnp.ndarray:
        """Root positions [n] — paged-mesh subclasses shard this."""
        return jnp.zeros((n,), jnp.int32)

    def _feature_width(self, F: int) -> int:
        """Width of the colsample-mask / constraint-path feature space.
        Local F by default; the vertical federated subclass returns the
        GLOBAL width so every rank draws identical masks."""
        return F

    def _split_values(self, sf: np.ndarray, sb: np.ndarray) -> np.ndarray:
        """Raw thresholds for the finished tree. Local cuts resolve every
        feature here; the vertical federated subclass sums owner
        contributions across ranks instead."""
        return self.cuts.split_values(sf, sb)

    # ------------------------------------------------------------- sampling
    def _col_masks(self, seed: int, F: int,
                   base: Optional[np.ndarray] = None):
        return col_masks(self.param, seed, F, base)

    def _allowed(self, path: np.ndarray) -> np.ndarray:
        """Interaction-constraint feature mask for a node with feature-path
        `path` (union of constraint sets containing the path)."""
        cs = self.constraint_sets
        if cs is None:
            return np.ones(len(path), bool)
        compat = ~np.any(path[None, :] & ~cs, axis=1)      # [S]
        if not compat.any():
            return np.ones(len(path), bool)
        return np.any(cs[compat], axis=0)

    def _resolve_schedule(self, n: int) -> None:
        """What ``hist_method`` runs at ``n`` rows (``_coarse``,
        ``_fused``): decided once (n is fixed per DMatrix), before the
        jitted per-split programs are built; the threshold is LOCAL rows.
        Explicit "coarse" keeps the two-dispatch schedule, "fused" and a
        promoting "auto" run apply + child eval as one program
        (bit-exact: tests/test_fused_hist.py)."""
        from ..context import DATA_AXIS

        world = (1 if self.mesh is None
                 else self.mesh.shape.get(DATA_AXIS, 1))
        col = self.split_mode == "col"
        sched = resolve_schedule(
            self.hist_method, n if col else n // max(world, 1),
            self.max_nbins, self.has_missing, numeric=self.cat is None,
            col_split=col)
        self._coarse, self._fused = sched.coarse, sched.fused

    # ------------------------------------------------------------------ grow
    def grow(self, bins: jnp.ndarray, gpair: jnp.ndarray,
             n_real_bins: jnp.ndarray, key: jax.Array) -> LossguideGrown:
        param = self.param
        n, F = bins.shape
        max_leaves = param.max_leaves if param.max_leaves > 0 else (
            2 ** max(param.max_depth, 1))
        cap = 2 * max_leaves - 1
        if self._coarse is None:
            self._resolve_schedule(n)
        fns = self._functions()
        eval2, apply1, root_sum_fn, gather = fns[:4]
        apply_eval = fns[4] if len(fns) > 4 else None
        try:
            seed = int(np.asarray(jax.random.key_data(key)).ravel()[-1])
        except (TypeError, ValueError):
            seed = int(np.asarray(key).ravel()[-1])
        F = self._feature_width(F)  # global width under vertical federated
        # colsample draws come from REAL columns only (padded mesh-col-split
        # columns have n_real == 0); the vertical-federated subclass widens
        # F past the local n_real_bins — its padding-free layout keeps the
        # all-ones base
        nr = np.asarray(n_real_bins)
        node_mask = self._col_masks(
            seed, F, (nr > 0) if nr.shape[0] == F else None)

        # host-side node arrays (compact ids in allocation order)
        sf = np.full(cap, -1, np.int32)
        sb = np.zeros(cap, np.int32)
        dl = np.zeros(cap, bool)
        lc = np.full(cap, -1, np.int32)
        rc = np.full(cap, -1, np.int32)
        pa = np.full(cap, -1, np.int32)
        gn = np.zeros(cap, np.float32)
        gh = np.zeros((cap, 2), np.float64)
        ics = np.zeros(cap, bool)
        cwords = np.zeros((cap, self.n_words), np.uint32)
        depth_of = np.zeros(cap, np.int32)
        lower = np.full(cap, -np.inf, np.float32)
        upper = np.full(cap, np.inf, np.float32)
        paths = np.zeros((cap, F), bool) if self.constraint_sets is not None \
            else None

        positions = self._init_positions(gpair.shape[0])
        bins_t = (None if getattr(bins, "is_paged", False)
                  else bins.T)  # loop-invariant relayout, once per tree
        cb_t = None
        if self._coarse and bins_t is not None:
            # coarse-pass bin ids are loop-invariant too — one pass per
            # tree instead of one per split evaluation
            from ..ops.split import coarse_bin_ids

            mb = (self.max_nbins - 1 if self.has_missing
                  else self.max_nbins)
            cb_t = coarse_bin_ids(bins_t.astype(jnp.int32), mb)
        gh[0] = np.asarray(root_sum_fn(gpair), np.float64)
        n_nodes = 1
        n_leaves = 1
        counter = 0
        pq: list = []   # (-gain, timestamp, nid, split payload)

        def eval_nodes(id0: int, id1: int, apply_args=None) -> None:
            """Evaluate candidate splits of one or two sibling nodes and
            push the valid ones onto the priority queue. ``apply_args``:
            the just-popped parent's split payload — under the fused
            schedule its one-node row advance runs in the SAME dispatch as
            the children's evaluation (the children are the advance's own
            outputs), falling back to a separate apply1 dispatch when the
            children are depth-filtered out of evaluation."""
            nonlocal counter, positions
            ids = [i for i in (id0, id1) if i >= 0]
            if param.max_depth > 0:
                ids = [i for i in ids if depth_of[i] < param.max_depth]
            if not ids:
                if apply_args is not None:
                    with _trace.span("lossguide/apply"):
                        positions = apply1(bins, positions, *apply_args)
                return
            i0 = ids[0]
            i1 = ids[1] if len(ids) > 1 else -1
            fm = np.stack([node_mask(int(depth_of[i])) if i >= 0
                           else np.zeros(F, bool) for i in (i0, i1)])
            if paths is not None:
                fm[0] &= self._allowed(paths[i0])
                if i1 >= 0:
                    fm[1] &= self._allowed(paths[i1])
            psums = np.stack([gh[i0], gh[i1] if i1 >= 0
                              else np.zeros(2)]).astype(np.float32)
            lowers = jnp.asarray(np.asarray(
                [lower[i0], lower[i1 if i1 >= 0 else 0]], np.float32))
            uppers = jnp.asarray(np.asarray(
                [upper[i0], upper[i1 if i1 >= 0 else 0]], np.float32))
            if apply_args is not None and apply_eval is not None:
                # siblings share a depth, so the filter kept both: i0/i1
                # ARE the advance's fresh children
                with _trace.span("lossguide/apply_eval"):
                    positions, res = apply_eval(
                        bins, gpair, positions, *apply_args,
                        jnp.asarray(psums), jnp.asarray(fm), lowers,
                        uppers, n_real_bins, bins_t, cb_t)
            else:
                if apply_args is not None:
                    with _trace.span("lossguide/apply"):
                        positions = apply1(bins, positions, *apply_args)
                with _trace.span("lossguide/eval"):
                    res = eval2(bins, gpair, positions, np.int32(i0),
                                np.int32(i1), jnp.asarray(psums),
                                jnp.asarray(fm), lowers, uppers,
                                n_real_bins, bins_t, cb_t)
            # ONE packed device->host pull for the whole SplitResult —
            # a per-field np.asarray costs 8 blocking device->host
            # transfers per split
            from ..utils.fetch import fetch_struct

            with _trace.span("lossguide/fetch"):
                res = fetch_struct(res)
            gain = np.asarray(res.gain)
            feat = np.asarray(res.feature)
            rbin = np.asarray(res.bin)
            rdl = np.asarray(res.default_left)
            lsum = np.asarray(res.left_sum, np.float64)
            rsum = np.asarray(res.right_sum, np.float64)
            ric = np.asarray(res.is_cat)
            rcw = np.asarray(res.cat_words)
            for slot, nid in ((0, i0), (1, i1)):
                if nid < 0:
                    continue
                g = float(gain[slot])
                if not np.isfinite(g) or g <= max(param.gamma, _EPS):
                    continue
                heapq.heappush(pq, (-g, counter, nid,
                                    (int(feat[slot]), int(rbin[slot]),
                                     bool(rdl[slot]), lsum[slot].copy(),
                                     rsum[slot].copy(), bool(ric[slot]),
                                     rcw[slot].copy())))
                counter += 1

        eval_nodes(0, -1)
        while pq and n_leaves < max_leaves:
            neg_gain, _, nid, payload = heapq.heappop(pq)
            feat, rbin, rdl, lsum, rsum, ric, rcw = payload
            li, ri = n_nodes, n_nodes + 1
            n_nodes += 2
            n_leaves += 1
            sf[nid] = feat
            sb[nid] = rbin
            dl[nid] = rdl
            gn[nid] = -neg_gain
            ics[nid] = ric
            cwords[nid] = rcw if ric else 0
            lc[nid], rc[nid] = li, ri
            pa[li] = pa[ri] = nid
            gh[li], gh[ri] = lsum, rsum
            depth_of[li] = depth_of[ri] = depth_of[nid] + 1
            if self.monotone is not None:
                wl = float(np.clip(calc_weight(lsum[0], lsum[1], param),
                                   lower[nid], upper[nid]))
                wr = float(np.clip(calc_weight(rsum[0], rsum[1], param),
                                   lower[nid], upper[nid]))
                mid = 0.5 * (wl + wr)
                mc = int(np.asarray(self.monotone)[max(feat, 0)])
                lower[li] = mid if mc < 0 else lower[nid]
                upper[li] = mid if mc > 0 else upper[nid]
                lower[ri] = mid if mc > 0 else lower[nid]
                upper[ri] = mid if mc < 0 else upper[nid]
            else:
                lower[li] = lower[ri] = lower[nid]
                upper[li] = upper[ri] = upper[nid]
            if paths is not None:
                child_path = paths[nid].copy()
                child_path[feat] = True
                paths[li] = paths[ri] = child_path
            eval_nodes(li, ri, apply_args=(
                np.int32(nid), np.int32(feat), np.int32(rbin),
                np.bool_(rdl), np.bool_(ric), jnp.asarray(cwords[nid]),
                np.int32(li), np.int32(ri),
                np.int32(self.max_nbins - 1 if self.has_missing
                         else self.max_nbins)))

        # ---- finalize: weights, leaf values, TreeModel -----------------
        w = calc_weight(gh[:n_nodes, 0].astype(np.float32),
                        gh[:n_nodes, 1].astype(np.float32), param)
        w = np.clip(w, lower[:n_nodes], upper[:n_nodes]) * param.eta
        is_leaf = lc[:n_nodes] < 0
        leaf_value = np.where(is_leaf, w, 0.0).astype(np.float32)
        split_value = self._split_values(sf[:n_nodes], sb[:n_nodes])
        tree = TreeModel(
            left_child=lc[:n_nodes].copy(), right_child=rc[:n_nodes].copy(),
            parent=pa[:n_nodes].copy(),
            split_feature=sf[:n_nodes].copy(), split_bin=sb[:n_nodes].copy(),
            split_value=split_value, default_left=dl[:n_nodes].copy(),
            is_leaf=is_leaf, leaf_value=leaf_value,
            sum_hess=gh[:n_nodes, 1].astype(np.float32),
            gain=np.where(is_leaf, 0.0, gn[:n_nodes]).astype(np.float32),
            is_cat_split=ics[:n_nodes].copy(),
            cat_words=cwords[:n_nodes].copy(),
            base_weight=w.astype(np.float32))
        tree.heap_map = np.arange(n_nodes, dtype=np.int32)  # already compact
        delta = gather(jnp.asarray(
            np.concatenate([leaf_value,
                            np.zeros(max(cap - n_nodes, 1), np.float32)])),
            positions)
        return LossguideGrown(positions=positions, delta=delta, tree=tree)

    def to_tree_model(self, g: LossguideGrown) -> TreeModel:
        return g.tree
