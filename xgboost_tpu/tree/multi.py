"""Vector-leaf trees — ``multi_strategy=multi_output_tree``.

Reference: ``MultiTargetTree`` (``src/tree/multi_target_tree_model.cc``,
``include/xgboost/multi_target_tree_model.h:23``) and the multi-target hist
builder (``HistMultiEvaluator``, ``src/tree/hist/evaluate_splits.h:478``;
``MultiTargetHistBuilder``, ``src/tree/updater_quantile_hist.cc:117``): ONE
tree per boosting round whose every leaf holds a K-vector; a split is shared
by all targets and scored by the summed per-target gain.

TPU shape: the depth-wise jitted loop of grow.py, with the gradient matrix
``[n, K, 2]``, per-level histograms ``[N, F, B, K, 2]`` (one fused Pallas
histogram pass per target), and the per-row margin delta accumulated as an
``[n, K]`` matrix via one ``[n, N] @ [N, K]`` one-hot matmul per level.
Interaction constraints apply per feature exactly as in the reference
(``HistMultiEvaluator`` queries ``interaction_constraints_`` per candidate,
``src/tree/hist/evaluate_splits.h:666-669``). Categorical splits and
monotone constraints are not supported in this mode — the reference has the
same restrictions (monotone: ``CHECK`` at
``src/tree/updater_quantile_hist.cc:500``).
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.histogram import build_hist_multi
from ..ops.partition import advance_positions_level, update_positions
from ..ops.split import evaluate_splits_multi
from .param import TrainParam, calc_weight
from .tree import TreeModel

_EPS = 1e-6


class GrownMulti(NamedTuple):
    split_feature: jnp.ndarray  # [max_nodes] int32
    split_bin: jnp.ndarray      # [max_nodes] int32
    default_left: jnp.ndarray   # [max_nodes] bool
    is_leaf: jnp.ndarray        # [max_nodes] bool
    active: jnp.ndarray         # [max_nodes] bool
    leaf_value: jnp.ndarray     # [max_nodes, K] f32 (eta applied)
    node_sum: jnp.ndarray       # [max_nodes, K, 2] f32
    gain: jnp.ndarray           # [max_nodes] f32
    positions: jnp.ndarray      # [n] int32 final heap position
    delta: jnp.ndarray          # [n, K] f32 margin update
    base_weight: jnp.ndarray    # [max_nodes, K] f32


@functools.partial(
    jax.jit,
    static_argnames=("param", "max_nbins", "hist_method", "axis_name",
                     "has_missing", "split_mode"))
def _grow_multi(bins: jnp.ndarray, gpair: jnp.ndarray,
                n_real_bins: jnp.ndarray, tree_mask: jnp.ndarray,
                key: jax.Array,
                constraint_sets: Optional[jnp.ndarray] = None, *,
                param: TrainParam, max_nbins: int,
                hist_method: str = "auto",
                axis_name: Optional[str] = None,
                has_missing: bool = True,
                split_mode: str = "row") -> GrownMulti:
    """``split_mode="col"``: features sharded over ``axis_name``, rows
    replicated — per level each shard evaluates ITS features, an
    all-gather picks the winning shard per node, and one boolean psum
    fans the owner's routing decisions out (the same best-split exchange
    as the scalar ``_grow``; reference ``HistMultiEvaluator`` under
    column split gathers expand entries, evaluate_splits.h:580-626)."""
    n, F = bins.shape
    K = gpair.shape[1]
    max_depth = param.max_depth
    max_nodes = 2 ** (max_depth + 1) - 1
    missing_bin = max_nbins - 1 if has_missing else max_nbins
    col_split = split_mode == "col"
    feat_off = (jax.lax.axis_index(axis_name) * F if col_split else None)
    if constraint_sets is not None:
        # features used on the path to each node (interaction constraints —
        # the reference's HistMultiEvaluator queries them per feature,
        # src/tree/hist/evaluate_splits.h:666-669; same in-jit path/compat
        # algebra as the scalar _grow)
        F_cons = constraint_sets.shape[1]
        node_path = jnp.zeros((max_nodes, F_cons), bool)

    def allreduce(x):
        # column split: every shard already sees all rows -> no hist psum
        if axis_name is None or col_split:
            return x
        return jax.lax.psum(x, axis_name)

    split_feature = jnp.full((max_nodes,), -1, jnp.int32)
    split_bin = jnp.zeros((max_nodes,), jnp.int32)
    default_left = jnp.zeros((max_nodes,), bool)
    is_leaf = jnp.ones((max_nodes,), bool)
    active = jnp.zeros((max_nodes,), bool).at[0].set(True)
    gain = jnp.zeros((max_nodes,), jnp.float32)
    node_sum = jnp.zeros((max_nodes, K, 2), jnp.float32)
    node_sum = node_sum.at[0].set(allreduce(jnp.sum(gpair, axis=0)))
    positions = jnp.zeros((n,), jnp.int32)
    bins_f32 = bins.astype(jnp.float32)
    bins_t = bins.T

    DENSE_LEVEL_MAX = 64
    dense_delta = 2 ** max_depth <= DENSE_LEVEL_MAX
    delta = jnp.zeros((n, K), jnp.float32)

    def level_weight(lo, n_level):
        s = node_sum[lo:lo + n_level]                      # [N,K,2]
        return calc_weight(s[..., 0], s[..., 1], param) * param.eta

    from .grow import _sample_features

    for depth in range(max_depth):
        lo = 2 ** depth - 1
        n_level = 2 ** depth
        idx = lo + jnp.arange(n_level)

        in_level = (positions >= lo) & (positions < lo + n_level)
        rel = jnp.where(in_level, positions - lo, n_level).astype(jnp.int32)
        # K per-target kernel passes (a fused all-components pass measured
        # slower on TPU — see ops/histogram.build_hist_multi)
        hist = build_hist_multi(bins, gpair, rel, n_level, max_nbins,
                                method=hist_method, bins_t=bins_t)
        hist = allreduce(hist)                             # [N,F,B,K,2]

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
            from .grow import interaction_allowed_dev

            path = node_path[lo:lo + n_level]                    # [N,Fc]
            allowed = interaction_allowed_dev(path, constraint_sets)
            if col_split:  # local feature-mask slice of the global allow
                allowed = jax.lax.dynamic_slice(
                    allowed, (0, feat_off), (n_level, F))
            fmask = fmask & allowed

        res = evaluate_splits_multi(hist, node_sum[lo:lo + n_level],
                                    n_real_bins, param, feature_mask=fmask,
                                    has_missing=has_missing)

        if col_split:
            # best-split exchange (scalar _grow protocol, shared helper —
            # the select mask broadcasts over the [N, K, 2] sums)
            from .grow import exchange_best_split

            local_feat, local_bin = res.feature, res.bin
            local_dl = res.default_left
            res, mine = exchange_best_split(res, axis_name, F)

        can_split = (active[lo:lo + n_level]
                     & (res.gain > max(param.gamma, _EPS))
                     & jnp.isfinite(res.gain))

        split_feature = split_feature.at[idx].set(
            jnp.where(can_split, res.feature, -1))
        split_bin = split_bin.at[idx].set(jnp.where(can_split, res.bin, 0))
        default_left = default_left.at[idx].set(can_split & res.default_left)
        is_leaf = is_leaf.at[idx].set(~can_split)
        gain = gain.at[idx].set(jnp.where(can_split, res.gain, 0.0))

        li, ri = 2 * idx + 1, 2 * idx + 2
        active = active.at[li].set(can_split).at[ri].set(can_split)
        zero = jnp.zeros_like(res.left_sum)
        node_sum = node_sum.at[li].set(
            jnp.where(can_split[:, None, None], res.left_sum, zero))
        node_sum = node_sum.at[ri].set(
            jnp.where(can_split[:, None, None], res.right_sum, zero))
        if constraint_sets is not None:
            path = node_path[lo:lo + n_level]
            fsel = (jnp.arange(constraint_sets.shape[1],
                               dtype=jnp.int32)[None, :]
                    == jnp.maximum(res.feature, 0)[:, None]) \
                & can_split[:, None]
            child_path = path | fsel
            node_path = node_path.at[li].set(child_path)
            node_path = node_path.at[ri].set(child_path)

        if dense_delta:
            leaf_now = active[idx] & ~can_split
            w_level = jnp.where(leaf_now[:, None],
                                level_weight(lo, n_level), 0.0)    # [N,K]
            rel_oh = (rel[:, None]
                      == jnp.arange(n_level, dtype=jnp.int32)[None, :])
            delta = delta + jax.lax.dot_general(
                rel_oh.astype(jnp.float32), w_level,
                (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST)

        if col_split and n_level <= DENSE_LEVEL_MAX:
            # only the owning shard routes rows; one boolean psum fans the
            # decisions out (reference partition-bitvector broadcast)
            positions = advance_positions_level(
                bins_f32, positions, rel,
                jnp.where(can_split & mine, local_feat, -1),
                jnp.where(can_split & mine, local_bin, 0),
                can_split & mine & local_dl, can_split, missing_bin,
                decision_axis=axis_name)
        elif n_level <= DENSE_LEVEL_MAX:
            positions = advance_positions_level(
                bins_f32, positions, rel,
                jnp.where(can_split, res.feature, -1),
                jnp.where(can_split, res.bin, 0),
                can_split & res.default_left, can_split, missing_bin)
        else:
            is_split_full = jnp.zeros((max_nodes,), bool).at[idx].set(
                can_split)
            positions = update_positions(
                bins, positions, split_feature, split_bin, default_left,
                is_split_full, missing_bin,
                decision_axis=axis_name if col_split else None,
                feat_offset=feat_off)

    w = calc_weight(node_sum[..., 0], node_sum[..., 1], param) * param.eta
    leaf_mask = (active & is_leaf)[:, None]
    leaf_value = jnp.where(leaf_mask, w, 0.0).astype(jnp.float32)
    base_weight = jnp.where(active[:, None], w, 0.0).astype(jnp.float32)

    if dense_delta:
        lo = 2 ** max_depth - 1
        n_level = 2 ** max_depth
        w_last = jnp.where(active[lo:lo + n_level, None],
                           level_weight(lo, n_level), 0.0)
        rel = jnp.where(positions >= lo, positions - lo,
                        n_level).astype(jnp.int32)
        rel_oh = rel[:, None] == jnp.arange(n_level, dtype=jnp.int32)[None, :]
        delta = delta + jax.lax.dot_general(
            rel_oh.astype(jnp.float32), w_last, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST)
    else:
        delta = leaf_value[positions]

    return GrownMulti(split_feature=split_feature, split_bin=split_bin,
                      default_left=default_left, is_leaf=is_leaf,
                      active=active, leaf_value=leaf_value,
                      node_sum=node_sum, gain=gain, positions=positions,
                      delta=delta, base_weight=base_weight)


class MultiTargetTreeModel(TreeModel):
    """Compact BFS tree whose ``leaf_value`` / ``base_weight`` are [n, K]
    (reference ``MultiTargetTree``). ``sum_hess`` keeps the target-summed
    hessian so cover-based importances stay defined."""

    @property
    def n_targets(self) -> int:
        return self.leaf_value.shape[1]

    def to_json(self) -> dict:
        # the scalar schema mixes thresholds and leaf values in
        # split_conditions; with vector leaves, thresholds stay there and the
        # leaf/base-weight matrices ride in their own fields
        return {
            "n_targets": self.n_targets,
            "left_children": self.left_child.tolist(),
            "right_children": self.right_child.tolist(),
            "parents": self.parent.tolist(),
            "split_indices": [int(max(f, 0)) for f in self.split_feature],
            "split_conditions": [float(v) for v in self.split_value],
            "default_left": [int(d) for d in self.default_left],
            "loss_changes": self.gain.tolist(),
            "sum_hessian": self.sum_hess.tolist(),
            "split_bins": self.split_bin.tolist(),
            "leaf_values": self.leaf_value.tolist(),
            "base_weights": self.base_weight.tolist(),
        }

    @staticmethod
    def from_json(obj: dict) -> "MultiTargetTreeModel":
        base = TreeModel.from_json({**obj, "base_weights":
                                    [0.0] * len(obj["left_children"])})
        lv = np.asarray(obj["leaf_values"], np.float32)
        bw = np.asarray(obj["base_weights"], np.float32)
        return MultiTargetTreeModel(
            left_child=base.left_child, right_child=base.right_child,
            parent=base.parent, split_feature=base.split_feature,
            split_bin=base.split_bin,
            split_value=np.asarray(obj["split_conditions"], np.float32),
            default_left=base.default_left, is_leaf=base.is_leaf,
            leaf_value=np.where(base.is_leaf[:, None], lv, 0.0),
            sum_hess=base.sum_hess, gain=base.gain, base_weight=bw)


@functools.partial(jax.jit, static_argnames=("max_depth",))
def _predict_margin_multi(split_feature, split_value, default_left, is_leaf,
                          left_child, right_child, leaf_value, X, base,
                          max_depth: int):
    """leaf_value: [T, M, K] -> (margin [n, K], leaf pos [n, T])."""
    n = X.shape[0]
    T, M, K = leaf_value.shape
    pos = jnp.zeros((n, T), jnp.int32)
    tofs = (jnp.arange(T, dtype=jnp.int32) * M)[None, :]
    sf = split_feature.reshape(-1)
    sv = split_value.reshape(-1)
    dl = default_left.reshape(-1)
    lf = is_leaf.reshape(-1)
    lc = left_child.reshape(-1)
    rc = right_child.reshape(-1)
    for _ in range(max_depth):
        gi = tofs + pos
        feat = sf[gi]
        x = jnp.take_along_axis(X, jnp.maximum(feat, 0), axis=1)
        go_right = x > sv[gi]
        go_right = jnp.where(jnp.isnan(x), ~dl[gi], go_right)
        child = jnp.where(go_right, rc[gi], lc[gi])
        pos = jnp.where(lf[gi], pos, child)
    leaf = leaf_value.reshape(T * M, K)[tofs + pos]        # [n, T, K]
    return jnp.sum(leaf, axis=1) + base[None, :], pos


@functools.partial(jax.jit, static_argnames=("max_depth", "missing_bin"))
def _predict_margin_binned_multi(split_feature, split_bin, default_left,
                                 is_leaf, left_child, right_child,
                                 leaf_value, bins, base, max_depth: int,
                                 missing_bin: int):
    n = bins.shape[0]
    T, M, K = leaf_value.shape
    pos = jnp.zeros((n, T), jnp.int32)
    tofs = (jnp.arange(T, dtype=jnp.int32) * M)[None, :]
    sf = split_feature.reshape(-1)
    sb = split_bin.reshape(-1)
    dl = default_left.reshape(-1)
    lf = is_leaf.reshape(-1)
    lc = left_child.reshape(-1)
    rc = right_child.reshape(-1)
    for _ in range(max_depth):
        gi = tofs + pos
        feat = sf[gi]
        b = jnp.take_along_axis(bins, jnp.maximum(feat, 0).astype(jnp.int32),
                                axis=1).astype(jnp.int32)
        go_right = b > sb[gi]
        go_right = jnp.where(b == missing_bin, ~dl[gi], go_right)
        child = jnp.where(go_right, rc[gi], lc[gi])
        pos = jnp.where(lf[gi], pos, child)
    leaf = leaf_value.reshape(T * M, K)[tofs + pos]
    return jnp.sum(leaf, axis=1) + base[None, :], pos


class MultiForestPredictor:
    """Batched inference over a list of vector-leaf trees."""

    def __init__(self, trees: List[MultiTargetTreeModel],
                 n_groups: int) -> None:
        cap = max(t.num_nodes() for t in trees)
        K = trees[0].n_targets
        T = len(trees)
        self.max_depth = max(t.max_depth() for t in trees)

        def pad1(vals, fill, dtype):
            out = np.full((T, cap), fill, dtype)
            for i, v in enumerate(vals):
                out[i, : len(v)] = v
            return out

        lv = np.zeros((T, cap, K), np.float32)
        for i, t in enumerate(trees):
            lv[i, : t.num_nodes()] = t.leaf_value
        self.dev: Dict[str, jnp.ndarray] = {
            "split_feature": jnp.asarray(
                pad1([t.split_feature for t in trees], -1, np.int32)),
            "split_value": jnp.asarray(
                pad1([t.split_value for t in trees], 0, np.float32)),
            "split_bin": jnp.asarray(
                pad1([t.split_bin for t in trees], 0, np.int32)),
            "default_left": jnp.asarray(
                pad1([t.default_left for t in trees], False, bool)),
            "is_leaf": jnp.asarray(
                pad1([t.is_leaf for t in trees], True, bool)),
            "left_child": jnp.asarray(
                pad1([t.left_child for t in trees], -1, np.int32)),
            "right_child": jnp.asarray(
                pad1([t.right_child for t in trees], -1, np.int32)),
            "leaf_value": jnp.asarray(lv),
        }

    def margin(self, X, base):
        d = self.dev
        return _predict_margin_multi(
            d["split_feature"], d["split_value"], d["default_left"],
            d["is_leaf"], d["left_child"], d["right_child"], d["leaf_value"],
            jnp.asarray(X, jnp.float32), jnp.asarray(base, jnp.float32),
            self.max_depth)

    def margin_binned(self, bins, missing_bin: int, base):
        d = self.dev
        return _predict_margin_binned_multi(
            d["split_feature"], d["split_bin"], d["default_left"],
            d["is_leaf"], d["left_child"], d["right_child"], d["leaf_value"],
            bins, jnp.asarray(base, jnp.float32), self.max_depth,
            missing_bin)


class MultiTargetGrower:
    """Host-side wrapper mirroring grow.TreeGrower for vector-leaf trees."""

    def __init__(self, param: TrainParam, max_nbins: int, cuts,
                 hist_method: str = "auto",
                 mesh: Optional[jax.sharding.Mesh] = None,
                 has_missing: bool = True,
                 constraint_sets: Optional[np.ndarray] = None,
                 split_mode: str = "row") -> None:
        if param.grow_policy == "lossguide":
            raise NotImplementedError(
                "multi_output_tree supports grow_policy=depthwise only; "
                "use MultiLossguideGrower via grow_policy=lossguide")
        if split_mode == "col" and mesh is None:
            raise ValueError("data_split_mode=col requires a mesh")
        self.param = param
        self.max_nbins = max_nbins
        self.cuts = cuts
        self.hist_method = hist_method
        self.mesh = mesh
        self.has_missing = has_missing
        self.split_mode = split_mode
        self.constraint_sets = (None if constraint_sets is None
                                else jnp.asarray(constraint_sets, bool))
        if split_mode == "col" and self.constraint_sets is not None:
            # bins pad the feature axis to a multiple of the mesh width;
            # the replicated GLOBAL constraint arrays must match (padding
            # columns have n_real == 0 and can never win a split)
            from ..context import DATA_AXIS

            world = mesh.shape.get(DATA_AXIS, 1)
            F = int(self.constraint_sets.shape[1])
            from ..data.binned import feature_pad_for_mesh

            pad = feature_pad_for_mesh(F, world)
            if pad:
                self.constraint_sets = jnp.pad(self.constraint_sets,
                                               ((0, 0), (0, pad)))
        self._sharded_fn = None
        self._repark_fn = None

    def grow(self, bins: jnp.ndarray, gpair: jnp.ndarray,
             n_real_bins: jnp.ndarray, key: jax.Array) -> GrownMulti:
        from .grow import _sample_features

        F = bins.shape[1]
        tree_mask = _sample_features(jax.random.fold_in(key, 0xC0),
                                     jnp.ones((F,), bool),
                                     self.param.colsample_bytree)
        key = jax.random.fold_in(key, 0x5EED)
        if self.mesh is None:
            g = _grow_multi(bins, gpair, n_real_bins, tree_mask, key,
                            self.constraint_sets,
                            param=self.param, max_nbins=self.max_nbins,
                            hist_method=self.hist_method, axis_name=None,
                            has_missing=self.has_missing)
        else:
            g = self._sharded(bins, gpair, n_real_bins, tree_mask, key)
        if self.param.max_leaves > 0:
            g = self._truncate_max_leaves(g)
        return g

    def _truncate_max_leaves(self, g: GrownMulti) -> GrownMulti:
        """Depth-wise ``max_leaves`` over vector leaves — the K-channel
        mirror of ``TreeGrower._truncate_max_leaves`` (same reference
        Driver schedule, shared via ``grow.select_max_leaves``)."""
        from .grow import select_max_leaves

        active = np.asarray(g.active)
        is_leaf = np.asarray(g.is_leaf)
        exists, selected, changed = select_max_leaves(
            active, is_leaf, self.param.max_leaves)
        if not changed:
            return g
        base_weight = np.asarray(g.base_weight)           # [cap, K]
        new_is_leaf = exists & ~selected
        leaf_value = np.where(new_is_leaf[:, None], base_weight,
                              0.0).astype(np.float32)
        if self.mesh is not None and self.split_mode == "row":
            # row-split mesh: positions are data-sharded (and on a
            # multi-process mesh not host-addressable) — re-park rows of
            # truncated subtrees ON DEVICE with the replicated node arrays
            pos, delta = self._repark(g.positions, jnp.asarray(exists),
                                      jnp.asarray(leaf_value))
        else:
            pos = np.asarray(g.positions)
            for _ in range(self.param.max_depth):
                # re-park rows of truncated subtrees on the ancestor
                pos = np.where(exists[pos], pos, (pos - 1) // 2)
            pos = pos.astype(np.int32)
            delta = jnp.asarray(leaf_value[pos])
        return GrownMulti(
            split_feature=np.where(selected, np.asarray(g.split_feature),
                                   -1).astype(np.int32),
            split_bin=np.where(selected, np.asarray(g.split_bin),
                               0).astype(np.int32),
            default_left=np.asarray(g.default_left) & selected,
            is_leaf=new_is_leaf, active=exists,
            leaf_value=leaf_value,
            node_sum=np.asarray(g.node_sum),
            gain=np.where(selected, np.asarray(g.gain),
                          0.0).astype(np.float32),
            positions=pos, delta=delta,
            base_weight=np.where(exists[:, None], base_weight,
                                 0.0).astype(np.float32))

    def _repark(self, positions, exists, leaf_value):
        """Device-side max_leaves re-park over sharded positions: walk each
        row up to its deepest surviving ancestor and gather its new leaf
        vector — one shard_map dispatch, no host pull of [n] arrays."""
        from ..context import DATA_AXIS

        if self._repark_fn is None:
            P = jax.sharding.PartitionSpec
            max_depth = self.param.max_depth

            def repark(pos, ex, lv):
                def body(_, p):
                    return jnp.where(ex[p], p, (p - 1) // 2)

                pos = jax.lax.fori_loop(0, max_depth, body, pos)
                return pos, lv[pos]

            self._repark_fn = jax.jit(jax.shard_map(
                repark, mesh=self.mesh,
                in_specs=(P(DATA_AXIS), P(), P()),
                out_specs=(P(DATA_AXIS), P(DATA_AXIS, None))))
        return self._repark_fn(positions, exists, leaf_value)

    def _sharded(self, bins, gpair, n_real_bins, tree_mask, key):
        from ..context import DATA_AXIS

        if self._sharded_fn is None:
            P = jax.sharding.PartitionSpec

            def inner(b, g, nr, tm, k):
                return _grow_multi(b, g, nr, tm, k, self.constraint_sets,
                                   param=self.param,
                                   max_nbins=self.max_nbins,
                                   hist_method=self.hist_method,
                                   axis_name=DATA_AXIS,
                                   has_missing=self.has_missing,
                                   split_mode=self.split_mode)

            if self.split_mode == "col":
                # features sharded, rows replicated; every output passes
                # through the best-split exchange and is replicated — the
                # static replication checker cannot prove it through the
                # owner-shard select chain (same as the scalar grower)
                in_specs = (P(None, DATA_AXIS), P(), P(DATA_AXIS),
                            P(DATA_AXIS), P())
                out_specs = GrownMulti(
                    split_feature=P(), split_bin=P(), default_left=P(),
                    is_leaf=P(), active=P(), leaf_value=P(), node_sum=P(),
                    gain=P(), positions=P(), delta=P(), base_weight=P())
                check_vma = False
            else:
                in_specs = (P(DATA_AXIS, None), P(DATA_AXIS, None, None),
                            P(), P(), P())
                out_specs = GrownMulti(
                    split_feature=P(), split_bin=P(), default_left=P(),
                    is_leaf=P(), active=P(), leaf_value=P(), node_sum=P(),
                    gain=P(), positions=P(DATA_AXIS),
                    delta=P(DATA_AXIS, None), base_weight=P())
                check_vma = True
            self._sharded_fn = jax.jit(jax.shard_map(
                inner, mesh=self.mesh,
                in_specs=in_specs, out_specs=out_specs,
                check_vma=check_vma))
        return self._sharded_fn(bins, gpair, n_real_bins, tree_mask, key)

    def to_tree_model(self, g) -> MultiTargetTreeModel:
        """Accepts a GrownMulti with device or host arrays (duck-typed)."""
        sf = np.asarray(g.split_feature)
        sb = np.asarray(g.split_bin)
        node_sum = np.asarray(g.node_sum)
        return MultiTargetTreeModel.from_heap(
            split_feature=sf, split_bin=sb,
            split_value=self.cuts.split_values(sf, sb),
            default_left=np.asarray(g.default_left),
            is_leaf=np.asarray(g.is_leaf), active=np.asarray(g.active),
            leaf_value=np.asarray(g.leaf_value),
            sum_hess=node_sum[:, :, 1].sum(axis=1),
            gain=np.asarray(g.gain),
            base_weight=np.asarray(g.base_weight))


def _eval2_multi(bins, gpair, positions, id0, id1, parent_sums, fmask,
                 n_real_bins, bins_t, *, param: TrainParam, max_nbins: int,
                 hist_method: str, has_missing: bool = True,
                 axis_name: Optional[str] = None):
    """Histogram + shared-split enumeration for (up to) two sibling nodes
    over the K-channel gradient — the vector-leaf mirror of
    ``lossguide._eval2`` (``bins_t``: loop-invariant transpose, once per
    tree). Under a row-split mesh the two-node histogram psums across the
    data axis, one collective per split (the same placement as the
    depthwise ``_grow_multi`` level psum)."""
    rel = jnp.where(positions == id0, 0,
                    jnp.where(positions == id1, 1, 2)).astype(jnp.int32)
    hist = build_hist_multi(bins, gpair, rel, 2, max_nbins,
                            method=hist_method, bins_t=bins_t)
    if axis_name is not None:
        hist = jax.lax.psum(hist, axis_name)
    return evaluate_splits_multi(hist, parent_sums, n_real_bins, param,
                                 feature_mask=fmask,
                                 has_missing=has_missing)


def _eval2_multi_col(bins, gpair, positions, id0, id1, parent_sums, fmask,
                     n_real_bins, bins_t, *, param: TrainParam,
                     max_nbins: int, hist_method: str, axis_name: str,
                     has_missing: bool = True):
    """Column-split ``_eval2_multi``: this shard's bins hold global
    features [off, off + F); rows replicate so the K-channel two-node
    histogram needs no psum (``_eval2_multi`` with ``axis_name=None``),
    and the per-shard best crosses the same best-split exchange as the
    depthwise ``_grow_multi`` col branch — gain allgather, psum-select
    the winner's fields with its feature id globalised. Reference: the
    col-split evaluator is updater-generic
    (``src/tree/hist/evaluate_splits.h:294-409``) and the LossGuide
    Driver imposes no split-mode restriction (``src/tree/driver.h``)."""
    from .grow import exchange_best_split

    res = _eval2_multi(bins, gpair, positions, id0, id1, parent_sums,
                       fmask, n_real_bins, bins_t, param=param,
                       max_nbins=max_nbins, hist_method=hist_method,
                       axis_name=None, has_missing=has_missing)
    res, _ = exchange_best_split(res, axis_name, bins.shape[1])
    return res


class MultiLossguideGrower:
    """Loss-guided vector-leaf growth — ``multi_strategy=multi_output_tree``
    with ``grow_policy=lossguide``. Reference: the SAME ``Driver`` template
    schedules both builders (``src/tree/driver.h:70-78`` pops one best
    candidate under LossGuide; ``MultiTargetHistBuilder`` plugs into it at
    ``src/tree/updater_quantile_hist.cc:54-115``), so the greedy pop loop
    of ``LossguideGrower`` carries over verbatim — only the two device
    kernels change to their K-channel forms. Compact host arrays, capacity
    ``2 * max_leaves - 1``."""

    def __init__(self, param: TrainParam, max_nbins: int, cuts,
                 hist_method: str = "auto",
                 mesh: Optional[jax.sharding.Mesh] = None,
                 has_missing: bool = True,
                 constraint_sets: Optional[np.ndarray] = None,
                 split_mode: str = "row") -> None:
        if split_mode == "col" and mesh is None:
            raise NotImplementedError(
                "multi_output_tree lossguide column split requires a "
                "device mesh (vertical federated vector-leaf training is "
                "not supported)")
        if param.max_leaves <= 0 and param.max_depth <= 0:
            raise ValueError(
                "grow_policy=lossguide needs max_leaves > 0 or max_depth > 0")
        self.param = param
        self.max_nbins = max_nbins
        self.cuts = cuts
        self.hist_method = hist_method
        self.mesh = mesh
        self.split_mode = split_mode
        self.has_missing = has_missing
        self.constraint_sets = (None if constraint_sets is None
                                else np.asarray(constraint_sets, bool))
        if split_mode == "col" and self.constraint_sets is not None:
            # bins pad the feature axis to a multiple of the mesh width;
            # the host-side interaction paths index the padded width
            # (padding columns have n_real == 0, never winning a split)
            from ..context import DATA_AXIS

            world = mesh.shape.get(DATA_AXIS, 1)
            from ..data.binned import feature_pad_for_mesh

            pad = feature_pad_for_mesh(self.constraint_sets.shape[1],
                                       world)
            if pad:
                self.constraint_sets = np.pad(self.constraint_sets,
                                              ((0, 0), (0, pad)))
        self._fns = None

    def _functions(self):
        if self._fns is None:
            from .lossguide import _apply1

            kw = dict(param=self.param, max_nbins=self.max_nbins,
                      hist_method=self.hist_method,
                      has_missing=self.has_missing)
            if self.mesh is None:
                ev = functools.partial(_eval2_multi, axis_name=None, **kw)
                self._fns = (jax.jit(ev), jax.jit(_apply1),
                             jax.jit(lambda g: jnp.sum(g, axis=0)),
                             jax.jit(lambda lv, pos: lv[pos]))
            elif self.split_mode == "col":
                # features sharded, rows replicated: the K-channel local
                # eval + the same winner exchange / owner-decision
                # advance as the scalar lossguide col branch
                from ..context import DATA_AXIS
                from .lossguide import _apply1_col
                P = jax.sharding.PartitionSpec

                ev = functools.partial(_eval2_multi_col,
                                       axis_name=DATA_AXIS, **kw)
                sharded_eval = jax.jit(jax.shard_map(
                    ev, mesh=self.mesh,
                    in_specs=(P(None, DATA_AXIS), P(), P(), P(), P(),
                              P(), P(None, DATA_AXIS), P(DATA_AXIS),
                              P(DATA_AXIS, None)),
                    out_specs=P(), check_vma=False))
                sharded_apply = jax.jit(jax.shard_map(
                    functools.partial(_apply1_col, axis_name=DATA_AXIS),
                    mesh=self.mesh,
                    in_specs=(P(None, DATA_AXIS), P()) + (P(),) * 9,
                    out_specs=P(), check_vma=False))
                # rows replicate: a local sum IS the global root sum
                sharded_root = jax.jit(lambda g: jnp.sum(g, axis=0))
                sharded_gather = jax.jit(lambda lv, pos: lv[pos])
                self._fns = (sharded_eval, sharded_apply, sharded_root,
                             sharded_gather)
            else:
                # row-split mesh (VERDICT r4 #5): the same two per-split
                # kernels as the scalar lossguide mesh branch, K-channel —
                # rows shard, the two-node histogram psums once per split
                from ..context import DATA_AXIS
                from .lossguide import _root_sum
                P = jax.sharding.PartitionSpec

                ev = functools.partial(_eval2_multi, axis_name=DATA_AXIS,
                                       **kw)
                sharded_eval = jax.jit(jax.shard_map(
                    ev, mesh=self.mesh,
                    in_specs=(P(DATA_AXIS, None), P(DATA_AXIS, None, None),
                              P(DATA_AXIS), P(), P(), P(), P(), P(),
                              P(None, DATA_AXIS)),
                    out_specs=P()))
                sharded_apply = jax.jit(jax.shard_map(
                    _apply1, mesh=self.mesh,
                    in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(), P(),
                              P(), P(), P(), P(), P(), P(), P()),
                    out_specs=P(DATA_AXIS)))
                sharded_root = jax.jit(jax.shard_map(
                    functools.partial(_root_sum, axis_name=DATA_AXIS),
                    mesh=self.mesh,
                    in_specs=(P(DATA_AXIS, None, None),), out_specs=P()))
                sharded_gather = jax.jit(jax.shard_map(
                    lambda lv, pos: lv[pos], mesh=self.mesh,
                    in_specs=(P(), P(DATA_AXIS)),
                    out_specs=P(DATA_AXIS, None)))
                self._fns = (sharded_eval, sharded_apply, sharded_root,
                             sharded_gather)
        return self._fns

    def _init_positions(self, n: int) -> jnp.ndarray:
        """Root positions [n] — the paged subclass shards this."""
        return jnp.zeros((n,), jnp.int32)

    def grow(self, bins: jnp.ndarray, gpair: jnp.ndarray,
             n_real_bins: jnp.ndarray, key: jax.Array):
        import heapq

        from .lossguide import LossguideGrown, col_masks

        param = self.param
        n, F = bins.shape
        K = gpair.shape[1]
        max_leaves = param.max_leaves if param.max_leaves > 0 else (
            2 ** max(param.max_depth, 1))
        cap = 2 * max_leaves - 1
        eval2, apply1, root_sum_fn, gather = self._functions()
        try:
            seed = int(np.asarray(jax.random.key_data(key)).ravel()[-1])
        except (TypeError, ValueError):
            seed = int(np.asarray(key).ravel()[-1])
        # seed colsample draws from real columns only — padded mesh-col-split
        # columns (n_real == 0) must not consume draws (ADVICE r5 #2)
        nr = np.asarray(n_real_bins)
        node_mask = col_masks(param, seed, F,
                              (nr > 0) if nr.shape[0] == F else None)

        sf = np.full(cap, -1, np.int32)
        sb = np.zeros(cap, np.int32)
        dl = np.zeros(cap, bool)
        lc = np.full(cap, -1, np.int32)
        rc = np.full(cap, -1, np.int32)
        pa = np.full(cap, -1, np.int32)
        gn = np.zeros(cap, np.float32)
        gh = np.zeros((cap, K, 2), np.float64)
        depth_of = np.zeros(cap, np.int32)
        cons = self.constraint_sets
        paths = np.zeros((cap, F), bool) if cons is not None else None
        _EPS = 1e-6

        # gpair.shape[0], NOT bins.shape[0]: in mesh x paged mode the
        # per-row vectors are padded to the page-aligned mesh layout
        # while the paged matrix reports its unpadded row count (same
        # convention as the scalar lossguide grower)
        positions = self._init_positions(gpair.shape[0])
        bins_t = (None if getattr(bins, "is_paged", False)
                  else bins.T)  # loop-invariant relayout, once per tree
        gh[0] = np.asarray(root_sum_fn(gpair), np.float64)
        n_nodes = 1
        n_leaves = 1
        counter = 0
        pq: list = []

        def eval_nodes(id0: int, id1: int) -> None:
            nonlocal counter
            ids = [i for i in (id0, id1) if i >= 0]
            if param.max_depth > 0:
                ids = [i for i in ids if depth_of[i] < param.max_depth]
            if not ids:
                return
            i0 = ids[0]
            i1 = ids[1] if len(ids) > 1 else -1
            fm = np.stack([node_mask(int(depth_of[i])) if i >= 0
                           else np.zeros(F, bool) for i in (i0, i1)])
            if paths is not None:
                from .grow import interaction_allowed_host

                fm[0] &= interaction_allowed_host(paths[i0][None], cons)[0]
                if i1 >= 0:
                    fm[1] &= interaction_allowed_host(paths[i1][None],
                                                     cons)[0]
            psums = np.stack([gh[i0], gh[i1] if i1 >= 0
                              else np.zeros((K, 2))]).astype(np.float32)
            res = eval2(bins, gpair, positions, np.int32(i0), np.int32(i1),
                        jnp.asarray(psums), jnp.asarray(fm), n_real_bins,
                        bins_t)
            # one packed pull (see lossguide.py eval_nodes)
            from ..utils.fetch import fetch_struct

            res = fetch_struct(res)
            gain = np.asarray(res.gain)
            feat = np.asarray(res.feature)
            rbin = np.asarray(res.bin)
            rdl = np.asarray(res.default_left)
            lsum = np.asarray(res.left_sum, np.float64)   # [2, K, 2]
            rsum = np.asarray(res.right_sum, np.float64)
            for slot, nid in ((0, i0), (1, i1)):
                if nid < 0:
                    continue
                g = float(gain[slot])
                if not np.isfinite(g) or g <= max(param.gamma, _EPS):
                    continue
                heapq.heappush(pq, (-g, counter, nid,
                                    (int(feat[slot]), int(rbin[slot]),
                                     bool(rdl[slot]), lsum[slot].copy(),
                                     rsum[slot].copy())))
                counter += 1

        eval_nodes(0, -1)
        missing_bin = np.int32(self.max_nbins - 1 if self.has_missing
                               else self.max_nbins)
        empty_words = jnp.zeros((1,), jnp.uint32)
        while pq and n_leaves < max_leaves:
            neg_gain, _, nid, payload = heapq.heappop(pq)
            feat, rbin, rdl, lsum, rsum = payload
            li, ri = n_nodes, n_nodes + 1
            n_nodes += 2
            n_leaves += 1
            sf[nid] = feat
            sb[nid] = rbin
            dl[nid] = rdl
            gn[nid] = -neg_gain
            lc[nid], rc[nid] = li, ri
            pa[li] = pa[ri] = nid
            gh[li], gh[ri] = lsum, rsum
            depth_of[li] = depth_of[ri] = depth_of[nid] + 1
            if paths is not None:
                child_path = paths[nid].copy()
                child_path[feat] = True
                paths[li] = paths[ri] = child_path
            positions = apply1(
                bins, positions, np.int32(nid), np.int32(feat),
                np.int32(rbin), np.bool_(rdl), np.bool_(False),
                empty_words, np.int32(li), np.int32(ri), missing_bin)
            eval_nodes(li, ri)

        w = np.asarray(calc_weight(
            jnp.asarray(gh[:n_nodes, :, 0], jnp.float32),
            jnp.asarray(gh[:n_nodes, :, 1], jnp.float32),
            param)) * param.eta                            # [n_nodes, K]
        is_leaf = lc[:n_nodes] < 0
        leaf_value = np.where(is_leaf[:, None], w, 0.0).astype(np.float32)
        split_value = self.cuts.split_values(sf[:n_nodes], sb[:n_nodes])
        tree = MultiTargetTreeModel(
            left_child=lc[:n_nodes].copy(), right_child=rc[:n_nodes].copy(),
            parent=pa[:n_nodes].copy(),
            split_feature=sf[:n_nodes].copy(), split_bin=sb[:n_nodes].copy(),
            split_value=split_value, default_left=dl[:n_nodes].copy(),
            is_leaf=is_leaf, leaf_value=leaf_value,
            sum_hess=gh[:n_nodes, :, 1].sum(axis=1).astype(np.float32),
            gain=np.where(is_leaf, 0.0, gn[:n_nodes]).astype(np.float32),
            is_cat_split=np.zeros(n_nodes, bool),
            cat_words=np.zeros((n_nodes, 1), np.uint32),
            base_weight=w.astype(np.float32))
        tree.heap_map = np.arange(n_nodes, dtype=np.int32)
        leaf_pad = np.zeros((max(cap, n_nodes), K), np.float32)
        leaf_pad[:n_nodes] = leaf_value
        delta = gather(jnp.asarray(leaf_pad), positions)

        return LossguideGrown(positions=positions, delta=delta, tree=tree)

    def to_tree_model(self, g) -> MultiTargetTreeModel:
        return g.tree
