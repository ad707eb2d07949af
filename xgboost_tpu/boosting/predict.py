"""Batched forest inference.

Reference predictors walk trees row-by-row (CPU ``src/predictor/cpu_predictor.cc:299``,
GPU one-thread-per-row ``src/predictor/gpu_predictor.cu:285-320``). The TPU-native
predictor is a *level-synchronous* walk: positions for ALL (row, tree) pairs
advance one depth per step via child-pointer gathers — no divergence, static
shapes, and the final per-group reduction is a [rows, trees] x [trees, groups]
matmul on the MXU. Node ids are the compact BFS ids of ``TreeModel``; rows
parked at a leaf gather themselves, so ragged tree depths cost nothing extra.
Categorical nodes route by membership in a packed uint32 left-set bitmask
(reference ``CategoricalSplitMatrix`` + ``Decision``); unseen / out-of-range
category codes follow the missing direction.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..registry import PREDICTORS


def _bit_is_left(code: jnp.ndarray, words_flat: jnp.ndarray,
                 gi: jnp.ndarray, n_words: int) -> jnp.ndarray:
    """code: [n,T] category; words_flat: [T*M, W]; gi: [n,T] node gather ids
    -> True when code is in the node's left set."""
    widx = jnp.clip(code // 32, 0, n_words - 1)
    words = words_flat[gi]                     # [n,T,W]
    word = jnp.take_along_axis(words, widx[..., None].astype(jnp.int32),
                               axis=2)[..., 0]
    bit = (word >> (code % 32).astype(jnp.uint32)) & jnp.uint32(1)
    return bit == 1


@functools.partial(jax.jit, static_argnames=("max_depth",))
def _predict_margin(split_feature: jnp.ndarray, split_value: jnp.ndarray,
                    default_left: jnp.ndarray, is_leaf: jnp.ndarray,
                    left_child: jnp.ndarray, right_child: jnp.ndarray,
                    leaf_value: jnp.ndarray, tree_weight: jnp.ndarray,
                    group_onehot: jnp.ndarray, X: jnp.ndarray,
                    base: jnp.ndarray, max_depth: int,
                    is_cat_split: Optional[jnp.ndarray] = None,
                    cat_words: Optional[jnp.ndarray] = None):
    """-> (margin [n, G], leaf_pos [n, T] compact node ids)."""
    n = X.shape[0]
    T, M = split_feature.shape
    pos = jnp.zeros((n, T), jnp.int32)
    tofs = (jnp.arange(T, dtype=jnp.int32) * M)[None, :]
    sf = split_feature.reshape(-1)
    sv = split_value.reshape(-1)
    dl = default_left.reshape(-1)
    lf = is_leaf.reshape(-1)
    lc = left_child.reshape(-1)
    rc = right_child.reshape(-1)
    if cat_words is not None:
        ics = is_cat_split.reshape(-1)
        cw = cat_words.reshape(T * M, -1)
        n_words = cat_words.shape[-1]
        n_cats = n_words * 32

    for _ in range(max_depth):
        gi = tofs + pos
        feat = sf[gi]
        x = jnp.take_along_axis(X, jnp.maximum(feat, 0), axis=1)
        go_right = x > sv[gi]
        missing = jnp.isnan(x)
        if cat_words is not None:
            code = jnp.where(missing, -1, x).astype(jnp.int32)
            in_range = (code >= 0) & (code < n_cats)
            left = _bit_is_left(jnp.maximum(code, 0), cw, gi, n_words)
            cat_node = ics[gi]
            go_right = jnp.where(cat_node, ~left, go_right)
            missing = missing | (cat_node & ~in_range)
        go_right = jnp.where(missing, ~dl[gi], go_right)
        child = jnp.where(go_right, rc[gi], lc[gi])
        pos = jnp.where(lf[gi], pos, child)

    leaf = leaf_value.reshape(-1)[tofs + pos] * tree_weight[None, :]
    margin = jnp.dot(leaf, group_onehot,
                     precision=jax.lax.Precision.HIGHEST) + base[None, :]
    return margin, pos


@functools.partial(jax.jit, static_argnames=("max_depth",))
def _predict_margin_binned(split_feature: jnp.ndarray, split_bin: jnp.ndarray,
                           default_left: jnp.ndarray, is_leaf: jnp.ndarray,
                           left_child: jnp.ndarray, right_child: jnp.ndarray,
                           leaf_value: jnp.ndarray, tree_weight: jnp.ndarray,
                           group_onehot: jnp.ndarray, bins: jnp.ndarray,
                           base: jnp.ndarray, max_depth: int,
                           missing_bin: int,
                           is_cat_split: Optional[jnp.ndarray] = None,
                           cat_words: Optional[jnp.ndarray] = None):
    """Same walk over the quantized matrix (training-data fast path). For
    categorical features local bin == category code, so the same bitmask test
    applies."""
    n = bins.shape[0]
    T, M = split_feature.shape
    pos = jnp.zeros((n, T), jnp.int32)
    tofs = (jnp.arange(T, dtype=jnp.int32) * M)[None, :]
    sf = split_feature.reshape(-1)
    sb = split_bin.reshape(-1)
    dl = default_left.reshape(-1)
    lf = is_leaf.reshape(-1)
    lc = left_child.reshape(-1)
    rc = right_child.reshape(-1)
    if cat_words is not None:
        ics = is_cat_split.reshape(-1)
        cw = cat_words.reshape(T * M, -1)
        n_words = cat_words.shape[-1]

    for _ in range(max_depth):
        gi = tofs + pos
        feat = sf[gi]
        b = jnp.take_along_axis(bins, jnp.maximum(feat, 0).astype(jnp.int32),
                                axis=1).astype(jnp.int32)
        miss = b == missing_bin
        go_right = b > sb[gi]
        if cat_words is not None:
            left = _bit_is_left(b, cw, gi, n_words)
            go_right = jnp.where(ics[gi], ~left, go_right)
        go_right = jnp.where(miss, ~dl[gi], go_right)
        child = jnp.where(go_right, rc[gi], lc[gi])
        pos = jnp.where(lf[gi], pos, child)

    leaf = leaf_value.reshape(-1)[tofs + pos] * tree_weight[None, :]
    margin = jnp.dot(leaf, group_onehot,
                     precision=jax.lax.Precision.HIGHEST) + base[None, :]
    return margin, pos


@PREDICTORS.register("tpu_predictor", "cpu_predictor", "gpu_predictor",
                     "auto")
class ForestPredictor:
    """Holds the stacked device forest and dispatches prediction variants.

    The stacked arrays pad BOTH axes to the next power of two — extra
    trees are inert single leaves with tree weight 0 (their contribution
    is exactly 0.0, so results are bit-identical) and extra node slots
    are unreachable leaves. A growing forest therefore compiles
    O(log T) distinct walk programs instead of one per tree count —
    without this, dart (whose dropped-tree margin recompute runs per
    round) and predict-after-every-round loops recompiled every round,
    and the ≤2x padded walk FLOPs are small next to a compile each."""

    def __init__(self, forest: Dict[str, np.ndarray], tree_info: np.ndarray,
                 n_groups: int, tree_weights: Optional[np.ndarray] = None) -> None:
        forest = dict(forest)
        self.max_depth = int(forest.pop("depth", 0))
        self.n_trees, self.max_nodes = forest["split_feature"].shape
        self.n_groups = n_groups
        Tp = 1 << max(self.n_trees - 1, 0).bit_length()
        Mp = 1 << max(self.max_nodes - 1, 0).bit_length()
        pad_fill = {"split_feature": -1, "left_child": -1, "right_child": -1,
                    "default_left": False, "is_leaf": True}

        def pad(k, v):
            pt, pm = Tp - v.shape[0], Mp - v.shape[1]
            if pt == 0 and pm == 0:
                return v
            width = [(0, pt), (0, pm)] + [(0, 0)] * (v.ndim - 2)
            return np.pad(v, width, constant_values=pad_fill.get(k, 0))

        padded = {k: pad(k, np.asarray(v)) for k, v in forest.items()}
        self.has_cat = "cat_words" in forest
        w = np.ones(self.n_trees) if tree_weights is None else tree_weights
        w_pad = np.pad(np.asarray(w, np.float32), (0, Tp - self.n_trees))
        onehot = np.zeros((Tp, n_groups), dtype=np.float32)
        onehot[np.arange(self.n_trees), np.asarray(tree_info)] = 1.0
        self._padded, self._w_pad, self._onehot = padded, w_pad, onehot
        self._chunk_cache = {}

    def _chunk_devs(self, n_rows: int):
        """Per-chunk device forests, chunk size adapted to the batch: the
        tree axis is split to keep n_rows * chunk under 2^24 row-tree
        pairs per walk program, which also bounds the compiled-program
        set. The bound was set where an earlier compile service crashed
        ([581k, 64] died, [581k, 16] compiled); whether the attached
        chip's compiler needs it is unverified (ROADMAP C4). Override
        with XTPU_PREDICT_TREE_CHUNK."""
        env = os.environ.get("XTPU_PREDICT_TREE_CHUNK")
        if env:
            step = max(1, int(env))
        else:
            budget = (1 << 24) // max(n_rows, 1)
            # largest pow2 <= budget, clamped to [1, TREE_CHUNK]; no floor —
            # for multi-million-row batches the budget drops below 8 and
            # forcing 8 trees/dispatch would put the walk program right
            # back past the 2^24 bound
            step = min(self.TREE_CHUNK, 1 << max(budget, 1).bit_length() - 1)
        if step not in self._chunk_cache:
            Tp = self._padded["split_feature"].shape[0]
            chunks = []
            for lo in range(0, Tp, step):
                hi = min(lo + step, Tp)
                chunks.append(dict(
                    dev={k: jnp.asarray(v[lo:hi])
                         for k, v in self._padded.items()},
                    tree_weight=jnp.asarray(self._w_pad[lo:hi]),
                    group_onehot=jnp.asarray(self._onehot[lo:hi])))
            self._chunk_cache[step] = chunks
        return self._chunk_cache[step]

    # Walk programs are additionally bounded to TREE_CHUNK trees per
    # dispatch: margins of chunks sum exactly (each tree's contribution is
    # independent), and the compiled-program set stays small AND bounded
    # in size (see _chunk_devs; unverified on the attached chip).
    TREE_CHUNK = 64

    def _cat_args(self, dev):
        if self.has_cat:
            return dev["is_cat_split"], dev["cat_words"]
        return None, None

    def _walk_chunked(self, run, base, n_rows):
        based = jnp.asarray(base, dtype=jnp.float32)
        zero = jnp.zeros_like(based)
        m_total, pos_parts = None, []
        for i, ch in enumerate(self._chunk_devs(n_rows)):
            m, pos = run(ch, based if i == 0 else zero)
            m_total = m if m_total is None else m_total + m
            pos_parts.append(pos)
        pos = (pos_parts[0] if len(pos_parts) == 1
               else jnp.concatenate(pos_parts, axis=1))
        return m_total, pos[:, : self.n_trees]

    def margin(self, X: jnp.ndarray, base: np.ndarray):
        Xd = jnp.asarray(X, dtype=jnp.float32)

        def run(ch, b):
            d = ch["dev"]
            ics, cw = self._cat_args(d)
            return _predict_margin(
                d["split_feature"], d["split_value"], d["default_left"],
                d["is_leaf"], d["left_child"], d["right_child"],
                d["leaf_value"], ch["tree_weight"], ch["group_onehot"],
                Xd, b, self.max_depth, ics, cw)

        return self._walk_chunked(run, base, int(Xd.shape[0]))

    def margin_binned(self, bins: jnp.ndarray, missing_bin: int,
                      base: np.ndarray):
        def run(ch, b):
            d = ch["dev"]
            ics, cw = self._cat_args(d)
            return _predict_margin_binned(
                d["split_feature"], d["split_bin"], d["default_left"],
                d["is_leaf"], d["left_child"], d["right_child"],
                d["leaf_value"], ch["tree_weight"], ch["group_onehot"],
                bins, b, self.max_depth, missing_bin, ics, cw)

        return self._walk_chunked(run, base, int(bins.shape[0]))
