"""Row partitioning — static-shape position updates under jit.

The reference partitions row index ranges in place (CPU ``CommonRowPartitioner``,
``src/tree/common_row_partitioner.h:86``; GPU ``RowPartitioner`` scatter,
``src/tree/gpu_hist/row_partitioner.cuh:196``). Dynamic-size row sets don't exist
under XLA, so the TPU design keeps a dense ``positions [n_rows]`` array of heap
node ids (root = 0, children of i = 2i+1 / 2i+2) and rewrites it with gathers —
O(n) per depth, embarrassingly parallel, no sorting needed for training.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..obs.trace import stage


def cat_goes_right(b: jnp.ndarray, words: jnp.ndarray) -> jnp.ndarray:
    """b: [n] bin/category ids; words: [n, W] uint32 left-set bitmasks ->
    True when the category is NOT in the left set."""
    W = words.shape[1]
    widx = jnp.clip(b // 32, 0, W - 1)
    word = jnp.take_along_axis(words, widx[:, None].astype(jnp.int32),
                               axis=1)[:, 0]
    bit = (word >> (b % 32).astype(jnp.uint32)) & jnp.uint32(1)
    return bit == 0


@stage("advance")
def advance_positions_level(bins_f32: jnp.ndarray, positions: jnp.ndarray,
                            rel: jnp.ndarray,
                            feat: jnp.ndarray, thr: jnp.ndarray,
                            dleft: jnp.ndarray, can_split: jnp.ndarray,
                            missing_bin: int,
                            is_cat: Optional[jnp.ndarray] = None,
                            cat_words: Optional[jnp.ndarray] = None,
                            decision_axis: Optional[str] = None
                            ) -> jnp.ndarray:
    """Advance rows below one freshly evaluated level — gather-free.

    TPU-native replacement for the per-row gather walk (reference
    ``CommonRowPartitioner::UpdatePosition``): with N = 2**depth level nodes,
    the bin of every node's split feature is fetched for all rows with ONE
    ``[n, F] @ [F, N]`` one-hot matmul on the MXU, the routing decision is
    computed densely for all (row, node) pairs on the VPU, and each row picks
    its node's decision via its position one-hot. No data-dependent gathers,
    which XLA:TPU would otherwise serialise.

    bins_f32: [n, F] bin ids as f32 (exact: ids < 2^24)
    rel: [n] int32 position relative to level start (N = "not in level")
    feat/thr/dleft/can_split: [N] per-level split decisions
    -> new positions [n]
    """
    n, F = bins_f32.shape
    N = feat.shape[0]
    oh_feat = (feat[:, None] == jnp.arange(F, dtype=jnp.int32)[None, :]
               ).astype(jnp.float32)                       # [N, F]
    sel = jax.lax.dot_general(
        bins_f32, oh_feat, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)               # [n, N]
    sel_i = sel.astype(jnp.int32)
    missing = sel_i == missing_bin
    go_right = sel_i > thr[None, :]                        # [n, N]
    if is_cat is not None:
        # a row's word of its node's left set: one select a word, in a
        # loop, not unrolled (W is 11 at 352 slots, 16 at the two-level
        # schedules' 512: tree/grow.py CAT_SLOTS_MAX, and the one-pass
        # schedules take any count)
        W = cat_words.shape[1]
        widx = jnp.clip(sel_i // 32, 0, W - 1)             # [n, N]

        def one_word(w, word):
            return jnp.where(widx == w, cat_words[None, :, w], word)

        # the first word starts the carry: it varies over a mesh axis as
        # the rows do
        word = jax.lax.fori_loop(1, W, one_word,
                                 one_word(0, jnp.uint32(0)))
        bit = (word >> (sel_i % 32).astype(jnp.uint32)) & jnp.uint32(1)
        go_right = jnp.where(is_cat[None, :], bit == 0, go_right)
    go_right = jnp.where(missing, ~dleft[None, :], go_right)
    rel_oh = rel[:, None] == jnp.arange(N, dtype=jnp.int32)[None, :]
    gr = jnp.any(rel_oh & go_right, axis=1)
    if decision_axis is not None:
        # column split: each node's decision is known only to the shard
        # owning its split feature (others contribute 0) — one psum fans the
        # boolean decisions out to every shard
        gr = jax.lax.psum(gr.astype(jnp.int32), decision_axis) > 0
    splitting = jnp.any(rel_oh & can_split[None, :], axis=1)
    return jnp.where(splitting,
                     2 * positions + 1 + gr.astype(positions.dtype),
                     positions)


@stage("advance")
def update_positions(bins: jnp.ndarray, positions: jnp.ndarray,
                     split_feature: jnp.ndarray, split_bin: jnp.ndarray,
                     default_left: jnp.ndarray, is_split: jnp.ndarray,
                     missing_bin: int,
                     is_cat_split: Optional[jnp.ndarray] = None,
                     cat_words: Optional[jnp.ndarray] = None,
                     decision_axis: Optional[str] = None,
                     feat_offset: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Advance rows one level down the tree.

    bins: [n, F] local bin ids; positions: [n] current heap node id;
    split_*: [max_nodes] per-node split info; is_split: [max_nodes] bool
    (True where the node was just expanded). Rows at non-split nodes stay put.
    Categorical nodes route by left-set bitmask membership instead of the
    threshold comparison (reference ``CategoricalSplitMatrix`` decision).

    Column split (``decision_axis`` + ``feat_offset``): ``split_feature``
    carries GLOBAL feature ids while ``bins`` holds this shard's feature
    slice starting at ``feat_offset``. Each shard computes decisions for
    the nodes whose split feature it owns; one boolean psum fans them out
    (the reference partition-bitvector broadcast,
    ``src/tree/common_row_partitioner.h``) — the same protocol as
    ``advance_positions_level``'s dense form, expressed over the per-row
    gather walk so deep levels stay O(n) in memory.
    """
    feat = split_feature[positions]
    thr = split_bin[positions]
    dleft = default_left[positions]
    splitting = is_split[positions]
    if decision_axis is not None:
        local_feat = feat - feat_offset
        owned = (local_feat >= 0) & (local_feat < bins.shape[1])
        safe_feat = jnp.clip(local_feat, 0, bins.shape[1] - 1)
    else:
        owned = None
        safe_feat = jnp.maximum(feat, 0)
    b = jnp.take_along_axis(bins, safe_feat[:, None].astype(jnp.int32),
                            axis=1)[:, 0].astype(jnp.int32)
    missing = b == missing_bin
    go_right = b > thr
    if is_cat_split is not None:
        node_words = cat_words[positions]                 # [n, W]
        go_right = jnp.where(is_cat_split[positions],
                             cat_goes_right(b, node_words), go_right)
    go_right = jnp.where(missing, ~dleft, go_right)
    if decision_axis is not None:
        contrib = owned & splitting & go_right
        go_right = jax.lax.psum(contrib.astype(jnp.int32),
                                decision_axis) > 0
    return jnp.where(splitting,
                     2 * positions + 1 + go_right.astype(positions.dtype),
                     positions)
