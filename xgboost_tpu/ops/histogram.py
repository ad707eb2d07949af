"""Histogram building — the hottest op (reference ``common::BuildHist``,
``src/common/hist_util.cc:110-370``; GPU ``SharedMemHistKernel``,
``src/tree/gpu_hist/histogram.cu:129-311``).

Output layout: dense ``[n_nodes, n_features, max_nbins, 2]`` (g, h) sums over the
uniform padded bin layout of data/binned.py. Two XLA strategies:

- ``segment``: one flattened ``segment_sum`` over (row, feature) pairs — the
  scatter-add formulation; efficient on CPU, and what the GPU reference does with
  atomics.
- ``onehot``: histogram-as-matmul — rows are tiled into blocks; per block a
  position/gradient matrix ``P [rows, 2*n_nodes]`` is contracted against
  per-feature one-hot bin encodings on the MXU. No atomics, deterministic,
  MXU-shaped: this is the TPU-native formulation (a Pallas-fused variant lives in
  ops/pallas/).

Unlike the GPU reference there is no ``GradientQuantiser`` fixed-point trick
(``src/tree/gpu_hist/histogram.cu:55-100``): XLA reductions are deterministic, so
f32 accumulation already gives run-to-run reproducible histograms.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.metrics import count_fused_boundary, count_refine_remap
from ..obs.trace import stage


def unpack_u4(packed: jnp.ndarray, n_features: int) -> jnp.ndarray:
    """Decode a u4-packed bin page (compressed page transport,
    ``XTPU_PAGE_PACK``): byte ``[r, w]`` holds feature ``2w`` in its low
    nibble and feature ``2w+1`` in its high nibble, so a ``[p, ceil(F/2)]``
    uint8 page expands to the original ``[p, F]`` bin ids. Pure integer
    unpack — bit-exact with the unpacked transport — shared by every lax
    consumer (paged kernel bodies, paged prediction, resident collapse);
    the Pallas int8 kernel carries its own in-VMEM decode
    (``build_hist_pallas(packed_u4=...)``) so the packed page is the only
    HBM-resident copy on that path."""
    lo = packed & jnp.uint8(0x0F)
    hi = packed >> jnp.uint8(4)
    out = jnp.stack([lo, hi], axis=2).reshape(packed.shape[0], -1)
    return out[:, :n_features]


def build_hist_segment(bins: jnp.ndarray, gpair: jnp.ndarray, rel_pos: jnp.ndarray,
                       n_nodes: int, max_nbins: int) -> jnp.ndarray:
    """Scatter-add histogram.

    bins: [n, F] local bin ids (any int dtype), missing at max_nbins-1
    gpair: [n, 2] f32
    rel_pos: [n] int32 in [0, n_nodes]; n_nodes means "inactive row" (dumped)
    -> [n_nodes, F, max_nbins, 2] f32
    """
    n, F = bins.shape
    stride = F * max_nbins
    seg = (rel_pos.astype(jnp.int32)[:, None] * stride
           + jnp.arange(F, dtype=jnp.int32)[None, :] * max_nbins
           + bins.astype(jnp.int32))
    data = jnp.broadcast_to(gpair[:, None, :], (n, F, 2)).reshape(-1, 2)
    hist = jax.ops.segment_sum(data, seg.reshape(-1),
                               num_segments=(n_nodes + 1) * stride)
    return hist[: n_nodes * stride].reshape(n_nodes, F, max_nbins, 2)


def build_hist_onehot(bins: jnp.ndarray, gpair: jnp.ndarray, rel_pos: jnp.ndarray,
                      n_nodes: int, max_nbins: int,
                      block_rows: int = 1 << 16) -> jnp.ndarray:
    """Matmul histogram: for each row block, P[r, node*2+k] = gpair[r, k] when
    rel_pos[r] == node, then per feature hist_f += onehot(bins_f)^T @ P.

    Rows with rel_pos == n_nodes one-hot to all-zeros and vanish for free.
    -> [n_nodes, F, max_nbins, 2] f32
    """
    n, F = bins.shape
    pad = (-n) % block_rows
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        gpair = jnp.pad(gpair, ((0, pad), (0, 0)))
        rel_pos = jnp.pad(rel_pos, (0, pad), constant_values=n_nodes)
    nb = (n + pad) // block_rows
    bins_b = bins.reshape(nb, block_rows, F)
    gpair_b = gpair.reshape(nb, block_rows, 2)
    pos_b = rel_pos.reshape(nb, block_rows)

    node_ids = jnp.arange(n_nodes, dtype=jnp.int32)
    bin_ids = jnp.arange(max_nbins, dtype=jnp.int32)

    def block_body(carry, xs):
        bins_blk, gpair_blk, pos_blk = xs
        # P: [rows, n_nodes*2]
        pos_oh = (pos_blk[:, None] == node_ids[None, :]).astype(jnp.float32)
        P = (pos_oh[:, :, None] * gpair_blk[:, None, :]).reshape(block_rows,
                                                                 n_nodes * 2)

        def feat_body(_, f):
            oh = (bins_blk[:, f][:, None] == bin_ids[None, :]).astype(jnp.float32)
            return None, jnp.dot(oh.T, P, precision=jax.lax.Precision.HIGHEST)

        _, per_feat = jax.lax.scan(feat_body, None, jnp.arange(F))
        # per_feat: [F, max_nbins, n_nodes*2]
        return carry + per_feat, None

    init = jnp.zeros((F, max_nbins, n_nodes * 2), dtype=jnp.float32)
    # under a row-split shard_map the rows vary over the mesh axis, and a
    # scan's carry must come in as it goes out
    vma = tuple(jax.typeof(gpair_b).vma)
    if vma:
        init = jax.lax.pcast(init, vma, to="varying")
    acc, _ = jax.lax.scan(block_body, init, (bins_b, gpair_b, pos_b))
    # [F, B, n_nodes, 2] -> [n_nodes, F, B, 2]
    return acc.reshape(F, max_nbins, n_nodes, 2).transpose(2, 0, 1, 3)


def _resolve_method(method: str, n_nodes: int) -> str:
    """The build ``method`` runs as here: "auto" is the XLA ``segment``
    build on the CPU, the fused Pallas kernel on a TPU up to 128 nodes (it
    accumulates ``[F_blk, max_nbins, 2 * n_nodes]`` blocks in VMEM; past
    that, at depth > 7, the XLA formulation rather than shrinking blocks),
    and the XLA ``onehot`` build elsewhere (Pallas specs here are
    TPU-only)."""
    if method != "auto":
        return method
    backend = jax.default_backend()
    if backend == "cpu":
        return "segment"
    return "pallas" if backend == "tpu" and n_nodes <= 128 else "onehot"


def refine_remap_where(method: str, n_nodes: int) -> str:
    """Where a refine build over ``n_nodes`` remaps the raw ids into the
    window (``build_hist(window=...)``): ``kernel``, in VMEM inside the
    int8x2 Pallas kernel, or ``xla``, by ``_refine_ids`` ahead of any other
    build."""
    resolved = _resolve_method(method, n_nodes)
    return "kernel" if resolved in ("pallas", "pallas:int8x2") else "xla"


def _refine_ids(bins: jnp.ndarray, rel_pos: jnp.ndarray,
                window: jnp.ndarray, missing_bin: int) -> jnp.ndarray:
    """``[n, F]`` refine slots (``ops/split.py refine_bin_ids``) of every
    row at its node's window: the per-row window by one
    ``[n, N+1] @ [N+1, F]`` MXU matmul (rows outside the level meet the
    zero pad row; their build contribution is dropped by rel == N)."""
    from .split import refine_bin_ids

    N, F = window.shape
    span_pad = jnp.concatenate([window.astype(jnp.float32),
                                jnp.zeros((1, F), jnp.float32)])  # [N+1, F]
    oh_rel = (rel_pos[:, None] == jnp.arange(
        N + 1, dtype=jnp.int32)[None, :]).astype(jnp.float32)    # [n, N+1]
    c_row = jax.lax.dot_general(oh_rel, span_pad, (((1,), (0,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST)
    return refine_bin_ids(bins.astype(jnp.int32), c_row.astype(jnp.int32),
                          missing_bin)


def build_hist(bins: jnp.ndarray, gpair: jnp.ndarray, rel_pos: jnp.ndarray,
               n_nodes: int, max_nbins: int, method: str = "auto",
               block_rows: int = 1 << 16,
               bins_t: jnp.ndarray = None, axis_name=None,
               packed_u4: int = 0, window: jnp.ndarray = None,
               missing_bin: int = None, cat_cols: tuple = None,
               cat_slots: int = 0):
    """``[n_nodes, F, max_nbins, 2]`` (g, h) sums of ``bins`` ([n, F]; its
    transpose ``bins_t``, where the caller holds one, feeds the Pallas
    kernels, padded to their row blocks or not) by ``method``.

    ``window`` ([n_nodes, F] window starts, ``ops/split.py
    choose_refine_window``) and ``missing_bin`` make it the refine build of
    the two-level search over the RAW ids: the histogram of ``refine_bin_ids``'
    slots at each row's node's window, ``max_nbins`` (WINDOW + 4) of them.
    The int8x2 Pallas kernel remaps in VMEM; every other build remaps by
    ``_refine_ids`` first. ``xtpu_refine_remap_total{where}`` counts which
    (``refine_remap_where``), once a call: a call inside a traced program
    is one a trace.

    ``cat_cols`` (static column indices) and ``cat_slots`` make the refine
    build also return those columns' full ``[n_nodes, len(cat_cols),
    cat_slots, 2]`` histograms, one slot a category: in the same sweep of
    the tile where the int8x2 kernel runs (``build_hist_pallas(cat_rows=
    ...)``), by a build of their own elsewhere."""
    if window is not None:
        if packed_u4:
            raise ValueError("the u4 page transport never refines")
        count_refine_remap(refine_remap_where(method, n_nodes))
    return _build_hist(bins, gpair, rel_pos, n_nodes, max_nbins, method,
                       block_rows, bins_t, axis_name, packed_u4, window,
                       missing_bin, cat_cols, cat_slots)


@partial(jax.jit, static_argnames=("n_nodes", "max_nbins", "method",
                                   "block_rows", "axis_name", "packed_u4",
                                   "missing_bin", "cat_cols", "cat_slots"))
def _build_hist(bins, gpair, rel_pos, n_nodes, max_nbins, method,
                block_rows, bins_t, axis_name, packed_u4, window,
                missing_bin, cat_cols=None, cat_slots=0):
    if packed_u4:
        # ``bins`` is a u4-packed [n, ceil(F/2)] page (packed_u4 = logical
        # F). The Pallas path decodes nibbles in-VMEM inside the kernel's
        # feature loop; every lax formulation decodes in-trace here (XLA
        # fuses the unpack into the consumer's read).
        if method.startswith("pallas") or (
                method == "auto" and jax.default_backend() == "tpu"
                and n_nodes <= 128):
            from .pallas.histogram import build_hist_pallas

            precision = method.split(":", 1)[1] if ":" in method else "int8x2"
            return build_hist_pallas(
                bins.T, gpair, rel_pos, n_nodes, max_nbins,
                precision=precision, axis_name=axis_name,
                packed_u4=packed_u4)
        bins = unpack_u4(bins, packed_u4)
        bins_t = None
    if method in ("coarse", "fused"):
        raise ValueError(
            f"hist_method='{method}' runs inside the depthwise scalar "
            "growers only (tree/grow.py resident, tree/paged.py external "
            "memory); this code path (lossguide / vector-leaf / vertical) "
            "does not support it")
    if window is not None:
        if refine_remap_where(method, n_nodes) == "kernel":
            from .pallas.histogram import build_hist_pallas

            return build_hist_pallas(
                bins.T if bins_t is None else bins_t, gpair, rel_pos,
                n_nodes, max_nbins, axis_name=axis_name, window=window,
                missing_bin=missing_bin, cat_rows=cat_cols or (),
                cat_slots=cat_slots)
        if cat_cols:
            return (_build_hist(
                _refine_ids(bins, rel_pos, window, missing_bin), gpair,
                rel_pos, n_nodes, max_nbins, method, block_rows, None,
                axis_name, 0, None, None),
                _build_hist(
                    bins[:, np.asarray(cat_cols)], gpair, rel_pos, n_nodes,
                    cat_slots, method, block_rows, None, axis_name, 0, None,
                    None))
        bins = _refine_ids(bins, rel_pos, window, missing_bin)
        bins_t = None
    method = _resolve_method(method, n_nodes)
    if method.startswith("pallas"):
        from .pallas.histogram import build_hist_pallas

        # default is the 15-bit fixed-point int8 MXU path (the reference
        # GradientQuantiser idea, src/tree/gpu_hist/histogram.cu:55-100):
        # fastest per level and deterministic; bf16x2 is the higher-precision
        # fallback selectable via "pallas:bf16x2"
        precision = method.split(":", 1)[1] if ":" in method else "int8x2"
        if bins_t is None:
            bins_t = bins.T
        return build_hist_pallas(bins_t, gpair, rel_pos, n_nodes, max_nbins,
                                 precision=precision, axis_name=axis_name)
    if method == "segment":
        return build_hist_segment(bins, gpair, rel_pos, n_nodes, max_nbins)
    if method == "onehot":
        return build_hist_onehot(bins, gpair, rel_pos, n_nodes, max_nbins,
                                 block_rows=min(block_rows, max(bins.shape[0], 8)))
    raise ValueError(f"unknown hist method {method}")


def build_hist_multi(bins: jnp.ndarray, gpair3: jnp.ndarray,
                     rel_pos: jnp.ndarray, n_nodes: int, max_nbins: int,
                     method: str = "auto",
                     bins_t: jnp.ndarray = None) -> jnp.ndarray:
    """K-target histogram [n_nodes, F, max_nbins, K, 2] from gpair [n, K, 2].

    Loops single-target builds: a fused all-components kernel pass was
    measured 2x SLOWER on TPU (the widened output spills past one MXU
    column tile — see the note in ops/pallas/histogram.py), so per-target
    passes are the fast path."""
    K = gpair3.shape[1]
    return jnp.stack(
        [build_hist(bins, gpair3[:, k], rel_pos, n_nodes, max_nbins,
                    method=method, bins_t=bins_t) for k in range(K)],
        axis=3)


# ---- cross-level fused sweep (hist_method="fused") -------------------------
# The two-level coarse->refine scheme has a hard dependency chain
# (coarse_L -> window_L -> refine_L -> splits_L -> positions_{L+1} ->
# coarse_{L+1}), so its bit-exact floor is TWO data sweeps per level:
# {refine_L} and {advance past splits_L + coarse_{L+1}}. The unfused
# resident path pays THREE streams (a [n, F] u8 coarse-id copy, the bin
# matrix for the refine, and a persistent 4-byte [n, F] f32 copy for the
# advance matmul); this op collapses the advance and the next level's
# coarse accumulation into ONE read of the bin tile — the same fusion the
# paged tier's adv_hist body has used since round 5 — and computes both
# the f32 advance operand and the coarse ids in-trace, so neither copy is
# ever materialised in HBM.

@stage("advance")
def _advance_below(bins: jnp.ndarray, positions: jnp.ndarray, prev: dict,
                   missing_bin: int, decision_axis) -> jnp.ndarray:
    """The row decision of a boundary sweep: advance rows below the
    PREVIOUS level's decoded splits (``prev``: ``fused_advance_coarse``
    docstring). Pure integer routing."""
    from .partition import advance_positions_level, update_positions

    lo_prev, nl_prev = prev["lo"], prev["n_level"]
    is_cat, words = prev.get("cat") or (None, None)
    if prev["kind"] == "dense":
        feat, thr, dleft, cs = prev["arrs"]
        rel_prev = jnp.where(
            (positions >= lo_prev) & (positions < lo_prev + nl_prev),
            positions - lo_prev, nl_prev).astype(jnp.int32)
        # f32 operand computed IN the trace: XLA fuses the upcast into the
        # matmul read — no materialised [n, F] f32 copy
        return advance_positions_level(
            bins.astype(jnp.float32), positions, rel_prev, feat, thr,
            dleft, cs, missing_bin, is_cat=is_cat, cat_words=words,
            decision_axis=decision_axis)
    sf, sb, dl, isf = prev["arrs"]
    return update_positions(
        bins, positions, sf, sb, dl, isf, missing_bin,
        is_cat_split=is_cat, cat_words=words,
        decision_axis=decision_axis,
        feat_offset=prev.get("feat_offset"))


def fused_advance_coarse(bins: jnp.ndarray, gpair: jnp.ndarray,
                         positions: jnp.ndarray, prev: dict, lo: int,
                         n_level: int, missing_bin: int, *,
                         bins_t: jnp.ndarray = None, method: str = "auto",
                         axis_name=None, decision_axis=None,
                         interpret: bool = False):
    """One sweep at the level boundary: advance rows below the PREVIOUS
    level's decoded splits, then accumulate the NEW level's coarse
    histogram from the same tile read.

    ``prev``: the previous level's split payload — ``kind`` ("dense" for
    the matmul advance over per-level vectors, "walk" for the deep-level
    per-row gather walk over full tree arrays), ``lo``, ``n_level``,
    ``arrs``, and optionally ``feat_offset`` (column split walk) and, on a
    matrix with categorical columns, ``cat`` (the categorical flags and
    left-set words, over the same nodes as ``arrs``) and ``cat_cols``
    (those columns, static) — the same convention as ``tree/paged.py``.
    Returns
    ``(new_positions, coarse_hist [n_level, F, COARSE_B, 2])``.

    Bit-exactness with the two-pass coarse path: the advance is pure
    integer routing (identical ops to ``advance_positions_level`` /
    ``update_positions``), and the coarse build runs the same kernel on
    the same quantities — the fused Pallas variant keeps the unfused
    kernel's block shapes and accumulation order, so the histograms are
    bit-identical, level by level.
    """
    from .split import COARSE_B, coarse_bin_ids

    kind = prev["kind"]
    lo_prev, nl_prev = prev["lo"], prev["n_level"]
    # The single-HBM-read Pallas kernel: TPU, dense advance, no cross-shard
    # decision exchange (col split routes through the XLA body's psum), and
    # the whole-F [F, COARSE_B, 2N] accumulator must fit the VMEM budget
    # the unfused int8x2 kernel uses — outside these bounds the XLA body
    # below is the fused path (one jit: XLA still elides the f32/coarse-id
    # copies, it just cannot guarantee the single tile read).
    F = bins.shape[1]
    use_pallas = (jax.default_backend() == "tpu"
                  and method in ("auto", "pallas")
                  and decision_axis is None and kind == "dense"
                  and nl_prev <= 64 and n_level <= 128
                  and F * COARSE_B * 2 * n_level * 4 <= 8 * 2 ** 20)
    count_fused_boundary("kernel" if use_pallas or interpret else "xla")
    if use_pallas or interpret:
        from .pallas.histogram import fused_advance_coarse_pallas

        feat, thr, dleft, cs = prev["arrs"]
        if bins_t is None:
            bins_t = bins.T
        return fused_advance_coarse_pallas(
            bins_t, gpair, positions, feat, thr, dleft, cs, prev.get("cat"),
            lo_prev=lo_prev, n_prev=nl_prev, lo=lo, n_level=n_level,
            missing_bin=missing_bin, axis_name=axis_name,
            interpret=interpret, cat_cols=prev.get("cat_cols", ()))
    positions = _advance_below(bins, positions, prev, missing_bin,
                               decision_axis)
    rel = jnp.where((positions >= lo) & (positions < lo + n_level),
                    positions - lo, n_level).astype(jnp.int32)
    cb = coarse_bin_ids(bins.astype(jnp.int32), missing_bin)
    cb_t = (None if bins_t is None
            else coarse_bin_ids(bins_t.astype(jnp.int32), missing_bin))
    hist = build_hist(cb, gpair, rel, n_level, COARSE_B, method=method,
                      bins_t=cb_t, axis_name=axis_name)
    return positions, hist


def advance_leaf(bins: jnp.ndarray, positions: jnp.ndarray, prev: dict,
                 leaf_value: jnp.ndarray, missing_bin: int, *,
                 bins_t: jnp.ndarray = None, decision_axis=None,
                 interpret: bool = False):
    """The epilogue of a fused grow program: advance rows below
    the LAST evaluated level's splits (``prev``: ``fused_advance_coarse``
    docstring), with no coarse pass left to fuse with. Returns
    ``(new_positions, delta, kind)``.

    ``kind`` says what ran. ``"kernel"``: the level is past the dense
    matmul advance (``prev["kind"] == "walk"``) and one Mosaic sweep of
    the bin tile routes the rows AND writes ``delta =
    leaf_value[new_positions]`` (``ops/pallas/histogram.py
    advance_leaf_pallas``), bit for bit what the walk and the leaf gather
    give: on a TPU, with no cross-shard decision exchange (column split
    keeps the walk and its psum), up to ``ADVANCE_LEAF_MAX_NODES`` nodes.
    ``"dense"`` / ``"walk"``: the XLA advance of ``_advance_below``;
    ``delta`` is None and the caller looks the leaves up."""
    from .pallas.histogram import ADVANCE_LEAF_MAX_NODES, advance_leaf_pallas

    lo, n_level = prev["lo"], prev["n_level"]
    use_pallas = ((interpret or jax.default_backend() == "tpu")
                  and decision_axis is None and prev["kind"] == "walk"
                  and n_level <= ADVANCE_LEAF_MAX_NODES)
    if not use_pallas:
        return (_advance_below(bins, positions, prev, missing_bin,
                               decision_axis), None, prev["kind"])
    with stage("advance"):
        # the level's rows of the walk's whole-heap arrays
        feat, thr, dleft, cs = (a[lo:lo + n_level] for a in prev["arrs"])
        cat = prev.get("cat")
        if cat is not None:
            cat = tuple(a[lo:lo + n_level] for a in cat)
        new_positions, delta = advance_leaf_pallas(
            bins.T if bins_t is None else bins_t, positions, feat, thr,
            dleft, cs, leaf_value, cat, n_prev=n_level,
            missing_bin=missing_bin, interpret=interpret,
            cat_cols=prev.get("cat_cols", ()))
    return new_positions, delta, "kernel"


# ---- the eval walk, from the device heap -----------------------------------
# A round's new tree is still on the device as its heap arrays when the
# held-out rows want its margin increment. The rows are routed down the
# heap by the two gather-free advances the training rows take in ``_grow``:
# the dense matmul advance at the narrow levels, one kernel sweep at the
# wide ones and at the last, which also writes each row's leaf value. No
# per-row gather (12-16M elements a second on a v5e: 211 ms a round for
# 500k rows at depth 8, PERF.md section 6, PR 32), and no pull of the tree
# to the host first.

# the heap arrays the walk reads, of ``tree/grow.py GrownTree``
HEAP_WALK_FIELDS = ("split_feature", "split_bin", "default_left", "is_leaf",
                    "leaf_value")


def heap_walk_takes(n_features: int, missing_bin: int, max_depth: int,
                    interpret: bool = False) -> bool:
    """Whether ``heap_walk_delta`` runs at this shape on this backend: a
    TPU (or the Pallas interpreter), a last level ``advance_leaf_pallas``
    takes, and a feature count and bin range its packed split word holds."""
    from .pallas.histogram import ADVANCE_LEAF_MAX_NODES

    return ((interpret or jax.default_backend() == "tpu")
            and 1 <= max_depth
            and 2 ** (max_depth - 1) <= ADVANCE_LEAF_MAX_NODES
            and n_features <= 0xFFFF and missing_bin <= 0xFFF)


def heap_walk_delta(grown, bins: jnp.ndarray, missing_bin: int,
                    max_depth: int, *, interpret: bool = False
                    ) -> jnp.ndarray:
    """Margin increment of one depthwise, numeric tree over binned rows,
    from its heap arrays: ``leaf_value[final heap position]`` per row,
    f32 ``[n]``, bit for bit the table entry (``advance_leaf_pallas``).

    ``grown``: a mapping with the ``HEAP_WALK_FIELDS`` of a ``GrownTree``
    over a heap of ``2^(max_depth+1) - 1`` nodes (the dict a pending tree
    holds, or the tuple's ``_asdict()``); ``bins``: ``[n, F]`` ids binned against the cuts the tree
    was grown with. Level-synchronous from the root: every level of at
    most ``DENSE_LEVEL_MAX`` nodes but the last takes
    ``advance_positions_level``, the others ``advance_leaf_pallas``, whose
    last call leaves the delta, the rows that stopped at a shallower leaf
    included. Callers hold the shape to ``heap_walk_takes``."""
    from ..tree.grow import DENSE_LEVEL_MAX
    from .pallas.histogram import advance_leaf_pallas
    from .partition import advance_positions_level

    splits = (grown["split_feature"], grown["split_bin"],
              grown["default_left"], ~grown["is_leaf"])
    with stage("margin"):
        positions = jnp.zeros((bins.shape[0],), jnp.int32)
        bins_t = bins.T
        # f32 operand computed in the trace, as ``_advance_below``
        bins_f32 = bins.astype(jnp.float32)
        for depth in range(max_depth):
            lo, n_level = 2 ** depth - 1, 2 ** depth
            level = tuple(a[lo:lo + n_level] for a in splits)
            if depth < max_depth - 1 and n_level <= DENSE_LEVEL_MAX:
                rel = jnp.where(positions >= lo, positions - lo, n_level)
                positions = advance_positions_level(
                    bins_f32, positions, rel, *level, missing_bin)
            else:
                with stage("advance"):
                    positions, delta = advance_leaf_pallas(
                        bins_t, positions, *level, grown["leaf_value"],
                        n_prev=n_level, missing_bin=missing_bin,
                        interpret=interpret)
    return delta

