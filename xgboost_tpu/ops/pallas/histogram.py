"""Pallas TPU kernel for histogram building — the framework's hottest op.

Reference counterpart: CUDA ``SharedMemHistKernel`` (shared-memory int64
atomics, ``src/tree/gpu_hist/histogram.cu:129-311``). TPUs have no fast
scatter, so the kernel keeps the histogram-as-matmul formulation but fuses
everything XLA would materialise:

- the bin one-hot is built directly in its transposed (MXU-ready) ``[B, R]``
  layout in VMEM from a ``[F, n]`` bin matrix and never touches HBM. The
  default int8x2 kernel interleaves build and contraction a DOT at a time,
  so Mosaic pipelines the VPU one-hot of dot d+1 against the MXU dot d
  (staging a whole ``[Fb*B, R]`` block for one big matmul, still used by
  the f32/bf16 variants, serialises the two units and measured 1.7x slower
  at B = 256). How many features a dot contracts follows from the width
  alone (``_dot_features``): at B = 256 (the one-pass schedules) one
  feature's one-hot fills the MXU's passes and a dot is a feature, as
  measured at 1M x 28 x 256; at the two-level search's 20 and 36 slots a
  feature's rows leave most of each latched ``PT4`` tile unused, and eight
  features' one-hots, stacked without padding, share it (PERF.md section 6,
  PR 37: 0.39 to 0.81 of the dot a feature's time, the same bits);
- the node-scatter matrix ``P^T [2N, R]`` (rows scattered to their tree node,
  times (g, h)) is built once per row block and shared by every feature;
- the accumulator (``[Fb, B, 2N]``; ``[Fb * B, 2N]`` under a stacked dot,
  whose features are one slice of rows) lives in VMEM across the row-block
  grid axis and only hits HBM once per feature block;
- a body runs its features in groups of at most ``FEATURE_GROUP`` (G): the
  per-feature steps of a group are unrolled in Python, and a matrix wider than
  G takes more groups (feature blocks on the grid, or a loop inside the
  kernel), not a longer body.

All vector inputs are lane-major (``[2, n]`` gpair, ``[1, n]`` positions) so no
VMEM is wasted padding 1- or 2-wide lanes to 128.

Precision ladder (replaces the CUDA ``GradientQuantiser`` fixed-point trick,
``src/tree/gpu_hist/histogram.cu:55-100``):

- ``"f32"``   — full f32 MXU passes (``Precision.HIGHEST``).
- ``"int8x2"``— the GradientQuantiser itself, TPU-style: (g, h) quantised to
  15-bit fixed point with a global per-component scale, split into two int8
  byte planes, and contracted in two int8 MXU passes (v5e: 2x the bf16 rate)
  with **exact** int32 accumulation. Deterministic and order-independent —
  the same property the reference's fixed-point atomics buy — with relative
  error bounded by 2^-15 of max|g| on each element.
- ``"bf16x2"``— split (g, h) into bf16 hi + bf16 lo, two MXU passes with f32
  accumulate; ~16 mantissa bits on the inputs at 2x the f32 matmul rate. The
  one-hot operand is exact in bf16, so all error comes from the gradient split.
- ``"bf16"``  — single bf16 pass; fastest, ~8 mantissa bits on gradients.

Every variant accumulates in f32/int32 inside the MXU, so histograms remain
deterministic run-to-run. NOTE: XLA:CPU emulates bf16 dots with bf16
accumulation, so the bf16 variants are only accurate on real TPUs; tests on
CPU should use ``precision="f32"`` or ``"int8x2"``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...obs.metrics import (count_hist_dot, count_hist_onehot,
                            note_hist_body_features, note_kernel_vmem)
from ...obs.trace import mesh_scope, stage

# The most features a kernel body unrolls. A body's per-feature steps are
# unrolled in Python so that Mosaic overlaps the one-hot of feature f+1 with
# the dot of feature f, and tracing, lowering and Mosaic's compile are all
# linear in what is unrolled: a depth-8 round program holds 17 such kernels,
# and at F = 968 with whole-F bodies it traced and lowered for 215 s and
# compiled for 152 s more (PERF.md section 6, PR 36). Wider matrices run in
# groups of at most this many features: ``build_hist_pallas`` caps its
# feature block on the grid, ``_make_fused_kernel``, which needs the whole-F
# tile for its advance, loops over groups inside the kernel.
FEATURE_GROUP = 256

# The scoped VMEM a Mosaic kernel may use unless it states a limit of its
# own (``vmem_limit_bytes``): the compiler's default on a v5e, of the core's
# 128 MiB.
SCOPED_VMEM = 16 * 2 ** 20


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# rows a grid step of the kernels over a bin tile
ROW_BLOCK = 2048


def row_blocks(n: int, block_rows: int = ROW_BLOCK) -> tuple:
    """(R, n_pad): the row block of a kernel over ``n`` rows (whole 128-row
    tiles, at most ``block_rows``) and the rows its grid covers."""
    R = min(block_rows, max(_round_up(n, 128), 128))
    return R, _round_up(max(n, R), R)


def fit_rows(x_t: jnp.ndarray, width: int) -> jnp.ndarray:
    """``x_t`` ([rows, m]) padded with zeros, or cut, to ``width``
    columns: nothing where it has them. A round program pads its bin tile
    to the row blocks once (``tree/grow.py``), and every kernel of the
    program takes that tile as it is; a kernel pads a narrower tile
    itself, per call."""
    have = x_t.shape[1]
    if have < width:
        return jnp.pad(x_t, ((0, 0), (0, width - have)))
    return x_t[:, :width] if have > width else x_t


def _vmem_params(kernel: str, limit: Optional[int]):
    """The compiler parameters of a kernel that states ``limit`` bytes of
    scoped VMEM (None where it keeps the default), noted in the gauge
    ``xtpu_kernel_vmem_bytes``."""
    note_kernel_vmem(kernel, SCOPED_VMEM if limit is None else limit)
    return None if limit is None else pltpu.CompilerParams(
        vmem_limit_bytes=limit)


def _feature_groups(F: int) -> tuple:
    """(groups, features a group): the fewest groups of at most
    ``FEATURE_GROUP`` features, evenly sized, so that the padding past F is
    under two features a group. One group of F features while F fits; more
    groups hold an even number each, so that a group's first row in a
    ``[F * B, 2N]`` accumulator (B a multiple of 4) is a whole sublane
    tile's."""
    groups = max(-(-F // FEATURE_GROUP), 1)
    group = -(-F // groups)
    return groups, group if groups == 1 else _round_up(group, 2)


def _feature_block(F: int, cap: int, step: int = 8) -> int:
    """Feature block of a grid over F features, at most ``cap`` a block: the
    whole F when it fits (no padding features burn one-hot builds, and a
    block spec allows any first dimension equal to the array's); otherwise
    the multiple of ``step``, no smaller than a quarter of the cap, that
    pads F least (every padded feature costs a one-hot build, and a
    cap-sized block can pad F nearly 2x), the largest such."""
    if F <= cap:
        return F
    cap = max(cap // step * step, step)
    least = max(cap // 4 // step * step, step)
    return min(range(least, cap + 1, step),
               key=lambda b: (_round_up(F, b), -b))


_CONTRACT_LAST = (((1,), (1,)), ((), ()))  # oh [M, R] . P^T [K, R] -> [M, K]


def _out_struct(shape, dtype, *inputs) -> jax.ShapeDtypeStruct:
    """An ``out_shape`` that varies over the mesh axes its ``inputs`` vary
    over: under ``shard_map(check_vma=True)`` ``pallas_call`` refuses an
    ``out_shape`` that does not say (outside a mesh the set is empty)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


# Features whose one-hots a stacked dot contracts: eight features of W words
# each are W whole (8, 128) uint32 vregs, whatever W is.
DOT_FEATURES = 8


def _dot_features(B: int, N: int) -> int:
    """Features whose SWAR one-hots one fixed-point dot contracts, from the
    kernel's static shapes alone; 1 is the dot a feature.

    ``PT4``, the node-scatter operand, is the same for every feature of a row
    block, and the MXU holds it a 128 x 128 tile at a time. Measured alone
    on a v5e (PERF.md section 6, PR 37): a latched tile costs what streaming
    about 64 int8 rows through it costs, and a feature's dot streams its B
    slots padded to whole 32-row int8 vregs, so at the two-level search's
    widths a dot a feature pays 64 rows a tile for 20 slots, or for 36 slots
    padded to 64. Stacked WITHOUT padding (``_packed_hist``), eight
    features' one-hots stream 8 B rows through each tile they latch and the
    time follows the slots: 0.39 of the dot a feature's at 20 slots and 128
    nodes, 0.60 at 36, 0.80 / 0.68 at the root. Measured at 20 and 36
    slots on the chip (16, the u4 page's, compiles and equals). A one-hot
    wider than 64 slots streams as long as a tile takes to latch by itself
    and keeps the dot a feature (the one-pass schedules at 256 bins:
    stacking two to four features there gained nothing,
    docs/performance.md)."""
    return DOT_FEATURES if B <= 64 else 1


def _note_dot(B: int, N: int, packed: bool = True) -> None:
    """Count a traced kernel's dot form and the one-hot rows of its dot."""
    G = _dot_features(B, N) if packed else 1
    count_hist_dot("stacked" if G > 1 else "feature", G * B)


def _swar_words(B: int, G: int, R: int):
    """The constants of ``_packed_hist``'s one-hot for a dot of G features of
    B slots, a tuple of uint32 arrays each: ``K4``, word w's four slot ids a
    byte each (the words of a feature follow each other, the features follow
    each other), and ``owner``, the feature of the dot that word w belongs
    to. The dot a feature takes one ``[B/4, R]`` array of words and no
    owners; a stacked dot takes them a vreg of eight words at a time (each
    from its own iota: Mosaic does not slice one)."""
    W = B // 4
    if G == 1:
        at = jax.lax.broadcasted_iota(jnp.uint32, (W, R), 0)
        return (at * jnp.uint32(4 * 0x01010101) + jnp.uint32(0x03020100),), ()
    sub = jax.lax.broadcasted_iota(jnp.uint32, (8, R), 0)
    K4, owner = [], []
    for v in range(G * W // 8):
        at = sub + jnp.uint32(8 * v)
        K4.append((at % jnp.uint32(W)) * jnp.uint32(4 * 0x01010101)
                  + jnp.uint32(0x03020100))
        owner.append(at // jnp.uint32(W))
    return tuple(K4), tuple(owner)


@functools.partial(jax.jit, static_argnames=("B",))
def _packed_hist(rows, K4, owner, PT4, *, B):
    """The histograms of ``len(rows)`` features inside a fixed-point kernel:
    the packed-SWAR one-hots of ``rows`` (each [1, R] u32 bin ids under B;
    ``_make_int8_kernel`` explains the detect), stacked without padding,
    against ``PT4`` ([4N, R] int8 byte planes) in ONE dot on the int8 MXU,
    recombined to [len(rows) * B, 2N] f32, feature k's slots at rows
    k*B .. (k+1)*B-1. A row block's sums are exact int32 however the
    features are grouped, so every grouping gives the same bits.

    A feature's B/4 one-hot words are no whole vreg (5 sublanes of 8 at 20
    slots, 9 of 16 at 36), and concatenating them would shift sublanes. So
    the stack is built a vreg at a time: vreg v holds words 8v .. 8v+7 of
    the stack, which belong to two or three features; their ids are
    broadcast over the sublanes and selected by ``owner``, and ONE detect
    chain runs on the dense vreg. The stack is then whole vregs and its
    concatenation is free.

    Jitted so that a kernel body traces it ONCE (twice where a last dot is
    short) and binds one equation a dot afterwards: the bodies run in
    groups of G features (``FEATURE_GROUP``; a group's features and up to 64
    previous nodes are unrolled in Python), and binding their primitives one
    by one cost the depth-8 batched round program 38.8 s of tracing and
    lowering under ``fused`` at HIGGS's shape against 14.9 s this way (one
    v5e host, PERF.md section 6, PR 28). Mosaic inlines the call when it
    lowers: the kernel and its device time are the same."""
    M7F = jnp.uint32(0x7F7F7F7F)
    W, g, R = B // 4, len(rows), K4[0].shape[1]
    spread = [row * jnp.uint32(0x01010101) for row in rows]    # [1, R]
    if not owner:                                  # the dot a feature
        x = K4[0] ^ spread[0]                      # [B/4, R]
    else:
        vregs = []
        for v in range(-(-g * W // 8)):
            first, last = 8 * v // W, min((8 * v + 7) // W, g - 1)
            ids = jnp.broadcast_to(spread[last], (8, R))
            for f in range(last - 1, first - 1, -1):
                ids = jnp.where(owner[v] == jnp.uint32(f),
                                jnp.broadcast_to(spread[f], (8, R)), ids)
            vregs.append(K4[v] ^ ids)
        x = vregs[0] if len(vregs) == 1 else jnp.concatenate(vregs, axis=0)
    y = (~(((x & M7F) + M7F) | x | M7F)) >> jnp.uint32(7)
    oh = pltpu.bitcast(y, jnp.int8)                # [>= g*B, R]
    acc4 = jax.lax.dot_general(
        oh, PT4, _CONTRACT_LAST,
        preferred_element_type=jnp.int32)          # [>= g*B, 4N]
    n2 = PT4.shape[0] // 2
    return (acc4[:g * B, :n2].astype(jnp.float32) * 256.0
            + acc4[:g * B, n2:].astype(jnp.float32))


def _acc_shapes(F_blk: int, F_pad: int, B: int, N: int) -> tuple:
    """(block, whole) shapes of a fixed-point kernel's SWAR accumulator over
    F_pad features in blocks of F_blk: ``[F, B, 2N]`` for the dot a feature,
    ``[F * B, 2N]`` for the stacked dot (``_accumulate``)."""
    if _dot_features(B, N) > 1:
        return (F_blk * B, 2 * N), (F_pad * B, 2 * N)
    return (F_blk, B, 2 * N), (F_pad, B, 2 * N)


def _int8_vmem_bytes(F_blk: int, F_pad: int, B: int, N: int, R: int,
                     tile_rows: int, itemsize: int, packed: bool,
                     refine: bool = False, cat_cols: int = 0,
                     cat_slots: int = 0) -> int:
    """Scoped VMEM a fixed-point histogram kernel holds, reckoned from what
    its body keeps: the input tiles twice (the bin block, its rows padded to
    whole 32-row tiles of bytes, and the gradient pair and positions, 8
    sublanes each); ``PT4`` and the int32 planes it is made from, five
    ``[N, R]`` int32 arrays' worth; a dot's one-hot, ``G * B`` rows of words
    and of bytes (a ``[B, R]`` int32 compare and its bytes without the SWAR
    build); and, where the whole ``[F_pad * B, 2N]`` output passes
    ``_OUT_IN_SCOPE``, the accumulator block's two pipeline buffers. The
    refine build that remaps in VMEM (``refine``) adds its window table
    twice (``[F_blk, N]`` f32, lanes padded to 128), the node one-hot it
    contracts (``[N, R]`` f32), and two ``[F_blk, R]`` 32-bit arrays: the
    remapped block's scratch and the window product it is made from. Its
    categorical columns (``cat_cols`` of ``cat_slots`` slots) add their
    output's two buffers, a column's compare one-hot (int32 and int8) and
    its dot's int32 product.

    Measured for a described v5e at 36 slots and 128 nodes (PERF.md section
    6): 5.9-6.4 MiB at 80-200 features a block while the whole
    output is at most 49 MiB (reckoned 6.75-7.25), the output block then
    outside the scoped allocation; 20.44 MiB at 200 features a block of
    F = 1,600 and 2,000, whose outputs of 56 and 70 MiB bring the block's
    two buffers in (reckoned 21.31); 3.95 at 64 nodes and 5.92 at 20 slots
    (reckoned 4.75 and 6.75)."""
    tiles = (_round_up(tile_rows, 32) * itemsize + 2 * 8 * 4) * R
    G = _dot_features(B, N) if packed else 1
    onehot = (2 if packed else 5) * G * B * R
    need = 2 * tiles + 5 * N * R * 4 + onehot
    if refine:
        rows = _round_up(F_blk, 8)
        need += (2 * rows * _round_up(N, 128) * 4 + N * R * 4
                 + 2 * rows * R * 4)
    if F_pad * B * 2 * N * 4 > _OUT_IN_SCOPE:
        need += 2 * F_blk * B * 2 * N * 4
    if cat_cols:
        need += (2 * cat_cols * cat_slots * _round_up(2 * N, 128) * 4
                 + 5 * cat_slots * R + cat_slots * _round_up(4 * N, 128) * 4)
    return need


# the largest whole histogram output measured to keep its accumulator
# block outside a kernel's scoped VMEM was 49 MiB, the smallest to bring its
# two buffers in 56 MiB (``_int8_vmem_bytes``)
_OUT_IN_SCOPE = 48 * 2 ** 20


def _accumulate(out_ref, at, rows, B, K4, owner, PT4):
    """Add the histograms of the features ``at .. at + len(rows) - 1``, one
    dot: into ``out_ref[at]`` of a ``[F, B, 2N]`` accumulator (the dot a
    feature), or into rows ``at * B ..`` of a ``[F * B, 2N]`` one (a stacked
    dot's features follow each other, so their rows are ONE aligned slice;
    the wrapper reshapes outside the kernel)."""
    val = _packed_hist(tuple(rows), K4, owner, PT4, B=B)
    if len(out_ref.shape) == 3:
        out_ref[at] += val
    else:
        # eight features' rows, or an even group's, start a sublane tile
        first = at * B if isinstance(at, int) else pl.multiple_of(at * B, 8)
        out_ref[pl.ds(first, len(rows) * B), :] += val


def _u4_row(bins_ref, f):
    """Feature ``f``'s bin ids from a u4-packed ``[ceil(F/2), R]`` block:
    byte row ``f // 2``, low nibble for even features, high for odd — the
    in-VMEM decode of the compressed page transport (the packed page is
    the only HBM-resident copy; each nibble extract is one VPU shift+mask
    against the same resident byte row)."""
    word = bins_ref[f // 2:f // 2 + 1, :].astype(jnp.int32)
    return (word >> (4 * (f % 2))) & 0x0F


def _make_kernel(n_feat_block: int, n_bins: int, n_nodes: int, block_rows: int,
                 precision: str, u4: bool = False):
    B, N, R, Fb = n_bins, n_nodes, block_rows, n_feat_block
    oh_dtype = jnp.float32 if precision == "f32" else jnp.bfloat16
    mxu_prec = (jax.lax.Precision.HIGHEST if precision == "f32"
                else jax.lax.Precision.DEFAULT)

    def kernel(bins_ref, gpair_ref, pos_ref, out_ref, oh_scratch):
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        pos_row = pos_ref[:]                               # [1, R] int32
        node_iota = jax.lax.broadcasted_iota(jnp.int32, (N, R), 0)
        on_node = (pos_row == node_iota).astype(jnp.float32)   # [N, R]
        g_row = gpair_ref[0:1, :]                          # [1, R]
        h_row = gpair_ref[1:2, :]
        PT = jnp.concatenate([on_node * g_row, on_node * h_row], axis=0)
        if precision == "f32":
            P_ops = [PT]
        else:
            hi = PT.astype(jnp.bfloat16)
            if precision == "bf16":
                P_ops = [hi]
            else:  # bf16x2 hi/lo split
                lo = (PT - hi.astype(jnp.float32)).astype(jnp.bfloat16)
                P_ops = [hi, lo]

        bin_iota = jax.lax.broadcasted_iota(jnp.int32, (B, R), 0)
        for f in range(Fb):
            row = (_u4_row(bins_ref, f) if u4
                   else bins_ref[f:f + 1, :].astype(jnp.int32))  # [1, R]
            oh_scratch[f * B:(f + 1) * B, :] = (
                bin_iota == row).astype(oh_dtype)
        acc = jnp.zeros((Fb * B, 2 * N), jnp.float32)
        for Pi in P_ops:
            acc = acc + jax.lax.dot_general(
                oh_scratch[:], Pi, _CONTRACT_LAST,
                precision=mxu_prec, preferred_element_type=jnp.float32)
        out_ref[:] += acc.reshape(Fb, B, 2 * N)

    return kernel


def _remap_window(tab_ref, bins_ref, on_node, rb_ref, missing_bin: int):
    """The refine build's remap of one ``[F_blk, R]`` block of raw bin ids,
    in VMEM: each row's window start is its node's entry of the window
    table (``tab_ref``, ``[F_blk, N]`` in coarse units), taken by ONE dot
    against the node one-hot the body already holds (exact: one non-zero
    term a sum, window starts below 16); ``refine_slots`` (``ops/split.py``,
    the XLA build's definition) maps the ids into ``rb_ref``, the int32
    scratch the feature loop then reads as it reads a bin block. A fixed
    number of equations whatever F_blk and N are. A row of no node of the
    level (outside it, or padding) meets a zero column and keeps window 0;
    its slot is inert, for its ``PT4`` column is zero."""
    from ..split import refine_slots

    win = jax.lax.dot_general(
        tab_ref[:], on_node.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # [F_blk, R]
    rb_ref[:] = refine_slots(bins_ref[:].astype(jnp.int32),
                             win.astype(jnp.int32), missing_bin)


def _make_int8_kernel(n_feat_block: int, n_bins: int, n_nodes: int,
                      block_rows: int, packed: bool = False,
                      u4: bool = False,
                      refine_missing: Optional[int] = None,
                      cat_rows: tuple = (), cat_slots: int = 0):
    """Fixed-point kernel: gradients arrive as two int8 byte planes
    (value = hi * 256 + lo, a 15-bit quantisation done by the caller);
    both planes are contracted with the 0/1 one-hot on the int8 MXU with
    exact int32 accumulation, then recombined into f32.

    ``refine_missing`` (the raw tile's missing slot) makes it the refine
    build of the two-level search: the kernel takes the window table
    first and the RAW bin block, remaps the block once a grid step
    (``_remap_window``) and histograms the refine slots, bit for bit the
    build over ``ops/split.py refine_bin_ids``' ids.

    ``cat_rows`` (a refine build's categorical columns, as ``(feature
    block, row in it)``): the same sweep sums their full histograms, one
    slot a category (``cat_slots``), into a second output ``[len(cat_rows),
    cat_slots, 2N]`` that stays in VMEM over the whole grid, from the RAW
    block and the ``PT4`` the refine dots take: a ``[cat_slots, R]``
    compare one-hot and one dot a column, in the block that holds it.

    ``packed=True`` (requires ``n_bins % 4 == 0 and n_bins <= 256``): the
    one-hot is built four bins per uint32 word with a SWAR zero-byte
    detect instead of a [B, R] i32 compare — word w of row r holds the
    one-hot bytes for bins 4w..4w+3, computed as

        x = (4w | 4w+1<<8 | 4w+2<<16 | 4w+3<<24) ^ (bin * 0x01010101)
        y = ~(((x & 0x7F7F7F7F) + 0x7F7F7F7F) | x | 0x7F7F7F7F) >> 7

    (byte of y = 1 iff the matching byte of x is zero; the masked +
    cannot carry across bytes so the detect is exact — the shorter
    ``(x-M01) & ~x & M80`` idiom has false positives from borrow ripple
    when a lower byte matches). ``pltpu.bitcast`` then reinterprets the
    ``[B/4, R]`` u32 plane as ``[B, R]`` int8 for free: int8's (32, 128)
    tiling packs 4 sublanes per 32-bit register row, so little-endian
    byte j of word w IS sublane 4w+j. Measured (device-lane, XLA trace,
    v5e, 1M x 28 x 256): 6.90 -> 4.93 ms/level together with the full-F
    feature block, bit-identical output; the kernel is then bound by the
    VPU SWAR chain + MXU operand handoff, not the compare.

    NOTE a fused variant carrying all 2K components of a K-target gradient
    in one pass was measured SLOWER than K separate passes (111ms vs 55ms
    at K=3, 1M rows: the widened [.., C*N] output spills past one MXU
    column tile), so multi-target histograms intentionally loop targets."""
    B, N, R, Fb = n_bins, n_nodes, block_rows, n_feat_block

    def kernel(*refs):
        if refine_missing is None:
            bins_ref, q_ref, pos_ref, out_ref = refs
        elif cat_rows:
            (tab_ref, raw_ref, q_ref, pos_ref, out_ref, cat_ref,
             bins_ref) = refs
        else:
            tab_ref, raw_ref, q_ref, pos_ref, out_ref, bins_ref = refs
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        if cat_rows:
            @pl.when((i == 0) & (pl.program_id(0) == 0))
            def _():
                cat_ref[:] = jnp.zeros_like(cat_ref)

        pos_row = pos_ref[:]                               # [1, R] int32
        node_iota = jax.lax.broadcasted_iota(jnp.int32, (N, R), 0)
        on_node = pos_row == node_iota                     # [N, R] bool
        zero = jnp.zeros((N, R), jnp.int32)
        if refine_missing is not None:
            _remap_window(tab_ref, raw_ref, on_node, bins_ref,
                          refine_missing)

        # Scatter q to nodes in the i32 layout domain, split into byte
        # planes, and drop to int8 only at the MXU boundary (int8 VPU
        # arithmetic/relayout is not legal on this hardware generation).
        def planes(row):                                   # [1, R] i32
            PTq = jnp.where(on_node, jnp.broadcast_to(row, (N, R)), zero)
            hi = (PTq + 128) >> 8                          # round-to-nearest
            lo = PTq - hi * 256                            # in [-128, 127]
            return hi.astype(jnp.int8), lo.astype(jnp.int8)

        g_hi, g_lo = planes(q_ref[0:1, :])
        h_hi, h_lo = planes(q_ref[1:2, :])
        # hi/lo byte planes as extra COLUMNS of one [4N, R] RHS: a single
        # MXU pass over the one-hot instead of two (the one-hot operand
        # feed dominates)
        PT4 = jnp.concatenate([g_hi, h_hi, g_lo, h_lo], axis=0)  # [4N, R] i8

        if cat_rows:
            cat_iota = jax.lax.broadcasted_iota(jnp.int32, (cat_slots, R), 0)
            for k, (blk, r) in enumerate(cat_rows):
                @pl.when(pl.program_id(0) == blk)
                def _(k=k, r=r):
                    oh = (cat_iota == raw_ref[r:r + 1, :].astype(jnp.int32)
                          ).astype(jnp.int8)                 # [C, R]
                    acc4 = jax.lax.dot_general(
                        oh, PT4, _CONTRACT_LAST,
                        preferred_element_type=jnp.int32)    # [C, 4N]
                    cat_ref[k] += (acc4[:, : 2 * N].astype(jnp.float32)
                                   * 256.0
                                   + acc4[:, 2 * N:].astype(jnp.float32))

        # One-hot + dot, ``_dot_features`` features at a time (not one big
        # [Fb*B, R] staged matmul): the dots are unrolled, so Mosaic
        # pipelines the VPU one-hot build of dot d+1 against the MXU dot d,
        # overlapping the kernel's two bound units. At B = 256 a dot is one
        # feature: measured 8.3 -> ~4.8 ms/level at 1M x 28 x 256 on v5e
        # against the staged matmul. At the two-level search's 20 and 36
        # slots it is eight (``_dot_features`` has that measurement).
        if packed:
            G = _dot_features(B, N)
            K4, owner = _swar_words(B, G, R)
            for f0 in range(0, Fb, G):
                _accumulate(out_ref, f0, [
                    (_u4_row(bins_ref, f).astype(jnp.uint32) if u4
                     else bins_ref[f:f + 1, :].astype(jnp.uint32))
                    for f in range(f0, min(f0 + G, Fb))], B, K4, owner, PT4)
        else:
            bin_iota = jax.lax.broadcasted_iota(jnp.int32, (B, R), 0)
            for f in range(Fb):
                row = (_u4_row(bins_ref, f) if u4
                       else bins_ref[f:f + 1, :].astype(jnp.int32))
                oh = (bin_iota == row).astype(jnp.int8)        # [B, R]
                acc4 = jax.lax.dot_general(
                    oh, PT4, _CONTRACT_LAST,
                    preferred_element_type=jnp.int32)      # [B, 4N]
                out_ref[f] += (acc4[:, : 2 * N].astype(jnp.float32) * 256.0
                               + acc4[:, 2 * N:].astype(jnp.float32))

    return kernel


# ---- categorical routing inside the advance kernels -------------------------
# A categorical split sends a row left when its category's bit is set in the
# node's left set (``ops/split.py SplitResult.cat_words``, W uint32 words).
# The kernels look the bit up with no gather: the words of every node of the
# level arrive as one table of bytes, ``[Fc * 4 * Wp, N]`` f32 (row ``(c,
# byte, w)`` holds byte ``byte`` of word ``w`` of node ``j``'s set where node
# ``j`` splits on the c-th categorical column, 0 elsewhere), and ONE dot
# against the rows' node one-hot ``[N, R]`` hands each row its own node's
# words, exactly (one non-zero term a sum, bytes below 256). A select over
# the Wp word sublanes then takes the word the row's category falls in.


def cat_word_rows(n_words: int) -> int:
    """Sublanes a column's words take in the routing table: W rounded up to
    whole sublane tiles."""
    return _round_up(max(n_words, 1), 8)


def cat_route_table(feat: jnp.ndarray, is_cat: jnp.ndarray,
                    words: jnp.ndarray, cat_cols: tuple) -> jnp.ndarray:
    """The routing table (above) of one level's ``N`` nodes: ``feat`` [N]
    split features, ``is_cat`` [N] categorical splits, ``words`` [N, W]
    their left sets; ``cat_cols`` the matrix's categorical columns.
    -> ``[len(cat_cols) * 4 * Wp, N]`` f32."""
    N, W = words.shape
    Wp = cat_word_rows(W)
    w = jnp.pad(words.astype(jnp.uint32), ((0, 0), (0, Wp - W)))
    shifts = jnp.uint32(8) * jnp.arange(4, dtype=jnp.uint32)
    by = (w[:, None, :] >> shifts[None, :, None]) & jnp.uint32(0xFF)
    on = is_cat.astype(bool)[:, None] & (
        feat[:, None] == jnp.asarray(cat_cols, jnp.int32)[None, :])
    tab = jnp.where(on[:, :, None, None], by[:, None], jnp.uint32(0))
    return tab.reshape(N, -1).T.astype(jnp.float32)


def _cat_route_vmem(n_cat: int, n_words: int, N: int, R: int) -> int:
    """Scoped VMEM the routing adds to an advance kernel: the table twice,
    the node one-hot it contracts and a column's product with its select."""
    rows = n_cat * 4 * cat_word_rows(n_words)
    return (2 * _round_up(rows, 8) * _round_up(N, 128) * 4 + N * R * 4
            + 2 * 4 * cat_word_rows(n_words) * R * 4)


def _cat_goes_left(tab_ref, on_prev, cat_rows):
    """``[1, R]`` int32, 1 where a row's category (in the column its node
    splits on) is in its node's left set; 0 for rows at a numeric node or
    outside the level. ``on_prev``: ``[N, R]`` f32 one-hot of each row's
    node; ``cat_rows``: the categorical columns' ``[1, R]`` int32 ids, in
    the table's order. A fixed number of equations a categorical column."""
    Wp = tab_ref.shape[0] // (4 * len(cat_rows))
    R = on_prev.shape[1]
    at = jax.lax.broadcasted_iota(jnp.int32, (Wp, R), 0)
    left = jnp.zeros((1, R), jnp.int32)
    for c, b in enumerate(cat_rows):
        per = jax.lax.dot_general(
            tab_ref[c * 4 * Wp:(c + 1) * 4 * Wp, :], on_prev,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # [4 Wp, R]
        hit = at == (b >> 5)
        word = jnp.zeros((1, R), jnp.int32)
        for k in range(4):
            v = jnp.sum(jnp.where(hit, per[k * Wp:(k + 1) * Wp], 0.0),
                        axis=0, keepdims=True).astype(jnp.int32)
            word = word | (v << (8 * k))
        left = left | (jax.lax.shift_right_logical(word, b & 31) & 1)
    return left


def _make_fused_kernel(n_feat: int, n_prev: int, n_nodes: int,
                       block_rows: int, lo_prev: int, lo: int,
                       missing_bin: int, coarse_b: int, shift: int,
                       cat_cols: tuple = ()):
    """Cross-level fused sweep (hist_method="fused"): ONE read of the
    ``[F, R]`` bin tile per row block drives (a) the row-position advance
    below the previous level's decoded splits, (b) the coarse-id remap
    ``bins >> shift`` for the NEW level, and (c) the packed-SWAR one-hot +
    int8 MXU contraction of the new level's coarse histogram. The unfused
    two-pass path reads the tile once for the advance and once (as a
    materialised coarse-id copy) for the coarse build; here both consumers
    share the VMEM-resident tile, halving the boundary's HBM traffic.

    The previous level's split payload arrives as a ``[4, n_prev]`` int32
    SMEM block (safe feature id, threshold bin, default_left, can_split);
    each previous node's split-feature row is pulled from the tile with
    one dynamic sublane slice — n_prev <= 64, so this is a short scalar
    loop, not a gather. The slice reads an int32 copy of the tile staged
    once per row block in a VMEM scratch (``[F, R]`` i32, 224 KiB at
    F=28, R=2048): Mosaic refuses a dynamic one-row slice of the packed
    uint8 tile itself (four rows share a sublane, so the row index would
    have to be provably aligned), and accepts it on 32-bit rows.

    Histogram math is IDENTICAL to ``_make_int8_kernel(packed=True)`` at
    ``B = coarse_b``: same loop over dots of ``_dot_features`` features,
    same PT4 node-scatter, same per-row-block f32 accumulation order: the
    fused coarse histogram is bit-identical to the unfused one.

    The advance reads an arbitrary split feature, so the tile stays whole-F
    and cannot be cut on the grid as ``build_hist_pallas`` cuts it. Past
    ``FEATURE_GROUP`` features the histogram runs as a ``fori_loop`` over
    even groups (``_feature_groups``), a group's features unrolled exactly
    as the one group of a narrow matrix is: the rows of the int32 scratch
    and the accumulator's leading axis take a dynamic index as they are.
    The scratch and the accumulator are ``groups x group`` features long;
    the rows past F (fewer than ``groups``) are zero ids, and the wrapper
    drops their histograms.

    ``cat_cols`` (the matrix's categorical columns): the payload gains a
    fifth row, the node's categorical flag, and the kernel the routing
    table (``cat_route_table``) as its fifth input; a categorical node
    routes its rows by their category's bit in its left set
    (``_cat_goes_left``, once a row block for every node of the level),
    a missing one by ``default_left``. Their coarse ids are never read."""
    from ..split import COARSE_NUMERIC_IDS, COARSE_PAD_SLOT

    B, N, R, F = coarse_b, n_nodes, block_rows, n_feat
    groups, group = _feature_groups(F)
    F_pad = groups * group

    # the two loop bodies (a previous node, a feature's coarse ids), jitted
    # for the reason ``_packed_hist`` gives: traced once a kernel, one
    # equation an iteration afterwards
    @jax.jit
    def advance_below(bj, tj, dj, cj, j, pos_row, rel_prev, new_pos):
        # the SMEM scalars enter as int32 operands only: a scalar bool
        # broadcast against a vector does not lower
        gr = jnp.where(bj == missing_bin, 1 - dj,
                       (bj > tj).astype(jnp.int32))
        child = 2 * pos_row + 1 + gr
        take = jnp.where(rel_prev == j, cj, 0)
        return jnp.where(take > 0, child, new_pos)

    @jax.jit
    def advance_below_cat(bj, tj, dj, cj, kj, j, pos_row, rel_prev, new_pos,
                          cat_right):
        gr = jnp.where(bj == missing_bin, 1 - dj,
                       jnp.where(kj > 0, cat_right,
                                 (bj > tj).astype(jnp.int32)))
        child = 2 * pos_row + 1 + gr
        take = jnp.where(rel_prev == j, cj, 0)
        return jnp.where(take > 0, child, new_pos)

    @jax.jit
    def coarse_ids(row):
        if missing_bin > COARSE_NUMERIC_IDS:
            # ids past the numeric range: a categorical column's, on a pad
            # slot (``ops/split.py coarse_bin_ids``)
            cb = jnp.where(row == missing_bin, B - 1,
                           jnp.minimum(row >> shift, COARSE_PAD_SLOT))
        else:
            cb = jnp.where(row == missing_bin, B - 1, row >> shift)
        return cb.astype(jnp.uint32)

    def kernel(split_ref, bins_ref, q_ref, pos_ref, *refs):
        if cat_cols:
            cat_ref, hist_ref, pos_out_ref, bins32 = refs
        else:
            hist_ref, pos_out_ref, bins32 = refs
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            hist_ref[:] = jnp.zeros_like(hist_ref)

        if F_pad == F:
            bins32[:] = bins_ref[:].astype(jnp.int32)      # [F, R] i32
        else:
            @pl.when(i == 0)
            def _():
                bins32[F:, :] = jnp.zeros((F_pad - F, R), jnp.int32)

            bins32[0:F, :] = bins_ref[:].astype(jnp.int32)

        # ---- advance: route rows below the previous level's splits ----
        pos_row = pos_ref[:]                               # [1, R] i32
        rel_prev = jnp.where(
            (pos_row >= lo_prev) & (pos_row < lo_prev + n_prev),
            pos_row - lo_prev, n_prev)
        new_pos = pos_row
        if cat_cols:
            on_prev = (jax.lax.broadcasted_iota(jnp.int32, (n_prev, R), 0)
                       == rel_prev).astype(jnp.float32)    # [n_prev, R]
            cat_right = 1 - _cat_goes_left(
                cat_ref, on_prev, [bins32[c:c + 1, :] for c in cat_cols])
        for j in range(n_prev):
            fj = split_ref[0, j]
            bj = bins32[pl.ds(fj, 1), :]                   # [1, R]
            if cat_cols:
                new_pos = advance_below_cat(
                    bj, split_ref[1, j], split_ref[2, j], split_ref[3, j],
                    split_ref[4, j], np.int32(j), pos_row, rel_prev, new_pos,
                    cat_right)
            else:
                new_pos = advance_below(
                    bj, split_ref[1, j], split_ref[2, j], split_ref[3, j],
                    np.int32(j), pos_row, rel_prev, new_pos)
        pos_out_ref[:] = new_pos
        rel = jnp.where((new_pos >= lo) & (new_pos < lo + N),
                        new_pos - lo, N)                   # [1, R]

        # ---- coarse histogram of the NEW level from the same tile ----
        node_iota = jax.lax.broadcasted_iota(jnp.int32, (N, R), 0)
        on_node = rel == node_iota                         # [N, R] bool
        zero = jnp.zeros((N, R), jnp.int32)

        def planes(row):                                   # [1, R] i32
            PTq = jnp.where(on_node, jnp.broadcast_to(row, (N, R)), zero)
            hi = (PTq + 128) >> 8                          # round-to-nearest
            lo_b = PTq - hi * 256                          # in [-128, 127]
            return hi.astype(jnp.int8), lo_b.astype(jnp.int8)

        g_hi, g_lo = planes(q_ref[0:1, :])
        h_hi, h_lo = planes(q_ref[1:2, :])
        PT4 = jnp.concatenate([g_hi, h_hi, g_lo, h_lo], axis=0)  # [4N, R]

        G = _dot_features(B, N)
        K4, owner = _swar_words(B, G, R)

        def one_group(g, carry):
            for f0 in range(0, group, G):
                at = g * group + f0
                _accumulate(hist_ref, at, [
                    coarse_ids(bins32[pl.ds(at + k, 1), :])
                    for k in range(min(G, group - f0))], B, K4, owner, PT4)
            return carry

        if groups == 1:
            one_group(0, 0)
        else:
            jax.lax.fori_loop(0, groups, one_group, 0)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("lo_prev", "n_prev", "lo", "n_level", "missing_bin",
                     "block_rows", "interpret", "axis_name", "cat_cols"))
def fused_advance_coarse_pallas(bins_t: jnp.ndarray, gpair: jnp.ndarray,
                                positions: jnp.ndarray, feat: jnp.ndarray,
                                thr: jnp.ndarray, dleft: jnp.ndarray,
                                can_split: jnp.ndarray, cat=None, *,
                                lo_prev: int, n_prev: int, lo: int,
                                n_level: int, missing_bin: int,
                                block_rows: int = ROW_BLOCK,
                                axis_name=None, interpret: bool = False,
                                cat_cols: tuple = ()):
    """Single-HBM-read advance + coarse build (see ``_make_fused_kernel``).

    bins_t: [F, n] fine bin ids (or the tile padded to the row blocks,
    ``fit_rows``); gpair: [n, 2] f32; positions: [n] heap node ids;
    feat/thr/dleft/can_split: [n_prev] previous-level split vectors
    (feat == -1 on non-split slots); cat: (is_cat [n_prev], words
    [n_prev, W]) of a matrix with the categorical columns ``cat_cols``.
    -> (new_positions [n] int32, hist [n_level, F, COARSE_B, 2] f32)
    """
    from ..split import COARSE_B, COARSE_SPAN

    F, n = bins_t.shape[0], gpair.shape[0]
    B, N = COARSE_B, n_level
    shift = COARSE_SPAN.bit_length() - 1

    R, n_pad = row_blocks(n, block_rows)
    bins_t = fit_rows(bins_t, n_pad)
    if n_pad != n:
        gpair = jnp.pad(gpair, ((0, n_pad - n), (0, 0)))
        # pad positions OUTSIDE every level: inactive for both the advance
        # and the new level's histogram (their quantised gpair is 0 anyway)
        positions = jnp.pad(positions, (0, n_pad - n), constant_values=-1)

    # identical 15-bit fixed-point quantisation to build_hist_pallas's
    # int8x2 path (global per-component scale, pmax'd across row shards)
    with stage("quantise"):
        gpair_t = gpair.T                                # [2, n]
        max_abs = jnp.max(jnp.abs(gpair_t), axis=1)
        if axis_name is not None:
            with mesh_scope("scale_pmax"):
                max_abs = jax.lax.pmax(max_abs, axis_name)
        scale = 32512.0 / jnp.maximum(max_abs, 1e-30)
        q = jnp.round(gpair_t * scale[:, None]).astype(jnp.int32)
    pos_t = positions.astype(jnp.int32)[None, :]         # [1, n]
    rows = [jnp.maximum(feat, 0).astype(jnp.int32), thr.astype(jnp.int32),
            dleft.astype(jnp.int32), can_split.astype(jnp.int32)]
    if cat_cols:
        rows.append(cat[0].astype(jnp.int32))
    splits = jnp.stack(rows)                             # [4 or 5, n_prev]

    grid = (n_pad // R,)
    groups, group = _feature_groups(F)
    F_pad = groups * group
    # The whole-F tile, double-buffered, and its int32 copy are 15.9 MB at
    # F = 968 with two-byte ids: all but the default 16 MiB of scoped VMEM
    # before the accumulator and the loop's operands (Mosaic asked 16.83M
    # for the grouped body at 32 nodes). A grouped kernel states what its
    # tile takes, with the default's room beside it (the accumulator, and a
    # stacked dot's words, one-hot and int32 product: 0.7 MB at 20 slots and
    # 32 nodes); a narrow one keeps the default.
    extra = (F_pad * R * (4 + 2 * bins_t.dtype.itemsize)
             if groups > 1 else 0)
    if cat_cols:
        # a categorical matrix's kernel states what its routing adds
        extra += _cat_route_vmem(len(cat_cols), cat[1].shape[1], n_prev, R)
    params = _vmem_params("fused_advance_coarse", None if not extra
                          else extra + SCOPED_VMEM)
    count_hist_onehot("swar")
    _note_dot(B, N)
    note_hist_body_features(group)
    in_specs = [pl.BlockSpec((splits.shape[0], n_prev), lambda i: (0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((F, R), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((2, R), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, R), lambda i: (0, i),
                             memory_space=pltpu.VMEM)]
    operands = [splits, bins_t, q, pos_t]
    if cat_cols:
        tab = cat_route_table(feat, cat[0], cat[1], cat_cols)
        in_specs.append(pl.BlockSpec(tab.shape, lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
        operands.append(tab)
    with stage("kernel.fused_advance_coarse"):
        _, acc = _acc_shapes(F_pad, F_pad, B, N)
        hist, pos_out = pl.pallas_call(
            _make_fused_kernel(F, n_prev, N, R, lo_prev, lo, missing_bin, B,
                               shift, cat_cols),
            out_shape=[_out_struct(acc, jnp.float32, bins_t, q),
                       _out_struct((1, n_pad), jnp.int32, bins_t, pos_t)],
            grid=grid,
            in_specs=in_specs,
            out_specs=[pl.BlockSpec(acc, lambda i: (0,) * len(acc),
                                    memory_space=pltpu.VMEM),
                       pl.BlockSpec((1, R), lambda i: (0, i),
                                    memory_space=pltpu.VMEM)],
            scratch_shapes=[pltpu.VMEM((F_pad, R), jnp.int32)],
            compiler_params=params,
            interpret=interpret,
            name="fused_advance_coarse",
        )(*operands)
    with stage("fold"):
        inv = jnp.repeat(1.0 / scale, N)[None, None, :]  # [1, 1, 2N]
        hist = hist.reshape(F_pad, B, 2 * N)[:F] * inv
        gh = hist.reshape(F, B, 2, N)
        return pos_out[0, :n], gh.transpose(3, 0, 1, 2)  # [N, F, B, 2]


def _make_advance_leaf_kernel(n_feat: int, n_prev: int, block_rows: int,
                              missing_bin: int, cat_cols: tuple = ()):
    """The LAST level's advance with the leaf delta behind it: one sweep of
    the ``[F, R]`` tile routes the rows below the deepest evaluated level
    and writes each row's leaf value, where the XLA epilogue paid six
    one-element gathers over all rows (``ops/partition.py
    update_positions`` + ``leaf_value[positions]``).

    The level is too wide for ``_make_fused_kernel``'s scalar loop (one
    ``[1, R]`` pass a previous node: a sublane in eight at work), so the
    row's payload comes by a one-hot of its node over the SUBLANES, on
    full-width vectors and in integers. ``tab_ref`` is ``[3, 2N, 1]``
    int32, row ``heap node id + 1`` (row 0 stays zero: pad rows carry
    position -1), ``N = n_prev`` nodes on the level, which starts at heap
    node ``N - 1``:

    - plane 0, rows ``1..N-1`` (the levels above): the node's leaf value,
      as its bit pattern: a row that stopped there takes it;
    - plane 0, rows ``N..2N-1`` (the level): the split, packed
      ``feature | bin << 16 | default_left << 28 | can_split << 29``;
    - planes 1 and 2, rows ``N..2N-1``: the left and the right child's
      leaf bits where the node splits, the node's own where it does not.

    A select-and-sum over the one-hot has one non-zero term a row, so it
    IS the table entry, bit for bit (a signed zero too: the leaf values
    never pass through float arithmetic). The row's bin of its node's
    split feature is the same select over the tile's feature sublanes.

    ``cat_cols`` (the matrix's categorical columns): the split word's bit
    30 marks a categorical split, and the routing table
    (``cat_route_table``) arrives as a fourth input; such a node routes a
    row by its category's bit in the node's left set
    (``_cat_goes_left``), a missing one by ``default_left``."""
    F, N, R = n_feat, n_prev, block_rows
    C = min(N, _LEAF_CHUNK)

    def take(acc, hit, tab_ref, plane, c):
        return acc + jnp.where(hit, tab_ref[plane, c:c + C, :], 0)

    def kernel(tab_ref, bins_ref, pos_ref, *refs):
        if cat_cols:
            cat_ref, pos_out_ref, delta_ref = refs
        else:
            pos_out_ref, delta_ref = refs
        pos = pos_ref[:]                                   # [1, R] i32
        idx = pos + 1
        iota = jax.lax.broadcasted_iota(jnp.int32, (C, R), 0)
        zero = jnp.zeros((C, R), jnp.int32)
        own = zero
        for c in range(0, N, C):
            own = take(own, iota == idx - c, tab_ref, 0, c)
        split, left, right = zero, zero, zero
        for c in range(N, 2 * N, C):
            hit = iota == idx - c
            split = take(split, hit, tab_ref, 0, c)
            left = take(left, hit, tab_ref, 1, c)
            right = take(right, hit, tab_ref, 2, c)
        own, split, left, right = (
            jnp.sum(a, axis=0, keepdims=True)
            for a in (own, split, left, right))            # [1, R] each
        feat = split & 0xFFFF
        thr = (split >> 16) & 0xFFF
        dleft = (split >> 28) & 1
        if cat_cols:
            can_split = (split >> 29) & 1
            is_cat = split >> 30
        else:
            can_split = split >> 29

        fiota = jax.lax.broadcasted_iota(jnp.int32, (F, R), 0)
        b = jnp.sum(jnp.where(fiota == feat,
                              bins_ref[:].astype(jnp.int32), 0),
                    axis=0, keepdims=True)                 # [1, R]

        if cat_cols:
            on_prev = (jax.lax.broadcasted_iota(jnp.int32, (N, R), 0)
                       == pos - (N - 1)).astype(jnp.float32)   # [N, R]
            cat_left = _cat_goes_left(
                cat_ref, on_prev,
                [bins_ref[c:c + 1, :].astype(jnp.int32) for c in cat_cols])
            go_right = jnp.where(b == missing_bin, 1 - dleft,
                                 jnp.where(is_cat > 0, 1 - cat_left,
                                           (b > thr).astype(jnp.int32)))
        else:
            go_right = jnp.where(b == missing_bin, 1 - dleft,
                                 (b > thr).astype(jnp.int32))
        pos_out_ref[:] = jnp.where(can_split > 0, 2 * pos + 1 + go_right,
                                   pos)
        bits = own + jnp.where(go_right > 0, right, left)
        delta_ref[:] = jax.lax.bitcast_convert_type(bits, jnp.float32)

    return kernel


# the widest last level ``advance_leaf_pallas`` takes: max_depth 10
ADVANCE_LEAF_MAX_NODES = 512
# table sublanes a step of the kernel's select: ``[C, R]`` accumulators
_LEAF_CHUNK = 64
# the widest tile ``advance_leaf_pallas`` compiles under the default scoped
# VMEM, measured for a described v5e below a level of 128 nodes: F = 968
# two-byte ids at R = 2048, whose two buffers and int32 copy are 15.1 of the
# 16 MiB (the table and the row vectors take the rest)
_ADVANCE_LEAF_TILE_FITS = 968 * 2048 * (4 + 2 * 2)


@functools.partial(
    jax.jit,
    static_argnames=("n_prev", "missing_bin", "block_rows", "interpret",
                     "cat_cols"))
def advance_leaf_pallas(bins_t: jnp.ndarray, positions: jnp.ndarray,
                        feat: jnp.ndarray, thr: jnp.ndarray,
                        dleft: jnp.ndarray, can_split: jnp.ndarray,
                        leaf_value: jnp.ndarray, cat=None, *, n_prev: int,
                        missing_bin: int, block_rows: int = ROW_BLOCK,
                        interpret: bool = False, cat_cols: tuple = ()):
    """Advance below the deepest evaluated level and look the leaf up, in
    one sweep (see ``_make_advance_leaf_kernel``).

    bins_t: [F, n] bin ids (or the tile padded to the row blocks,
    ``fit_rows``); positions: [n] heap node ids, none below the
    level; feat/thr/dleft/can_split: [n_prev] the level's splits, the
    level starting at heap node ``n_prev - 1``; leaf_value: [max_nodes]
    f32 over the whole heap, the level's children included; cat: (is_cat
    [n_prev], words [n_prev, W]) of a matrix with the categorical columns
    ``cat_cols``.
    -> (new_positions [n] int32, delta [n] f32 = leaf_value[new_positions])
    """
    F, n = bins_t.shape[0], positions.shape[0]
    N, lo = n_prev, n_prev - 1
    if F > 0xFFFF or missing_bin > 0xFFF:
        raise NotImplementedError("advance_leaf_pallas packs the feature "
                                  "in 16 bits and the bin in 12")
    R, n_pad = row_blocks(n, block_rows)
    bins_t = fit_rows(bins_t, n_pad)
    if n_pad != n:
        positions = jnp.pad(positions, (0, n_pad - n), constant_values=-1)
    pos_t = positions.astype(jnp.int32)[None, :]           # [1, n]
    # The whole-F tile, double-buffered, and its int32 copy. Past the widest
    # measured to compile under the default scoped VMEM the kernel states
    # what they take, with the default's room beside it, as the boundary
    # sweep does: at F = 2,000 with one-byte ids they are 23.4 MiB, and
    # Mosaic asked 24.75M (PERF.md section 6).
    tile = F * R * (4 + 2 * bins_t.dtype.itemsize)
    if cat_cols:
        # a categorical matrix's kernel states what its routing adds
        tile += _cat_route_vmem(len(cat_cols), cat[1].shape[1], N, R)
    params = _vmem_params("advance_leaf",
                          None if tile <= _ADVANCE_LEAF_TILE_FITS
                          and not cat_cols else tile + SCOPED_VMEM)

    bits = jax.lax.bitcast_convert_type(
        leaf_value.astype(jnp.float32), jnp.int32)
    node = lo + jnp.arange(N, dtype=jnp.int32)
    cs = can_split.astype(bool)
    packed = (jnp.maximum(feat, 0).astype(jnp.int32)
              | thr.astype(jnp.int32) << 16
              | dleft.astype(jnp.int32) << 28
              | cs.astype(jnp.int32) << 29)
    if cat_cols:
        packed = packed | (cat[0].astype(bool) & cs).astype(jnp.int32) << 30
    zeros = jnp.zeros((N,), jnp.int32)
    tab = jnp.stack([
        jnp.concatenate([zeros[:1], bits[:lo], packed]),
        jnp.concatenate([zeros, jnp.where(cs, bits[2 * node + 1],
                                          bits[node])]),
        jnp.concatenate([zeros, jnp.where(cs, bits[2 * node + 2],
                                          bits[node])]),
    ])[:, :, None]                                         # [3, 2N, 1]

    in_specs = [pl.BlockSpec((3, 2 * N, 1), lambda i: (0, 0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((F, R), lambda i: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, R), lambda i: (0, i),
                             memory_space=pltpu.VMEM)]
    operands = [tab, bins_t, pos_t]
    if cat_cols:
        route = cat_route_table(feat, cat[0], cat[1], cat_cols)
        in_specs.append(pl.BlockSpec(route.shape, lambda i: (0, 0),
                                     memory_space=pltpu.VMEM))
        operands.append(route)
    with stage("kernel.advance_leaf"):
        pos_out, delta = pl.pallas_call(
            _make_advance_leaf_kernel(F, N, R, missing_bin, cat_cols),
            out_shape=[_out_struct((1, n_pad), jnp.int32, bins_t, pos_t),
                       _out_struct((1, n_pad), jnp.float32, bins_t, pos_t)],
            grid=(n_pad // R,),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, R), lambda i: (0, i),
                                    memory_space=pltpu.VMEM),
                       pl.BlockSpec((1, R), lambda i: (0, i),
                                    memory_space=pltpu.VMEM)],
            compiler_params=params,
            interpret=interpret,
            name="advance_leaf",
        )(*operands)
    return pos_out[0, :n], delta[0, :n]


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "max_nbins", "precision", "block_rows",
                     "feat_block", "interpret", "axis_name", "packed_u4",
                     "missing_bin", "cat_rows", "cat_slots"))
def build_hist_pallas(bins_t: jnp.ndarray, gpair: jnp.ndarray,
                      rel_pos: jnp.ndarray, n_nodes: int, max_nbins: int,
                      precision: str = "int8x2", block_rows: int = ROW_BLOCK,
                      feat_block: Optional[int] = None,
                      interpret: bool = False,
                      axis_name=None, packed_u4: int = 0,
                      window: Optional[jnp.ndarray] = None,
                      missing_bin: Optional[int] = None,
                      cat_rows: tuple = (), cat_slots: int = 0):
    """Fused histogram kernel.

    bins_t: [F, n] local bin ids (any int dtype), missing at max_nbins - 1
        — or, with ``packed_u4 = F``, a u4-packed ``[ceil(F/2), n]`` uint8
        page (compressed page transport): nibbles decode in-VMEM inside
        the feature loop, so the packed page is the only HBM copy. A tile
        padded to the row blocks (``fit_rows``) is taken as it is
    gpair: [n, 2] f32
    rel_pos: [n] int32 in [0, n_nodes]; n_nodes means "inactive row"
    axis_name: mesh axis carrying row shards — the int8x2 quantisation
        scale is pmax'd over it so every shard quantises identically and
        N-chip histograms reproduce the 1-chip run bit-for-bit
    window, missing_bin: the refine build of the two-level search
        (int8x2 only). ``window`` is ``[n_nodes, F]`` int32, each node's
        window start in coarse units (``ops/split.py
        choose_refine_window``), ``missing_bin`` the raw tile's missing
        slot; ``bins_t`` holds the RAW ids, and the kernel remaps them in
        VMEM (``_remap_window``): the histogram of ``refine_bin_ids``'
        slots, ``max_nbins`` of them, with no remapped copy in HBM
    cat_rows, cat_slots: the refine build of a matrix with categorical
        columns (``tree/grow.py``): the same sweep also sums the full
        histograms of the tile's rows ``cat_rows``, one slot a category,
        ``cat_slots`` of them (``_make_int8_kernel``)
    -> [n_nodes, F, max_nbins, 2] f32; with ``cat_rows``, that and the
       categorical columns' ``[n_nodes, len(cat_rows), cat_slots, 2]``
    """
    u4 = bool(packed_u4)
    refine = window is not None
    if refine and (u4 or precision != "int8x2" or missing_bin is None):
        raise ValueError("the refine window remaps inside the int8x2 "
                         "kernel only, given the missing slot; the u4 page "
                         "transport never refines")
    if cat_rows and not (refine and cat_slots % 32 == 0):
        raise ValueError("categorical columns ride the refine build only, "
                         "in whole 32-slot tiles")
    F, n = (packed_u4 if u4 else bins_t.shape[0]), gpair.shape[0]
    B, N = max_nbins, n_nodes

    if precision == "bf16x2":
        # two bf16 operand planes + two matmul intermediates: the default
        # 2048-row block busts the 16M scoped-VMEM limit at 256 bins (the
        # feature block can't shrink below 8 — sublane minimum)
        block_rows = min(block_rows, 1024)
    R, n_pad = row_blocks(n, block_rows)
    if feat_block is None:
        if u4 or precision == "int8x2":
            # whole-F feature block when the [F, B, 2N] f32 accumulator
            # fits the VMEM budget: no padding features burn one-hot
            # builds (F=28 pads to 32 at feat_block=8 — a 12.5% tax) and
            # the node-scatter PT4 is built once per ROW block instead of
            # once per (feature block, row block). Budget: the 16M
            # scoped-VMEM limit must also hold the one-hot plane, PT4,
            # double-buffered input blocks and SWAR temporaries — 8M for
            # the accumulator leaves that headroom (a 12M budget OOMed
            # the Mosaic stack at F=136, B=256, N=32: 17.53M > 16M).
            # (The packed transport exists for max_nbins <= 16, so its
            # accumulator is far inside the budget.) Where what the body
            # holds with that block passes the default all the same, the
            # kernel states its limit (``_int8_vmem_bytes``).
            feat_block = max((8 * 2 ** 20) // (B * 2 * N * 4), 8)
        else:
            # f32/bf16 variants stage a [Fb*B, R] scratch — keep it small
            feat_block = 8
    # no body unrolls more than FEATURE_GROUP features (see the constant);
    # a u4 block is whole (32, 128) tiles of bytes
    F_blk = _feature_block(F, min(feat_block, FEATURE_GROUP),
                           step=64 if u4 else 8)
    F_pad = _round_up(F, F_blk)
    note_hist_body_features(F_blk)
    rows_pad = (-(-F_pad // 2) - bins_t.shape[0]) if u4 else F_pad - F
    if rows_pad:
        bins_t = jnp.pad(bins_t, ((0, rows_pad), (0, 0)))
    bins_t = fit_rows(bins_t, n_pad)
    if n_pad != n:
        gpair = jnp.pad(gpair, ((0, n_pad - n), (0, 0)))
        rel_pos = jnp.pad(rel_pos, (0, n_pad - n),
                          constant_values=n_nodes)  # padded rows inactive

    gpair_t = gpair.T                                # [2, n] lane-major
    pos_t = rel_pos.astype(jnp.int32)[None, :]       # [1, n]
    grid = (F_pad // F_blk, n_pad // R)

    # a u4 block's nibble rows are addressed in-kernel: ceil(F_blk/2) bytes
    bins_spec = pl.BlockSpec((-(-F_blk // 2) if u4 else F_blk, R),
                             lambda j, i: (j, i),
                             memory_space=pltpu.VMEM)
    vec2_spec = pl.BlockSpec((2, R), lambda j, i: (0, i),
                             memory_space=pltpu.VMEM)
    pos_spec = pl.BlockSpec((1, R), lambda j, i: (0, i),
                            memory_space=pltpu.VMEM)
    # SWAR one-hot needs every bin id to fit a byte and whole words:
    # matrices with a missing slot (B = 257) or tiny max_bin fall back
    # to the compare build
    packed = precision == "int8x2" and B % 4 == 0 and B <= 256
    block, whole = (_acc_shapes(F_blk, F_pad, B, N) if packed
                    else ((F_blk, B, 2 * N), (F_pad, B, 2 * N)))
    out_spec = pl.BlockSpec(block, lambda j, i: (j,) + (0,) * (len(block) - 1),
                            memory_space=pltpu.VMEM)
    out_shape = _out_struct(whole, jnp.float32, bins_t, gpair_t, pos_t)

    if precision == "int8x2":
        # 15-bit fixed-point with a global per-component scale (reference
        # GradientQuantiser, src/tree/gpu_hist/histogram.cu:55-100)
        with stage("quantise"):
            max_abs = jnp.max(jnp.abs(gpair_t), axis=1)      # [2]
            if axis_name is not None:
                with mesh_scope("scale_pmax"):               # global scale
                    max_abs = jax.lax.pmax(max_abs, axis_name)
            scale = 32512.0 / jnp.maximum(max_abs, 1e-30)    # vs 32767
            q = jnp.round(gpair_t * scale[:, None]).astype(jnp.int32)
        count_hist_onehot("swar" if packed else "compare")
        _note_dot(B, N, packed)
        # the default scoped VMEM holds the body at every shape but the
        # widest; there the kernel states what it holds, with the default's
        # room beside it
        vmem = _int8_vmem_bytes(F_blk, F_pad, B, N, R,
                                bins_spec.block_shape[0],
                                bins_t.dtype.itemsize, packed, refine,
                                len(cat_rows), cat_slots)
        params = _vmem_params("build_hist_int8",
                              None if vmem <= SCOPED_VMEM
                              else vmem + SCOPED_VMEM)
        in_specs, operands, scratch = ([bins_spec, vec2_spec, pos_spec],
                                       [bins_t, q, pos_t], [])
        if refine:
            # the window table [F_pad, N] f32, a feature block's rows a step
            tab = jnp.pad(window.T.astype(jnp.float32),
                          ((0, F_pad - F), (0, 0)))
            in_specs.insert(0, pl.BlockSpec((F_blk, N), lambda j, i: (j, 0),
                                            memory_space=pltpu.VMEM))
            operands.insert(0, tab)
            scratch.append(pltpu.VMEM((F_blk, R), jnp.int32))
        out_shapes, out_specs = out_shape, out_spec
        if cat_rows:
            # a categorical column's (feature block, row in it)
            where = tuple((c // F_blk, c % F_blk) for c in cat_rows)
            cat_shape = (len(cat_rows), cat_slots, 2 * N)
            out_shapes = [out_shape, _out_struct(cat_shape, jnp.float32,
                                                 bins_t, gpair_t, pos_t)]
            out_specs = [out_spec, pl.BlockSpec(
                cat_shape, lambda j, i: (0, 0, 0), memory_space=pltpu.VMEM)]
        with stage("kernel.build_hist_int8"):
            out = pl.pallas_call(
                _make_int8_kernel(F_blk, B, N, R, packed=packed, u4=u4,
                                  refine_missing=missing_bin if refine
                                  else None,
                                  cat_rows=where if cat_rows else (),
                                  cat_slots=cat_slots),
                out_shape=out_shapes,
                grid=grid,
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=scratch,
                compiler_params=params,
                interpret=interpret,
                name="build_hist_int8",
            )(*operands)
        # columns [0:N] hold g-sums, [N:2N] h-sums -> per-component dequant
        with stage("fold"):
            inv = jnp.repeat(1.0 / scale, N)[None, None, :]  # [1, 1, 2N]
            if cat_rows:
                out, cat_out = out
                cat_out = (cat_out * inv).reshape(
                    len(cat_rows), cat_slots, 2, N).transpose(3, 0, 1, 2)
            out = out.reshape(F_pad, B, 2 * N) * inv
    else:
        count_hist_onehot("compare")
        _note_dot(B, N, packed=False)
        _vmem_params("build_hist", None)
        with stage("kernel.build_hist"):
            out = pl.pallas_call(
                _make_kernel(F_blk, B, N, R, precision, u4=u4),
                out_shape=out_shape,
                grid=grid,
                in_specs=[bins_spec, vec2_spec, pos_spec],
                out_specs=out_spec,
                scratch_shapes=[pltpu.VMEM(
                    (F_blk * B, R),
                    jnp.float32 if precision == "f32" else jnp.bfloat16)],
                interpret=interpret,
                name="build_hist",
            )(bins_t, gpair_t, pos_t)

    with stage("fold"):
        out = out[:F]                                # [F, B, 2N]
        gh = out.reshape(F, B, 2, N)                 # split g-part / h-part
        if cat_rows:
            return gh.transpose(3, 0, 1, 2), cat_out
        return gh.transpose(3, 0, 1, 2)              # [N, F, B, 2]
