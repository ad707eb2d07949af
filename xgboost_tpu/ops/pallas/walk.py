"""Pallas TPU variant of the packed-forest walk (experimental, no caller).

``ops/walk.py`` lets XLA schedule the level-synchronous walk; this
kernel instead pins the whole packed node pool (words + value plane)
in VMEM once and streams row blocks through it on a 1-D grid — the
gather-heavy walk then never re-reads node state from HBM between
levels, which is the same residency argument the histogram kernel
makes for its accumulator. The leaf→group reduction stays a single
``[R, T] @ [T, G]`` MXU dot per block.

Scope (why nothing routes to it; ROADMAP C3 decides whether it stays):

- **no categorical splits** — the bitset gather would need a second
  VMEM-resident pool; callers with ``has_cat`` packs must stay on
  ``walk_packed`` (the wrapper enforces this);
- the node pool must FIT in VMEM (~16 MB ⇒ ≲1M nodes for the two f32
  planes); the wrapper raises past that rather than silently spilling;
- it does not compile: on jax 0.9.0 the per-level ``words[idx]`` gather
  fails Pallas' TPU lowering with ``NotImplementedError: Only 2D gather
  is supported`` (tried compiled for v5e, PR 21). Only interpret mode
  runs, and callers ask for it explicitly (``interpret=True``,
  tests/test_packed.py).

Parity: same node-word layout (``serve/packed.py`` constants), same
NaN→default routing, same HIGHEST-precision leaf dot as the reference
walk — tests/test_packed.py compares it row-for-row against
``walk_packed`` in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...serve.packed import _field_layout

# rows per grid step: one (8, 128)-aligned block of the batch
BLOCK_ROWS = 128

# two f32/u32 planes of the node pool must sit in VMEM together with
# the per-block row state; stay well under the ~16 MB budget
MAX_VMEM_NODES = 1 << 20


def _walk_kernel(words_ref, values_ref, offs_ref, tw_ref, oh_ref,
                 x_ref, base_ref, out_ref, *, max_depth: int, lay: dict):
    X = x_ref[...]                               # [R, F] block in VMEM
    words = words_ref[...]                       # [N] resident pool
    values = values_ref[...]
    R = X.shape[0]
    T = offs_ref.shape[0]
    idx = jnp.zeros((R, T), jnp.int32) + offs_ref[...][None, :]
    for _ in range(max_depth):
        w = words[idx]                           # [R, T] gather
        leaf = (w & lay["leaf_bit"]) != 0
        dl = (w & lay["dl_bit"]) != 0
        feat = ((w >> lay["feat_shift"])
                & lay["feat_mask"]).astype(jnp.int32)
        delta = (w & lay["off_mask"]).astype(jnp.int32)
        x = jnp.take_along_axis(X, feat, axis=1)
        go_right = jnp.where(jnp.isnan(x), ~dl, x > values[idx])
        nxt = idx + delta + go_right.astype(jnp.int32)
        idx = jnp.where(leaf, idx, nxt)
    leaf_v = values[idx] * tw_ref[...][None, :]
    out_ref[...] = jnp.dot(
        leaf_v, oh_ref[...],
        precision=jax.lax.Precision.HIGHEST) + base_ref[...][None, :]


@functools.partial(
    jax.jit, static_argnames=("max_depth", "interpret", "block_rows"))
def _walk_pallas(words, values, tree_offsets, tree_weight, group_onehot,
                 X, base, *, max_depth: int, interpret: bool,
                 block_rows: int):
    n, _ = X.shape
    G = group_onehot.shape[1]
    pad = (-n) % block_rows
    if pad:
        X = jnp.pad(X, ((0, pad), (0, 0)))
    grid = (X.shape[0] // block_rows,)
    kern = functools.partial(_walk_kernel, max_depth=max_depth,
                             lay=_field_layout())
    out = pl.pallas_call(
        kern,
        name="forest_walk",
        grid=grid,
        in_specs=[
            pl.BlockSpec(words.shape, lambda i: (0,)),     # resident
            pl.BlockSpec(values.shape, lambda i: (0,)),
            pl.BlockSpec(tree_offsets.shape, lambda i: (0,)),
            pl.BlockSpec(tree_weight.shape, lambda i: (0,)),
            pl.BlockSpec(group_onehot.shape, lambda i: (0, 0)),
            pl.BlockSpec((block_rows, X.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec(base.shape, lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, G), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((X.shape[0], G), jnp.float32),
        interpret=interpret,
    )(words, values, tree_offsets, tree_weight, group_onehot, X, base)
    return out[:n]


def walk_packed_pallas(pf, X, base, *, interpret: bool = False,
                       block_rows: int = BLOCK_ROWS):
    """Margin of a packed forest via the Pallas kernel. ``pf`` is a
    :class:`~...serve.packed.PackedForest`; raises for categorical
    packs and pools past the VMEM budget (use ``walk_packed``)."""
    if pf.has_cat:
        raise ValueError("pallas walk does not support categorical "
                         "splits; use ops.walk.walk_packed")
    if pf.words.shape[0] > MAX_VMEM_NODES:
        raise ValueError(
            f"node pool of {pf.words.shape[0]} exceeds the VMEM-resident "
            f"budget ({MAX_VMEM_NODES}); use ops.walk.walk_packed")
    d = pf.device_arrays()
    return _walk_pallas(
        d["words"], d["values"], d["tree_offsets"], d["tree_weight"],
        d["group_onehot"], jnp.asarray(X, jnp.float32),
        jnp.asarray(np.asarray(base, np.float32)),
        max_depth=pf.max_depth, interpret=interpret,
        block_rows=block_rows)
