"""Candidate forms of the fixed-point histogram dot, for
``tools/bench_hist_groups.py --forms``.

``ops/pallas/histogram.py`` ships the form that won on the chip (PERF.md
section 6, PR 37: ``stacked``, ``dense``, eight features a dot); all of them
live here so that the table can be measured again, ``held`` with ``dense``
among them, which is ahead under 32 nodes and not shipped (ROADMAP.md A3). One kernel over a (feature block, row block) grid, the build of
``_make_int8_kernel(packed=True)`` word for word up to the dot: the SWAR
one-hot, the ``PT4`` node-scatter operand, float32 accumulation across row
blocks in the same order, so every form's histogram equals
``build_hist_pallas``'s bit for bit. What differs is how a body's one-hots
meet ``PT4`` on the MXU:

- ``stacked``: G features' one-hots as one ``[G*S, R]`` int8 operand, one
  dot a group, ``OH . PT4^T -> [G*S, 4N]``: a ``PT4`` tile the MXU holds
  serves G*S streamed rows, not one feature's B.
- ``held``: the same operand on the other side, ``PT4 . OH^T -> [4N, G*S]``:
  the MXU holds the one-hot's tiles and streams the 4N node rows.

and how a feature's B slots lie in the stacked operand (``pack``):

- ``pad``: S = B rounded up to whole uint32 sublane tiles (20 -> 32,
  36 -> 64): a feature's words are whole vregs, the stack is an aligned
  concatenation, the rows past B are zero one-hots and are dropped outside;
- ``dense``: S = B: the group's words are built vreg by vreg, each vreg's
  sublanes selecting among the two or three features whose words it holds,
  so the SWAR chain and the MXU see no padding.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xgboost_tpu.ops.pallas.histogram import (_CONTRACT_LAST, _feature_block,
                                              _round_up)

FORMS = ("stacked", "held")
PACKS = ("pad", "dense")


def slots(B: int, pack: str) -> int:
    return _round_up(B, 32) if pack == "pad" else B


def _swar(x):
    M7F = jnp.uint32(0x7F7F7F7F)
    return (~(((x & M7F) + M7F) | x | M7F)) >> jnp.uint32(7)


def _make_kernel(Fb, B, N, R, form, pack, G):
    S = slots(B, pack)
    W = S // 4                                  # uint32 words a feature

    def kernel(bins_ref, q_ref, pos_ref, out_ref):
        i = pl.program_id(1)

        @pl.when(i == 0)
        def _():
            out_ref[:] = jnp.zeros_like(out_ref)

        pos_row = pos_ref[:]
        node_iota = jax.lax.broadcasted_iota(jnp.int32, (N, R), 0)
        on_node = pos_row == node_iota
        zero = jnp.zeros((N, R), jnp.int32)

        def planes(row):
            PTq = jnp.where(on_node, jnp.broadcast_to(row, (N, R)), zero)
            hi = (PTq + 128) >> 8
            lo = PTq - hi * 256
            return hi.astype(jnp.int8), lo.astype(jnp.int8)

        g_hi, g_lo = planes(q_ref[0:1, :])
        h_hi, h_lo = planes(q_ref[1:2, :])
        PT4 = jnp.concatenate([g_hi, h_hi, g_lo, h_lo], axis=0)  # [4N, R]

        sub = jax.lax.broadcasted_iota(jnp.uint32, (8, R), 0)

        def spread(f):                          # [1, R] u32, the id in 4 bytes
            return bins_ref[f:f + 1, :].astype(jnp.uint32) * jnp.uint32(
                0x01010101)

        def words(f0, g):
            """The SWAR one-hot words of features f0 .. f0+g-1, [V*8, R]
            uint32, V whole vregs of sublanes."""
            vregs = []
            for v in range(-(-g * W // 8)):
                w0 = 8 * v                       # first word of this vreg
                at = sub + jnp.uint32(w0)        # word index in the group
                base = (at % jnp.uint32(W)) * jnp.uint32(4)
                K4 = base * jnp.uint32(0x01010101) + jnp.uint32(0x03020100)
                a, b = w0 // W, min((w0 + 7) // W, g - 1)
                ids = jnp.broadcast_to(spread(f0 + b), (8, R))
                for f in range(b - 1, a - 1, -1):
                    ids = jnp.where(at < jnp.uint32((f + 1) * W),
                                    jnp.broadcast_to(spread(f0 + f), (8, R)),
                                    ids)
                vregs.append(_swar(K4 ^ ids))
            return vregs[0] if len(vregs) == 1 else jnp.concatenate(vregs, 0)

        for f0 in range(0, Fb, G):
            g = min(G, Fb - f0)
            oh = pltpu.bitcast(words(f0, g), jnp.int8)     # [V*32, R]
            rows = g * S
            if form == "stacked":
                acc4 = jax.lax.dot_general(
                    oh, PT4, _CONTRACT_LAST,
                    preferred_element_type=jnp.int32)      # [V*32, 4N]
                val = (acc4[:, :2 * N].astype(jnp.float32) * 256.0
                       + acc4[:, 2 * N:].astype(jnp.float32))
                out_ref[f0 * S:f0 * S + rows, :] += val[:rows]
            else:
                acc4 = jax.lax.dot_general(
                    PT4, oh, _CONTRACT_LAST,
                    preferred_element_type=jnp.int32)      # [4N, V*32]
                val = (acc4[:2 * N].astype(jnp.float32) * 256.0
                       + acc4[2 * N:].astype(jnp.float32))
                out_ref[:, f0 * S:f0 * S + rows] += val[:, :rows]

    return kernel


@functools.partial(jax.jit, static_argnames=(
    "n_nodes", "max_nbins", "form", "pack", "group", "block_rows",
    "feat_block", "interpret"))
def hist_form(bins_t, gpair, rel_pos, n_nodes, max_nbins, *, form, pack,
              group, block_rows=2048, feat_block=None, interpret=False):
    """``build_hist_pallas(precision="int8x2")`` under another dot form ->
    [n_nodes, F, max_nbins, 2] float32, the same bits."""
    F, n = bins_t.shape
    B, N = max_nbins, n_nodes
    if form not in FORMS or pack not in PACKS or B % 4:
        raise ValueError((form, pack, B))
    S = slots(B, pack)
    if form == "held" and (group * S) % 128:
        raise ValueError("a held group is whole 128-lane tiles")
    R = min(block_rows, max(_round_up(n, 128), 128))
    n_pad = _round_up(max(n, R), R)
    if feat_block is None:
        feat_block = max((8 * 2 ** 20) // (S * 2 * N * 4), 8)
    step = 8 * group // math.gcd(8, group)
    F_blk = _feature_block(F, min(feat_block, 256) // step * step, step=step)
    F_pad = _round_up(F, F_blk)
    bins_t = jnp.pad(bins_t, ((0, F_pad - F), (0, n_pad - n)))
    gpair = jnp.pad(gpair, ((0, n_pad - n), (0, 0)))
    rel_pos = jnp.pad(rel_pos, (0, n_pad - n), constant_values=N)
    gpair_t = gpair.T
    max_abs = jnp.max(jnp.abs(gpair_t), axis=1)
    scale = 32512.0 / jnp.maximum(max_abs, 1e-30)
    q = jnp.round(gpair_t * scale[:, None]).astype(jnp.int32)
    pos_t = rel_pos.astype(jnp.int32)[None, :]
    if form == "stacked":
        out_shape, out_block = (F_pad * S, 2 * N), (F_blk * S, 2 * N)
        out_map = lambda j, i: (j, 0)                       # noqa: E731
    else:
        out_shape, out_block = (2 * N, F_pad * S), (2 * N, F_blk * S)
        out_map = lambda j, i: (0, j)                       # noqa: E731
    out = pl.pallas_call(
        _make_kernel(F_blk, B, N, R, form, pack, group),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        grid=(F_pad // F_blk, n_pad // R),
        in_specs=[pl.BlockSpec((F_blk, R), lambda j, i: (j, i),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((2, R), lambda j, i: (0, i),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, R), lambda j, i: (0, i),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(out_block, out_map, memory_space=pltpu.VMEM),
        interpret=interpret,
        name="hist_form",
    )(bins_t, q, pos_t)
    if form == "held":
        out = out.T                                         # [F_pad*S, 2N]
    out = out.reshape(F_pad, S, 2 * N)[:F, :B]
    out = out * jnp.repeat(1.0 / scale, N)[None, None, :]
    return out.reshape(F, B, 2, N).transpose(3, 0, 1, 2)

