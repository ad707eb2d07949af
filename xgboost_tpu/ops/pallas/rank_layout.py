"""Rows in and out of the ranking gradient's padded ``[Gp, L]`` layout by
128-lane tiles (``objective/ranking.py``).

The rows are listed group by group, so a group's rows are one run that
starts at lane ``shift`` of tile ``first`` of the rows viewed as
``[tiles, 128]``, and its L slots lie in the W tiles from there (W =
``window_tiles(L)``). A grid step takes ``GROUPS`` consecutive groups; their
rows lie in the S tiles (``span_tiles(L)``) from the step's first group's
first tile on.

``rows_in``: a step copies its S tiles into VMEM with one DMA, started a
step ahead; each group's window is read at its own tile, its lanes rotated
``shift`` to the left (a lane that leaves a tile is taken from the next
one) and masked past the group's size.

``rows_out``: a step masks each group's padded row past its size, rotates
it ``shift`` lanes to the right and adds it into a span of S tiles at the
group's tile: the span holds one value a lane, since the groups' rows do
not overlap. The tile where the next step's first group starts is carried
into the next step's span, and each span goes back by one DMA, in
ascending order, each waited before the next starts: the last write of a
tile is that of the step whose span holds all of its rows.

A group longer than ``PIECE`` slots moves as pieces of ``PIECE`` slots,
each a group of its own to the kernels (``_pieces``), so what a step holds
in VMEM does not grow with the longest group.

Both give, bit for bit, what a slice of L rows a group and a gather of one
element a row give (a zero's sign aside; ``tests/test_objectives.py`` keeps
those forms as the reference). The caller says whether they run in the
Pallas interpreter (``interpret``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128
GROUPS = 64      # groups a grid step (a multiple of 8): at the ranking
                 # cell's shape on a v5e 64 took 0.85 ms in and 2.03 out
                 # unrolled, 32 took 0.92 and 2.22 (PERF.md §6)
PIECE = 4096     # the most slots a kernel moves as one group: a step then
                 # holds at most 4.4 MB of VMEM buffers (two spans of
                 # GROUPS x PIECE rows, two blocks of GROUPS x PIECE slots),
                 # under the default 16 MiB whatever the longest group


def window_tiles(L: int) -> int:
    """W, the tiles a group's L slots span from any lane."""
    return -(-(L + LANES - 1) // LANES)


def span_tiles(L: int) -> int:
    """S, the tiles a step's ``GROUPS`` groups of at most L rows span from
    the first one's tile, with a whole window past the last one's."""
    return -(-(GROUPS * L + LANES - 1) // LANES) + window_tiles(L)


def _pieces(first, shift, sizes, L):
    """The ``[Gp]`` tables of groups of up to L slots as those of groups of
    up to ``PIECE``: ``[Gp x P]`` (P = ceil(L / PIECE)), group by group.
    Piece c of group g holds its rows from slot ``c x PIECE`` on, and none
    past its size (it then starts where the next group does), so the
    pieces' rows ascend as the groups' do. Returns the tables and the
    pieces' slots; L <= PIECE leaves the tables as they are."""
    if L <= PIECE:
        return first, shift, sizes, L
    off = jnp.arange(-(-L // PIECE), dtype=jnp.int32) * PIECE
    start = (first * LANES + shift)[:, None] + jnp.minimum(off,
                                                           sizes[:, None])
    size = jnp.clip(sizes[:, None] - off, 0, PIECE)
    return ((start // LANES).reshape(-1), (start % LANES).reshape(-1),
            size.reshape(-1), PIECE)


def _steps(first, shift, sizes, T):
    """The per-group tables padded up to whole steps: the groups added start
    past the last row (tile T) and hold none, so the steps' tiles ascend."""
    Gp = sizes.shape[0]
    pad = -Gp % GROUPS
    return (jnp.pad(first, (0, pad), constant_values=T),
            jnp.pad(shift, (0, pad)), jnp.pad(sizes, (0, pad)))


def _lanes(W):
    """Each element's lane and its slot in a ``[W, 128]`` window."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (W, LANES), 1)
    return lane, jax.lax.broadcasted_iota(jnp.int32, (W, LANES), 0) * LANES \
        + lane


def _eights(body, then=lambda k: None) -> None:
    """``body(k, i)`` for the step's groups ``8 k + i``, then ``then(k)``: a
    loop over the step's blocks of eight groups, each block unrolled, so
    the chip's compiler overlaps the groups' loads and stores while the
    traced body stays an eighth of a step."""
    def block(k, carry):
        for i in range(8):
            body(k, i)
        then(k)
        return carry

    jax.lax.fori_loop(0, GROUPS // 8, block, 0)


def _pallas():
    """Pallas, imported where a kernel is built: ``import xgboost_tpu``
    (which registers the ranking objectives) does not pay for it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl, pltpu


def _call(kernel, out_shape, grid, in_specs, out_specs, scratch, name,
          interpret, *args):
    pl, pltpu = _pallas()
    return pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(args) - 1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name=name)(*args)


def rows_in(rows, first, shift, sizes, *, L: int, fill, interpret: bool):
    """``rows [n]`` -> ``[Gp, L]``: group g's rows in its first slots,
    ``fill`` past its size."""
    n, Gp = rows.shape[0], sizes.shape[0]
    first, shift, sizes, Lp = _pieces(first, shift, sizes, L)
    P = sizes.shape[0] // Gp
    W, S, Lt, T = window_tiles(Lp), span_tiles(Lp), -(-Lp // LANES), \
        -(-n // LANES)
    tiled = jnp.pad(rows, (0, (T + S) * LANES - n)).reshape(T + S, LANES)
    first, shift, sizes = _steps(first, shift, sizes, T)
    pl, pltpu = _pallas()

    def kernel(first_ref, shift_ref, size_ref, x_hbm, o_ref, span, eight,
               sem):
        b, slot = pl.program_id(0), pl.program_id(0) % 2

        def copy(step, sl):
            return pltpu.make_async_copy(
                x_hbm.at[pl.ds(first_ref[step * GROUPS], S)], span.at[sl],
                sem.at[sl])

        @pl.when(b == 0)
        def _():
            copy(0, 0).start()

        @pl.when(b + 1 < pl.num_programs(0))
        def _():
            copy(b + 1, 1 - slot).start()

        copy(b, slot).wait()
        base = first_ref[b * GROUPS]
        lane, pos = _lanes(W)

        def group(k, i):
            g = b * GROUPS + k * 8 + i
            o = shift_ref[g]
            rot = pltpu.roll(span[slot, pl.ds(first_ref[g] - base, W)],
                             (LANES - o) % LANES, axis=1)
            win = jnp.where(lane < LANES - o, rot,
                            pltpu.roll(rot, W - 1, axis=0))
            win = jnp.where(pos < size_ref[g], win, fill)
            for j in range(Lt):
                eight[pl.ds(i, 1), pl.ds(j * LANES, LANES)] = win[j:j + 1]

        def store(k):   # rows stored at a dynamic index: eight at a time
            o_ref[pl.ds(pl.multiple_of(k * 8, 8), 8)] = eight[...]

        _eights(group, store)

    Gk = sizes.shape[0]
    out = _call(kernel, jax.ShapeDtypeStruct((Gk, Lt * LANES), rows.dtype),
                (Gk // GROUPS,), [pl.BlockSpec(memory_space=pl.ANY)],
                pl.BlockSpec((GROUPS, Lt * LANES), lambda b, *_: (b, 0)),
                [pltpu.VMEM((2, S, LANES), rows.dtype),
                 pltpu.VMEM((8, Lt * LANES), rows.dtype),
                 pltpu.SemaphoreType.DMA((2,))],
                "rank_rows_in", interpret, first, shift, sizes, tiled)
    return out[:Gp * P].reshape(Gp, P * Lt * LANES)[:, :L]


def rows_out(pad, first, shift, sizes, *, n: int, interpret: bool):
    """``rows_in`` backwards: ``pad [Gp, L]`` -> ``[n]`` rows; what a slot
    past its group's size holds reaches no row."""
    Gp, L = pad.shape
    first, shift, sizes, Lp = _pieces(first, shift, sizes, L)
    P = sizes.shape[0] // Gp
    W, S, Lt, T = window_tiles(Lp), span_tiles(Lp), -(-Lp // LANES), \
        -(-n // LANES)
    first, shift, sizes = _steps(first, shift, sizes, T)
    Gk = sizes.shape[0]
    nxt = jnp.concatenate([first[GROUPS::GROUPS], first[-1:]])
    x = jnp.pad(pad, ((0, 0), (0, P * Lt * LANES - L))).reshape(
        Gp * P, Lt * LANES)
    x = jnp.pad(x, ((0, Gk - Gp * P), (0, 0)))
    pl, pltpu = _pallas()

    def kernel(first_ref, shift_ref, size_ref, next_ref, x_ref, o_hbm, span,
               carry, sem):
        b, slot = pl.program_id(0), pl.program_id(0) % 2
        base = first_ref[b * GROUPS]

        @pl.when(b == 0)
        def _():
            carry[...] = jnp.zeros_like(carry)

        span[slot] = jnp.zeros((S, LANES), span.dtype)
        span[slot, pl.ds(0, 1)] = carry[...]
        lane, pos = _lanes(W)

        def group(k, i):
            g = b * GROUPS + k * 8 + i
            o = shift_ref[g]
            win = jnp.concatenate(
                [x_ref[k * 8 + i], jnp.zeros((W - Lt, LANES), x_ref.dtype)])
            rot = pltpu.roll(jnp.where(pos < size_ref[g], win, 0.0), o,
                             axis=1)
            at = pl.ds(first_ref[g] - base, W)
            span[slot, at] = span[slot, at] + jnp.where(
                lane >= o, rot, pltpu.roll(rot, 1, axis=0))

        _eights(group)
        carry[...] = span[slot, pl.ds(next_ref[b] - base, 1)]

        def write(step, sl):
            return pltpu.make_async_copy(
                span.at[sl], o_hbm.at[pl.ds(first_ref[step * GROUPS], S)],
                sem.at[sl])

        @pl.when(b > 0)
        def _():
            write(b - 1, 1 - slot).wait()

        write(b, slot).start()

        @pl.when(b + 1 == pl.num_programs(0))
        def _():
            write(b, slot).wait()

    out = _call(kernel, jax.ShapeDtypeStruct((T + S, LANES), pad.dtype),
                (Gk // GROUPS,),
                [pl.BlockSpec((GROUPS, Lt, LANES), lambda b, *_: (b, 0, 0))],
                pl.BlockSpec(memory_space=pl.ANY),
                [pltpu.VMEM((2, S, LANES), pad.dtype),
                 pltpu.VMEM((1, LANES), pad.dtype),
                 pltpu.SemaphoreType.DMA((2,))],
                "rank_rows_out", interpret, first, shift, sizes, nxt,
                x.reshape(Gk, Lt, LANES))
    return out.reshape(-1)[:n]
