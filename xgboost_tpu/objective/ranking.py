"""LambdaRank objectives: rank:ndcg, rank:map, rank:pairwise.

Reference: ``src/objective/lambdarank_obj.cc:44-160,620-628`` + caches in
``src/common/ranking_utils.h`` and the CUDA pair kernels in
``src/objective/lambdarank_obj.cu``. Per query group, pairs (i, j) with
label_i > label_j get the RankNet lambda scaled by the metric delta
(|ΔNDCG| / |ΔMAP| / 1). Pair generation follows the reference's two modes:
``mean`` (k random pairs per doc) and ``topk`` (every pair whose
better-ranked doc is inside the truncation, each pair once).

All three objectives run ON DEVICE in both pair modes: groups pad into a
``[G, L]`` matrix (L = longest group), chunked over groups by ``lax.map``
to bound memory — the TPU answer to the reference's per-pair CUDA kernels.
``topk`` (top-k docs × the docs ranked below them, deterministic) works in
RANK order: one sort a chunk puts scores, labels and slots by (score,
slot), the pair interaction is a ``[C, K, L]`` VPU tensor over the sorted
rows (K = min(truncation, L) anchors a group; ``[C, L, L]`` with no
truncation), and one sort by slot returns the sums: no per-row gather
inside the loop. ``mean`` (the default, matching the reference: k uniform
out-of-label-bucket rivals per doc, ``lambdarank_obj.h:231-275``) keeps
slot order, per-group ranks from two stable argsorts and a sampled
``[C, L, k]`` tensor. MAP's |ΔAP| rides the same kernels via rank-ordered
prefix statistics (``_map_prefix_ranked``/``_map_swap_delta``). The
per-group numpy loop remains as the oracle/fallback, forced with
XTPU_RANK_HOST=1.

Deliberate recipe difference from the reference implementation: lambdas
follow the LambdaMART paper exactly (lam = -sigmoid * |delta|), WITHOUT
the reference's extra empirical scalings — the per-pair
``delta /= (|s_i - s_j| + 0.01)`` division, the hessian x2, and the
per-group ``log2(1+sum_lambda)/sum_lambda`` normalization borrowed from
LightGBM (``lambdarank_obj.h:112-126``, ``lambdarank_obj.cc:178-231``).
Quality against the reference implementation on a public LETOR set: not
measured. Measured (PERF.md, cell ``istella-letor.train``, one v5e chip,
7,325,625 x 220, 23,219 groups): gradients, trees and ``ndcg@10`` against a
plain LambdaMART reference of this recipe (``benchmark/lib/
reference_rank.py``). The paper recipe keeps the device kernels
branch-free. ``lambdarank_unbiased``
implements the same eq. 30/31 bias estimation the reference does, ON
DEVICE for both pair methods (``_debias_dev``; the ti+/tj- vectors live
on the host in f64 for the normalize/damp update and serialization, as
the reference keeps them in its objective config).
"""

from __future__ import annotations

import functools
import os
import time
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import trace as obs_trace
from ..obs.metrics import count_rank_gradient
from ..obs.trace import rank_scope, stage
from ..registry import OBJECTIVES
from .base import ObjInfo, Objective


def _dcg_discount(ranks: np.ndarray) -> np.ndarray:
    return 1.0 / np.log2(ranks + 2.0)  # ranks are 0-based


def _gains(labels: np.ndarray, exp_gain: bool) -> np.ndarray:
    return (np.power(2.0, labels) - 1.0) if exp_gain else labels


def _bucket_stats(y: np.ndarray):
    """Label-bucket statistics for mean pair sampling (the reference's
    rival mapping, ``lambdarank_obj.h`` MakePairs): returns (order,
    n_lefts, n_geq) where ``order`` lists doc indices in stable
    label-descending order, ``n_lefts[i]`` counts docs with a strictly
    higher label than doc i, and ``n_geq[i]`` counts at-least-as-high.
    INVARIANT shared with the vectorized device build (``_mean_stats``):
    both define the mapping purely by these tie-insensitive counts plus a
    stable label-descending argsort, so the host and device samplers draw
    from the same rival distribution."""
    order = np.argsort(-y, kind="stable")
    ys = y[order]
    n_lefts = np.searchsorted(-ys, -y, side="left")
    n_geq = np.searchsorted(-ys, -y, side="right")
    return order, n_lefts, n_geq


def _map_prefix(yp, vp, order, L):
    """Per-group MAP prefix statistics in current rank order, from slot-order
    labels and the rank order's slots (the ``mean`` kernel's way in)."""
    yb = ((yp > 0) & vp).astype(jnp.float32)
    return _map_prefix_ranked(jnp.take_along_axis(yb, order, axis=1), L)


def _map_prefix_ranked(rel_rank, L):
    """C_k (relevant count in top k+1), T0 (shifted cumsum of rel/(rank+1);
    T0[k] == T[k-1], T0[0] == 0) and R (total relevant, floored at 1) of the
    ``[C, L]`` relevance in rank order — the device mirror of the host
    ``LambdaRankMAP._delta`` precomputation."""
    Ck = jnp.cumsum(rel_rank, axis=1)
    T = jnp.cumsum(rel_rank / (jnp.arange(L, dtype=jnp.float32) + 1.0),
                   axis=1)
    T0 = jnp.concatenate([jnp.zeros((T.shape[0], 1), T.dtype), T], axis=1)
    R = jnp.maximum(Ck[:, -1], 1.0)
    return Ck, T0, R


def _padded_layout(s, y, starts, sizes, *, n_groups, chunk, L):
    """Scores and labels as ``[Gp, L]`` (Gp: the groups padded up to whole
    chunks), the mask of real slots and the group sizes ``[Gp]``.
    ``_device_layout`` lists the rows group by group, so group g's slots are
    the L rows from ``starts[g]`` on, masked past its size: one gather of
    whole slices, where a scatter of single rows costs the device a sort of
    all rows and leaves ops that carry no scope."""
    Gp = -(-n_groups // chunk) * chunk
    first = jnp.zeros((Gp,), jnp.int32).at[:n_groups].set(starts)
    sz = jnp.zeros((Gp,), jnp.int32).at[:n_groups].set(
        sizes.astype(jnp.int32))
    valid = jnp.arange(L, dtype=jnp.int32)[None, :] < sz[:, None]

    def padded(rows, fill):
        ext = jnp.concatenate([rows, jnp.zeros((L,), rows.dtype)])
        block = jax.vmap(
            lambda p: jax.lax.dynamic_slice(ext, (p,), (L,)))(first)
        return jnp.where(valid, block, fill)

    return Gp, padded(s, -jnp.inf), padded(y, 0.0), valid, sz


def _ranknet_dev(s_i, s_j, a_is_i, delta, mask):
    """RankNet lambda/hessian from oriented score differences — the ONE
    device encoding of the clip bound (50) and hessian floor (1e-16) the
    host loop uses, shared by the topk and mean kernels. Also returns the
    oriented sigmoid ``p`` (the unbiased path's pair-cost input)."""
    sij = jnp.where(a_is_i, s_i - s_j, s_j - s_i)
    p = 1.0 / (1.0 + jnp.exp(jnp.clip(sij, -50.0, 50.0)))
    lam = jnp.where(mask, -p * delta, 0.0)
    hes = jnp.where(mask, jnp.maximum(p * (1.0 - p) * delta, 1e-16), 0.0)
    return lam, hes, p


def _debias_dev(lam, hes, p, delta, mask, a_is_i, i_pos, j_pos, ti, tj,
                kpos):
    """Unbiased-LambdaMART position debiasing for a device pair tensor
    (reference ``lambdarank_obj.h:121-141`` + ``.cu``): scale each pair's
    lambda/hessian by 1/(ti+[pos_i] * tj-[pos_j]) where pos_* index the
    INPUT (presentation) order, and accumulate the per-position pair costs
    that drive the post-iteration bias update. Positions >= kpos (or with
    a zero bias estimate — the reference's Eps64 gate) pass through
    unscaled and unaccumulated. Returns (lam, hes, cost/tmj, cost/tpi,
    ok) with the cost terms zeroed outside ``ok``."""
    tpi = ti[jnp.minimum(i_pos, kpos - 1)]
    tmj = tj[jnp.minimum(j_pos, kpos - 1)]
    return _debias_scaled(lam, hes, p, delta,
                          mask & (i_pos < kpos) & (j_pos < kpos), tpi, tmj)


def _debias_scaled(lam, hes, p, delta, mask, tpi, tmj):
    """``_debias_dev`` past its lookups: ``tpi`` / ``tmj`` are each pair's
    ti+[pos_i] / tj-[pos_j], ``mask`` the pairs with both positions tracked.
    The gate threshold is the HOST loop's float64 eps (not f32 tiny): a
    bias estimate below it must be EXCLUDED, not divided by — dividing by
    ~1e-20 in f32 overflows the lambdas where the reference trains
    normally."""
    eps = jnp.float32(np.finfo(np.float64).eps)
    ok = mask & (tpi >= eps) & (tmj >= eps)
    scale = jnp.where(ok, tpi * tmj, 1.0)
    lam = lam / scale
    hes = hes / scale
    cost = jnp.where(ok, jnp.log(1.0 / jnp.maximum(p, 1e-30)) * delta, 0.0)
    return lam, hes, cost / jnp.maximum(tmj, eps), \
        cost / jnp.maximum(tpi, eps), ok


def _delta_dev(objective, *, yp, vp, order, L, gv, dv, inv_idcg,
               gj, dj, rank_i, rank_j, a_is_i):
    """Metric delta for a gathered pair tensor in slot order — the ``mean``
    kernel's 3-way dispatch (|ΔNDCG| / |ΔMAP| / 1; ``topk`` states its own
    in rank coordinates, where nothing is gathered); ``gj``/``dj``/
    ``rank_j`` arrive already gathered to the pair shape."""
    if objective == "pairwise":
        return jnp.float32(1.0)
    if objective == "map":
        Ck, T0, R = _map_prefix(yp, vp, order, L)
        return _map_delta_dev(rank_i, rank_j, a_is_i, Ck, T0, R)
    return jnp.abs((gv[:, :, None] - gj) * (dv[:, :, None] - dj)) \
        * inv_idcg[:, None, None]


def _map_delta_dev(rank_i, rank_j, a_is_i, Ck, T0, R):
    """|ΔAP| for swapping the (oriented-relevant) doc i with doc j, the
    prefix statistics gathered at the pair's two ranks."""
    r_rel = jnp.where(a_is_i, rank_i, rank_j)
    r_irr = jnp.where(a_is_i, rank_j, rank_i)
    u = jnp.minimum(r_rel, r_irr)
    v = jnp.maximum(r_rel, r_irr)
    shape = u.shape
    Cc = shape[0]

    def g2(A, idx):
        return jnp.take_along_axis(A, idx.reshape(Cc, -1),
                                   axis=1).reshape(shape)

    return _map_swap_delta(
        u, v, r_rel < r_irr, Cu=g2(Ck, u), Cv=g2(Ck, v), Tv1=g2(T0, v),
        Tu=g2(T0, u + 1), Tu1=g2(T0, u), R=R)


def _map_swap_delta(u, v, rel_above, *, Cu, Cv, Tv1, Tu, Tu1, R):
    """|ΔAP| of swapping the docs at ranks u < v, the relevant one above
    where ``rel_above`` — the device mirror of the host formula (binary
    relevance). C* = C_k at u / v; Tv1, Tu, Tu1 = T[v-1], T[u], T[u-1];
    everything broadcastable to the ``[C, ., .]`` pair shape."""
    uf = u.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    d_down = Cv / (vf + 1.0) - Cu / (uf + 1.0) - (Tv1 - Tu)
    d_up = (Cu + 1.0) / (uf + 1.0) - Cv / (vf + 1.0) + (Tv1 - Tu1)
    return jnp.abs(jnp.where(rel_above, d_down, d_up)) / R[:, None, None]


def _topk_anchors(kcap: int, L: int) -> int:
    """K, the rows of a group that can be a pair's better-ranked row under
    ``topk``: the truncation, or all L of the padded group without one."""
    return L if kcap == 0 else min(kcap, L)


@functools.partial(
    jax.jit,
    static_argnames=("kcap", "L", "exp_gain", "objective", "chunk",
                     "n_groups", "kpos"))
@stage("gradient")
def _lambda_grad_device(s, y, qidx, slot, starts, sizes, w_row, ti=None,
                        tj=None, *,
                        kcap, L, exp_gain, objective, chunk, n_groups,
                        kpos=0):
    """Truncated all-pairs LambdaRank lambdas over padded [G, L] groups.

    Exactly the host loop's math (orientation, RankNet clip, 1e-16 hessian
    floor) in f32. Every unordered pair of rows with different labels
    counts ONCE, from its better-ranked row, and only where that row is
    currently ranked < ``kcap`` (the reference's ``MakePairs`` rule for
    ``topk``: ``for i < k: for j > i``); ``kcap`` = 0 means no truncation.
    The same pairs as ``_pairs``.

    A chunk works in RANK coordinates: one sort puts its rows by score
    (ties by slot, as the host's stable argsort), so a row's rank is its
    position, its discount a column of the ``[L]`` table, the anchors the
    first K (``_topk_anchors``) columns and the pair block ``[C, K, L]``;
    one sort by slot returns the sums. No gather inside the chunk loop.
    """
    K = _topk_anchors(kcap, L)
    with rank_scope("layout"):
        Gp, s_pad, y_pad, _valid, sz = _padded_layout(
            s, y, starts, sizes, n_groups=n_groups, chunk=chunk, L=L)
        kc = sz if kcap == 0 else jnp.minimum(kcap, sz)
        pos = jnp.arange(L, dtype=jnp.int32)
        disc = 1.0 / jnp.log2(pos.astype(jnp.float32) + 2.0)
        if kpos > 0:  # unbiased LambdaMART: slots ARE input positions
            ti_slot = ti[jnp.minimum(pos, kpos - 1)]
            tj_slot = tj[jnp.minimum(pos, kpos - 1)]

    def gains_j(v):
        return (jnp.exp2(v) - 1.0) if exp_gain else v

    # ``lax.slice`` by name: ``a[:, :K, None]`` traces as a gather
    first_k = lambda a: jax.lax.slice_in_dim(a, 0, K, axis=1)
    rows = lambda a: jnp.broadcast_to(a[None, :], (chunk, L))
    anchor = lambda a: first_k(a)[..., None]   # [C, L] -> the block's i axis
    partner = lambda a: a[:, None, :]          # [C, L] -> the block's j axis

    def by_rank(sp, yp):
        """Scores, labels, slots (and the slots' bias estimates) in rank
        order: padded slots hold -inf and sort last."""
        with rank_scope("order"):
            bias = (rows(ti_slot), rows(tj_slot)) if kpos > 0 else ()
            # keys (-score, slot): the order of a stable sort by score,
            # less the iota operand XLA gives a stable sort of its own
            neg, slots, ys, *bias = jax.lax.sort(
                (-sp, rows(pos), yp) + bias, dimension=1, is_stable=False,
                num_keys=2)
            y_desc = -jnp.sort(-yp, axis=1)
            idcg = jnp.sum(gains_j(y_desc) * disc[None, :], axis=1)
            inv_idcg = jnp.where(idcg > 0, 1.0 / idcg, 0.0)
        return -neg, ys, slots, bias, inv_idcg

    def by_slot(slots, *sums):
        """``[C, L]`` sums of the sorted positions back in slot order (a
        group's slots are distinct: no stability to pay an operand for)."""
        with rank_scope("order"):
            return jax.lax.sort((slots,) + sums, dimension=1,
                                is_stable=False, num_keys=1)[1:]

    def both_axes(a_side, b_side):
        """A sorted position's sum over the block: what it collects as a
        partner plus, in the first K positions, as an anchor."""
        return b_side.sum(axis=1) + jnp.pad(a_side.sum(axis=2),
                                            ((0, 0), (0, L - K)))

    def one_chunk(args):
        sp, yp, szc, kcc = args                      # [C, L] / [C]
        ss, ys, slots, bias, inv_idcg = by_rank(sp, yp)
        pi, pj = anchor(pos[None, :]), pos[None, None, :]
        yi, yj = anchor(ys), partner(ys)
        # each pair once: i is its better-ranked row, inside the truncation
        mask = ((pi < kcc[:, None, None]) & (pi < pj)
                & (pj < szc[:, None, None]) & (yi != yj))
        a_is_i = yi > yj
        if objective == "pairwise":
            delta = jnp.float32(1.0)
        elif objective == "map":    # ranks u = pi < v = pj: columns, no gather
            Ck, T0, R = _map_prefix_ranked(
                ((ys > 0) & (pos[None, :] < szc[:, None])).astype(
                    jnp.float32), L)
            delta = _map_swap_delta(
                pi, pj, a_is_i, Cu=anchor(Ck), Cv=partner(Ck),
                Tv1=partner(T0[:, :L]), Tu=anchor(T0[:, 1:]),
                Tu1=anchor(T0), R=R)
        else:
            gv = gains_j(ys)
            delta = jnp.abs((anchor(gv) - partner(gv))
                            * (anchor(disc[None, :]) - disc[None, None, :])) \
                * inv_idcg[:, None, None]
        lam, hes, p = _ranknet_dev(anchor(ss), partner(ss), a_is_i, delta,
                                   mask)
        sums = ()
        if kpos > 0:
            ti_s, tj_s = bias
            tracked = anchor(slots < kpos) & partner(slots < kpos)
            lam, hes, ci, cj, _ok = _debias_scaled(
                lam, hes, p, delta, mask & tracked,
                jnp.where(a_is_i, anchor(ti_s), partner(ti_s)),
                jnp.where(a_is_i, partner(tj_s), anchor(tj_s)))
            # per-position pair-cost sums: i_pos is the anchor's slot where
            # a_is_i, else the partner's (and symmetrically for j_pos)
            sums = (both_axes(jnp.where(a_is_i, ci, 0.0),
                              jnp.where(a_is_i, 0.0, ci)),
                    both_axes(jnp.where(a_is_i, 0.0, cj),
                              jnp.where(a_is_i, cj, 0.0)))
        lam_i = jnp.where(a_is_i, lam, -lam)
        g, h, *costs = by_slot(slots, both_axes(lam_i, -lam_i),
                               both_axes(hes, hes), *sums)
        if kpos > 0:
            li_c, lj_c = (c.sum(axis=0) for c in costs)
        else:
            li_c = lj_c = jnp.zeros((L,), jnp.float32)
        return g, h, li_c, lj_c

    cs = lambda a: a.reshape(Gp // chunk, chunk, *a.shape[1:])
    with rank_scope("pairs"):       # the chunk loop's own glue included
        g_pad, h_pad, li_s, lj_s = jax.lax.map(
            one_chunk, (cs(s_pad), cs(y_pad), cs(sz), cs(kc)))
    return _rows_of(g_pad, h_pad, li_s, lj_s, qidx, slot, w_row, Gp, L, kpos)


def _rows_of(g_pad, h_pad, li_s, lj_s, qidx, slot, w_row, Gp, L, kpos):
    """The padded sums gathered back to rows: ([n, 1, 2] gradient pairs,
    li, lj); the last two None unless the unbiased path is on."""
    with rank_scope("reduce"):
        g = g_pad.reshape(Gp, L)[qidx, slot] * w_row
        h = h_pad.reshape(Gp, L)[qidx, slot] * w_row
        gpair = jnp.stack([g, h], axis=-1)[:, None, :]   # [n, 1, 2] f32
        if kpos > 0:
            m = min(kpos, L)
            li = jnp.zeros((kpos,), jnp.float32).at[:m].set(
                li_s.sum(axis=0)[:m])
            lj = jnp.zeros((kpos,), jnp.float32).at[:m].set(
                lj_s.sum(axis=0)[:m])
            return gpair, li, lj
        return gpair, None, None


@functools.partial(
    jax.jit,
    static_argnames=("k", "L", "exp_gain", "objective", "chunk",
                     "n_groups", "kpos"))
@stage("gradient")
def _lambda_grad_device_mean(s, y, qidx, slot, starts, sizes, w_row, key,
                             y_order_g, n_lefts_g, n_geq_g, ti=None,
                             tj=None, *, k, L, exp_gain, objective, chunk,
                             n_groups, kpos=0):
    """Sampled-pair (``mean``) LambdaRank lambdas over padded [G, L] groups.

    The reference's distribution (``lambdarank_obj.h:231-275``): each doc
    draws ``k`` rivals uniformly from outside its label bucket (different
    label, same group), so every pair is valid by construction. The pair
    tensor is [C, L, k] — with the default k=1 this is L times lighter
    than the all-pairs kernel, letting much larger group chunks ride one
    ``lax.map`` step. RNG stream: jax.random.split(key, n_chunks)
    (chunk-size-dependent); the reference seeds per (iter, group), so
    distributional — not bitwise — parity."""
    with rank_scope("layout"):
        Gp, s_pad, y_pad, valid, sz = _padded_layout(
            s, y, starts, sizes, n_groups=n_groups, chunk=chunk, L=L)
        disc = 1.0 / jnp.log2(jnp.arange(L, dtype=jnp.float32) + 2.0)
        # pad the precomputed per-group bucket statistics to [Gp, L]
        op = jnp.zeros((Gp, L), jnp.int32).at[:n_groups].set(y_order_g)
        nl_p = jnp.zeros((Gp, L), jnp.int32).at[:n_groups].set(n_lefts_g)
        ng_p = jnp.zeros((Gp, L), jnp.int32).at[:n_groups].set(n_geq_g)

    def gains_j(v):
        return (jnp.exp2(v) - 1.0) if exp_gain else v

    C = chunk
    iota_c = jnp.arange(C, dtype=jnp.int32)

    def one_chunk(args):
        sp, yp, vp, szc, y_order, n_lefts, n_geq, ck = args
        with rank_scope("order"):
            order = jnp.argsort(-sp, axis=1, stable=True)
            rank_of = jnp.argsort(order, axis=1, stable=True)
            y_desc = -jnp.sort(-yp, axis=1)
            idcg = jnp.sum(gains_j(y_desc) * disc[None, :], axis=1)
            inv_idcg = jnp.where(idcg > 0, 1.0 / idcg, 0.0)
            gv = gains_j(yp)
            dv = disc[rank_of]                          # [C, L]
        yi = yp[:, :, None]
        n_riv = n_lefts + (szc[:, None] - n_geq)
        u = (jax.random.uniform(ck, (C, L, k))
             * n_riv[:, :, None].astype(jnp.float32)).astype(jnp.int32)
        u = jnp.clip(u, 0, jnp.maximum(n_riv[:, :, None] - 1, 0))
        ridx = jnp.where(u < n_lefts[:, :, None], u,
                         u - n_lefts[:, :, None] + n_geq[:, :, None])
        rival = jnp.take_along_axis(
            y_order, ridx.reshape(C, L * k), axis=1).reshape(C, L, k)
        pair_ok = vp[:, :, None] & (n_riv[:, :, None] > 0)

        take = lambda a: jnp.take_along_axis(
            a, rival.reshape(C, L * k), axis=1).reshape(C, L, k)
        yj = take(yp)
        sj = take(sp)
        gj2 = take(gv)
        dj2 = take(dv)
        a_is_i = yi > yj
        delta = _delta_dev(
            objective, yp=yp, vp=vp, order=order, L=L, gv=gv, dv=dv,
            inv_idcg=inv_idcg, gj=gj2, dj=dj2,
            rank_i=jnp.broadcast_to(rank_of[:, :, None],
                                    rank_of.shape + (rival.shape[2],)),
            rank_j=take(rank_of), a_is_i=a_is_i)
        lam, hes, p = _ranknet_dev(sp[:, :, None], sj, a_is_i, delta,
                                   pair_ok)
        riv_flat = rival.reshape(C, L * k)
        if kpos > 0:  # unbiased: anchor slot vs sampled-rival slot
            pos = jnp.arange(L, dtype=jnp.int32)
            i_pos = jnp.where(a_is_i, pos[None, :, None], rival)
            j_pos = jnp.where(a_is_i, rival, pos[None, :, None])
            lam, hes, ci, cj, ok = _debias_dev(
                lam, hes, p, delta, pair_ok, a_is_i, i_pos, j_pos, ti, tj,
                kpos)
            li_c = jnp.where(a_is_i, ci, 0.0).sum(axis=2).sum(axis=0)
            lj_c = jnp.where(~a_is_i, cj, 0.0).sum(axis=2).sum(axis=0)
            sc_i = jnp.zeros((C, L), jnp.float32).at[
                iota_c[:, None], riv_flat].add(
                jnp.where(~a_is_i, ci, 0.0).reshape(C, L * k))
            sc_j = jnp.zeros((C, L), jnp.float32).at[
                iota_c[:, None], riv_flat].add(
                jnp.where(a_is_i, cj, 0.0).reshape(C, L * k))
            li_c = li_c + sc_i.sum(axis=0)
            lj_c = lj_c + sc_j.sum(axis=0)
        else:
            li_c = lj_c = jnp.zeros((L,), jnp.float32)
        g = jnp.where(a_is_i, lam, -lam).sum(axis=2)
        h = hes.sum(axis=2)
        g_r = jnp.where(a_is_i, -lam, lam).reshape(C, L * k)
        h_r = hes.reshape(C, L * k)
        g = g.at[iota_c[:, None], riv_flat].add(g_r)
        h = h.at[iota_c[:, None], riv_flat].add(h_r)
        return g, h, li_c, lj_c

    cs = lambda a: a.reshape(Gp // chunk, chunk, *a.shape[1:])
    keys = jax.random.split(key, Gp // chunk)
    with rank_scope("pairs"):       # the chunk loop's own glue included
        g_pad, h_pad, li_s, lj_s = jax.lax.map(
            one_chunk, (cs(s_pad), cs(y_pad), cs(valid), cs(sz), cs(op),
                        cs(nl_p), cs(ng_p), keys))
    return _rows_of(g_pad, h_pad, li_s, lj_s, qidx, slot, w_row, Gp, L, kpos)


class _LambdaRankBase(Objective):
    info = ObjInfo("ranking")
    default_metric = "ndcg"

    def _pairs(self, rng: np.random.RandomState, y: np.ndarray,
               rank_of: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Candidate (i, j) index arrays within one group."""
        n = len(y)
        method = str(self.params.get("lambdarank_pair_method", "mean"))
        k = int(self.params.get("lambdarank_num_pair_per_sample",
                                n if method == "topk" else 1))
        if method == "mean":
            # reference MakePairs mean branch (lambdarank_obj.h:231-275):
            # each doc draws k rivals uniformly from OUTSIDE its label
            # bucket — every sampled pair is label-distinct by construction
            order_y, n_lefts, n_geq = _bucket_stats(y)
            n_riv = n_lefts + (n - n_geq)
            u = (rng.random_sample((n, k)) * n_riv[:, None]).astype(np.int64)
            ridx = np.where(u < n_lefts[:, None], u,
                            u - n_lefts[:, None] + n_geq[:, None])
            keep = np.repeat(n_riv > 0, k)
            i = np.repeat(np.arange(n), k)[keep]
            j = order_y[np.clip(ridx, 0, n - 1)].ravel()[keep]
            return i, j
        # topk: docs currently ranked < k against every doc ranked below
        # them, so each pair counts once (reference MakePairs:
        # ``for i < k: for j > i`` over the rank order)
        anchors = np.nonzero(rank_of < min(k, n))[0]
        i = np.repeat(anchors, n)
        j = np.tile(np.arange(n), len(anchors))
        keep = (y[i] != y[j]) & (rank_of[i] < rank_of[j])
        return i[keep], j[keep]

    def _delta(self, y, i, j, rank_of, inv_idcg, exp_gain) -> np.ndarray:
        raise NotImplementedError

    def _device_layout(self, info):
        """Cached padded-group indexing arrays (+ per-row weights). The key
        hashes the CONTENT of labels/groups/weights, not object identity:
        a mutated-in-place MetaInfo or a recycled id() must rebuild, or the
        device gradient would silently use stale y/slots (the host path
        re-reads them every call). What the key costs a call is kept in
        ``layout_key_ms`` (``core.Booster.update`` puts the last reading on
        its ``round/gradient`` span); the build itself is the ``rank/layout``
        span, once a dataset."""
        t0 = time.perf_counter()
        ptr = np.asarray(info.group_ptr, dtype=np.int64)
        y_np = np.asarray(info.labels, np.float32).reshape(-1)
        w_np = (None if info.weights is None
                else np.asarray(info.weights, np.float32))
        key = (hash(ptr.tobytes()), hash(y_np.tobytes()),
               None if w_np is None else hash(w_np.tobytes()))
        self.layout_key_ms = 1e3 * (time.perf_counter() - t0)
        cached = getattr(self, "_dev_layout", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        sizes = np.diff(ptr)
        G, L = len(sizes), int(sizes.max(initial=1))
        with obs_trace.phase("rank/layout", "train",
                             {"groups": G, "longest": L,
                              "rows": int(ptr[-1])}):
            qidx = np.repeat(np.arange(G, dtype=np.int32), sizes)
            slot = (np.arange(ptr[-1], dtype=np.int32)
                    - np.repeat(ptr[:-1], sizes).astype(np.int32))
            if w_np is not None:
                w_row = np.repeat(w_np, sizes) if len(w_np) == G else w_np
            else:
                w_row = np.ones(int(ptr[-1]), np.float32)
            layout = dict(
                G=G, L=L, _ptr=ptr, _y_np=y_np,
                qidx=jnp.asarray(qidx), slot=jnp.asarray(slot),
                starts=jnp.asarray(ptr[:-1], jnp.int32),
                sizes=jnp.asarray(sizes, jnp.int32),
                w_row=jnp.asarray(w_row),
                y=jnp.asarray(y_np),
                fill=float(ptr[-1]) / max(G * L, 1))
        self._dev_layout = (key, layout)
        return layout

    @staticmethod
    def _chunk_of(layout, block: int) -> int:
        """Groups a ``lax.map`` step of the device kernels: as many as keep
        one pair block, ``block`` slots a group, at ~64 MB of float32."""
        return max(1, min(layout["G"], (1 << 24) // max(block, 1)))

    @staticmethod
    def _pairs_kept(layout, kcap: int) -> int:
        """Pairs the ``topk`` truncation admits inside the groups' real
        rows, from the group sizes alone: a group of n rows with m = min(k,
        n) truncated ranks holds m (n - 1) - m (m - 1) / 2 (rank r < m
        against the n - 1 - r rows below it). Label ties are not counted
        out, so it bounds the pairs that carry a lambda from above. Cached
        on the layout by ``kcap``."""
        kept = layout.setdefault("_kept", {})
        if kcap not in kept:
            n = np.diff(layout["_ptr"])
            m = n if kcap == 0 else np.minimum(kcap, n)
            kept[kcap] = int(np.sum(m * (n - 1) - m * (m - 1) // 2))
        return kept[kcap]

    @staticmethod
    def _mean_stats(layout):
        """Lazily attach the mean-sampling bucket statistics to a cached
        layout (static per dataset; only mean-mode gradients read them —
        topk callers never pay the build or the 3 [G, L] device arrays).
        Same count-based encoding as the host ``_bucket_stats`` (see its
        invariant note), built vectorized over chunked [c, L, L] counts."""
        if "y_order" not in layout:
            ptr, y_np = layout["_ptr"], layout["_y_np"]
            G, L = layout["G"], layout["L"]
            # padded [G, L] label matrix; pads sort last / count nowhere
            sizes = np.diff(ptr)
            qidx = np.repeat(np.arange(G), sizes)
            slot = np.arange(int(ptr[-1])) - np.repeat(ptr[:-1], sizes)
            y_pad = np.zeros((G, L), np.float32)
            vpad = np.zeros((G, L), bool)
            y_pad[qidx, slot] = y_np
            vpad[qidx, slot] = True
            y_order = np.argsort(
                np.where(vpad, -y_pad, np.inf), axis=1,
                kind="stable").astype(np.int32)
            # vectorized bucket counts, chunked so [c, L, L] stays bounded
            n_lefts = np.zeros((G, L), np.int32)
            n_geq = np.zeros((G, L), np.int32)
            c = max(1, (1 << 24) // max(L * L, 1))
            for a in range(0, G, c):
                b = min(G, a + c)
                yq = y_pad[a:b, None, :]
                vq = vpad[a:b, None, :]
                yi = y_pad[a:b, :, None]
                n_lefts[a:b] = (vq & (yq > yi)).sum(axis=2)
                n_geq[a:b] = (vq & (yq >= yi)).sum(axis=2)
            layout["y_order"] = jnp.asarray(y_order)
            layout["n_lefts"] = jnp.asarray(n_lefts)
            layout["n_geq"] = jnp.asarray(n_geq)
        return layout

    def get_gradient(self, preds, info, iteration=0):
        if info.group_ptr is None:
            raise ValueError(f"{self.name} requires query group information "
                             "(set group= or qid= on the DMatrix)")
        if self.name == "rank:map":
            # reference IsBinaryRel (ranking_utils.h:362-377): |dAP| is
            # only defined for binary relevance — graded labels would
            # silently optimise a distorted objective. Validated once per
            # label content (labels are static across boosting rounds).
            lab = np.asarray(info.labels).reshape(-1)
            key = (lab.shape[0], hash(lab.tobytes()))
            if getattr(self, "_map_labels_ok", None) != key:
                if not np.all((lab == 0) | (lab == 1)):
                    raise ValueError(
                        "rank:map requires binary relevance labels (0/1); "
                        "got graded labels — use rank:ndcg instead")
                self._map_labels_ok = key
        method = str(self.params.get("lambdarank_pair_method", "mean"))
        exp_gain = str(self.params.get("ndcg_exp_gain", "true")).lower() \
            not in ("false", "0")
        unbiased = str(self.params.get(
            "lambdarank_unbiased", "false")).lower() in ("1", "true")
        if (self.name in ("rank:ndcg", "rank:pairwise", "rank:map")
                and method in ("topk", "mean")
                and os.environ.get("XTPU_RANK_HOST") != "1"):
            lay = self._device_layout(info)
            n = lay["y"].shape[0]
            s = jnp.asarray(preds, jnp.float32).reshape(-1)[:n]
            kpos, ti_d, tj_d = 0, None, None
            if unbiased:
                # device unbiased LambdaMART (reference lambdarank_obj.cu):
                # ti+/tj- live on the host in f64 (serialization + the
                # normalize/damp update) and ride into the kernel as f32.
                # the PREVIOUS iteration's pair-cost pull lands inside
                # _position_bias_state — it was left in flight so it
                # overlapped that round's tree build instead of blocking
                # twice per round (numerically identical, the update
                # still precedes this iteration's gradient; not measured
                # on the chip: no cell runs the unbiased path, PERF.md 7)
                kpos = self._position_bias_state(method, int(lay["L"]))
                bias = jnp.asarray(
                    np.stack([self._ti_plus, self._tj_minus]), jnp.float32)
                ti_d, tj_d = bias[0], bias[1]
            if method == "mean":
                lay = self._mean_stats(lay)
                k = int(self.params.get(
                    "lambdarank_num_pair_per_sample", 1))
                key = jax.random.fold_in(
                    jax.random.key(int(self.params.get("seed", 0))),
                    iteration)
                slots, kept = lay["L"] * k, n * k   # the [C, L, k] block
                chunk = self._chunk_of(lay, slots)
                gpair, li, lj = _lambda_grad_device_mean(
                    s, lay["y"], lay["qidx"], lay["slot"], lay["starts"],
                    lay["sizes"], lay["w_row"], key, lay["y_order"], lay["n_lefts"],
                    lay["n_geq"], ti_d, tj_d, k=k, L=lay["L"],
                    exp_gain=exp_gain, objective=self.name.split(":")[1],
                    chunk=chunk, n_groups=lay["G"], kpos=kpos)
            else:
                kcap = int(self.params.get(
                    "lambdarank_num_pair_per_sample", 0))
                # the [C, K, L] block in rank order: K anchors a group
                slots = _topk_anchors(kcap, lay["L"]) * lay["L"]
                kept = self._pairs_kept(lay, kcap)
                chunk = self._chunk_of(lay, slots)
                gpair, li, lj = _lambda_grad_device(
                    s, lay["y"], lay["qidx"], lay["slot"], lay["starts"],
                    lay["sizes"], lay["w_row"], ti_d, tj_d, kcap=kcap, L=lay["L"],
                    exp_gain=exp_gain, objective=self.name.split(":")[1],
                    chunk=chunk, n_groups=lay["G"], kpos=kpos)
            # groups padded up to whole chunks are swept too
            count_rank_gradient(method, -(-lay["G"] // chunk) * chunk * slots,
                                kept, lay["fill"])
            if unbiased:
                # ONE packed device array, pulled lazily at the next
                # gradient call / serialization (see _flush_bias_update)
                self._pending_bias = jnp.stack([li, lj])
            return gpair
        y_all = np.asarray(info.labels, dtype=np.float64).reshape(-1)
        s_all = np.asarray(preds, dtype=np.float64).reshape(-1)[: len(y_all)]
        ptr = np.asarray(info.group_ptr, dtype=np.int64)
        rng = np.random.RandomState(int(self.params.get("seed", 0))
                                    + iteration)
        g = np.zeros_like(s_all)
        h = np.zeros_like(s_all)
        if unbiased:
            # Unbiased LambdaMART (Hu et al.; reference lambdarank_obj.cc:
            # 42-89 + lambdarank_obj.h:121-141): position-bias ratios
            # ti+/tj- indexed by the doc's position in the INPUT list (the
            # presentation order of the click log), updated per iteration
            # from the accumulated pair costs. k positions tracked:
            # truncation level under topk, else min(max group, 32).
            sizes = np.diff(ptr)
            kpos = self._position_bias_state(
                method, int(sizes.max(initial=1)))
            li_acc = np.zeros(kpos, np.float64)
            lj_acc = np.zeros(kpos, np.float64)
            eps64 = np.finfo(np.float64).eps
        for q in range(len(ptr) - 1):
            a, b = int(ptr[q]), int(ptr[q + 1])
            n = b - a
            if n < 2:
                continue
            y = y_all[a:b]
            s = s_all[a:b]
            order = np.argsort(-s, kind="stable")
            rank_of = np.empty(n, dtype=np.int64)
            rank_of[order] = np.arange(n)
            gains = _gains(np.sort(y)[::-1], exp_gain)
            idcg = float(np.sum(gains * _dcg_discount(np.arange(n))))
            inv_idcg = 1.0 / idcg if idcg > 0 else 0.0
            i, j = self._pairs(rng, y, rank_of)
            if len(i) == 0:
                continue
            # orient so y[i] > y[j]
            swap = y[i] < y[j]
            i, j = np.where(swap, j, i), np.where(swap, i, j)
            delta = self._delta(y, i, j, rank_of, inv_idcg, exp_gain)
            sij = s[i] - s[j]
            p = 1.0 / (1.0 + np.exp(np.clip(sij, -50, 50)))  # RankNet
            lam = -p * delta
            hes = np.maximum(p * (1.0 - p) * delta, 1e-16)
            if unbiased:
                # debias: divide by ti+[pos_high] * tj-[pos_low]; track the
                # per-position pair costs for the post-iteration update
                # (eq. 30/31; cost = log(1/(1-sigmoid)) * delta with
                # sigmoid = 1 - p). A position whose bias estimate hits
                # exactly 0 stays excluded — faithful to the reference's
                # Eps64 gate (lambdarank_obj.h:133-140).
                tpi = self._ti_plus[np.minimum(i, kpos - 1)]
                tmj = self._tj_minus[np.minimum(j, kpos - 1)]
                ok = ((i < kpos) & (j < kpos)
                      & (tpi >= eps64) & (tmj >= eps64))
                scale = np.where(ok, tpi * tmj, 1.0)
                lam = lam / scale
                hes = hes / scale
                cost = np.log(1.0 / np.maximum(p, 1e-300)) * delta
                np.add.at(li_acc, i[ok], cost[ok] / tmj[ok])
                np.add.at(lj_acc, j[ok], cost[ok] / tpi[ok])
            np.add.at(g, a + i, lam)
            np.add.at(g, a + j, -lam)
            np.add.at(h, a + i, hes)
            np.add.at(h, a + j, hes)
        if unbiased:
            self._update_position_bias(li_acc, lj_acc)
        if info.weights is not None:
            # ranking weights are per query
            w = np.asarray(info.weights, dtype=np.float64)
            if len(w) == len(ptr) - 1:
                w_row = np.repeat(w, np.diff(ptr))
            else:
                w_row = w
            g *= w_row
            h *= w_row
        gpair = np.stack([g, h], axis=-1).astype(np.float32)
        return jnp.asarray(gpair)[:, None, :]

    # ti+/tj- are PROPERTIES so any reader — internal or external (tests,
    # serialization, continuation) — lands the deferred device pull first;
    # the raw arrays live in _ti_plus_v/_tj_minus_v
    @property
    def _ti_plus(self):
        self._flush_bias_update()
        return self.__dict__.get("_ti_plus_v")

    @_ti_plus.setter
    def _ti_plus(self, v):
        self.__dict__["_ti_plus_v"] = v

    @property
    def _tj_minus(self):
        self._flush_bias_update()
        return self.__dict__.get("_tj_minus_v")

    @_tj_minus.setter
    def _tj_minus(self, v):
        self.__dict__["_tj_minus_v"] = v

    def _flush_bias_update(self) -> None:
        """Apply a deferred device pair-cost accumulation to ti+/tj-.
        Runs before anything reads the bias state (the next gradient,
        serialization, continuation — all via the properties above)."""
        pend = self.__dict__.get("_pending_bias")
        if pend is None:
            return
        self.__dict__["_pending_bias"] = None
        acc = np.asarray(pend, np.float64)        # one packed pull
        self._update_position_bias(acc[0], acc[1])

    def _position_bias_state(self, method: str, max_gs: int) -> int:
        """The ONE kpos rule + ti+/tj- (re)initialization, shared by the
        device and host unbiased paths (k positions tracked: truncation
        level under topk, else min(max group, 32)). Flushes any deferred
        device update first — every reader of ti+/tj- comes through
        here or to_json."""
        self._flush_bias_update()
        if method == "topk":
            kpos = int(self.params.get(
                "lambdarank_num_pair_per_sample", max_gs))
        else:
            kpos = min(max_gs, 32)
        kpos = max(kpos, 1)
        if (getattr(self, "_ti_plus", None) is None
                or len(self._ti_plus) != kpos):
            self._ti_plus = np.ones(kpos, np.float64)
            self._tj_minus = np.ones(kpos, np.float64)
        self._ti_plus = np.asarray(self._ti_plus, np.float64)
        self._tj_minus = np.asarray(self._tj_minus, np.float64)
        return kpos

    def _update_position_bias(self, li_acc, lj_acc):
        """reference LambdaRankUpdatePositionBias: normalize the
        accumulated pair costs to position 0 and damp by
        1 / (1 + lambdarank_bias_norm)."""
        eps64 = np.finfo(np.float64).eps
        reg = 1.0 / (1.0 + float(self.params.get(
            "lambdarank_bias_norm", 1.0)))
        if li_acc[0] >= eps64:
            self._ti_plus = np.power(li_acc / max(li_acc[0], eps64), reg)
        if lj_acc[0] >= eps64:
            self._tj_minus = np.power(lj_acc / max(lj_acc[0], eps64), reg)

    def init_estimation(self, info):
        return np.zeros(1, dtype=np.float32)

    # -- serialization: the learned position-bias state must survive
    # save/load and training continuation (the reference persists ti+/tj-
    # in the objective config, lambdarank_obj.cc SaveConfig)
    def to_json(self):
        out = super().to_json()
        self._flush_bias_update()  # a deferred device pull must land first
        if getattr(self, "_ti_plus", None) is not None:
            out["ti_plus"] = [float(v) for v in self._ti_plus]
            out["tj_minus"] = [float(v) for v in self._tj_minus]
        return out

    def configure(self, params):
        params = dict(params)
        tp = params.pop("ti_plus", None)
        tm = params.pop("tj_minus", None)
        super().configure(params)

        def _vec(v):
            if isinstance(v, str):
                import json as _json

                v = _json.loads(v)
            return np.asarray(v, np.float64)

        if tp is not None:
            self._ti_plus = _vec(tp)
        if tm is not None:
            self._tj_minus = _vec(tm)


@OBJECTIVES.register("rank:ndcg")
class LambdaRankNDCG(_LambdaRankBase):
    name = "rank:ndcg"
    default_metric = "ndcg"

    def _delta(self, y, i, j, rank_of, inv_idcg, exp_gain):
        gi = _gains(y[i], exp_gain)
        gj = _gains(y[j], exp_gain)
        di = _dcg_discount(rank_of[i].astype(np.float64))
        dj = _dcg_discount(rank_of[j].astype(np.float64))
        return np.abs((gi - gj) * (di - dj)) * inv_idcg


@OBJECTIVES.register("rank:pairwise")
class LambdaRankPairwise(_LambdaRankBase):
    name = "rank:pairwise"
    default_metric = "map"

    def _delta(self, y, i, j, rank_of, inv_idcg, exp_gain):
        return np.ones(len(i), dtype=np.float64)


@OBJECTIVES.register("rank:map")
class LambdaRankMAP(_LambdaRankBase):
    """MAP delta for binary relevance (reference ``MAPStat``)."""

    name = "rank:map"
    default_metric = "map"

    def _delta(self, y, i, j, rank_of, inv_idcg, exp_gain):
        # exact |ΔAP| from swapping relevant doc i with irrelevant doc j
        # (binary relevance): AP = (1/R) Σ_{ranks k with rel doc} C_k/(k+1)
        yb = (y > 0).astype(np.float64)
        order = np.argsort(rank_of)
        rel_sorted = yb[order]
        C = np.cumsum(rel_sorted)                     # rel count in top k+1
        T = np.cumsum(rel_sorted / (np.arange(len(y)) + 1.0))
        R = max(C[-1], 1.0)
        ri = rank_of[i].astype(np.int64)
        rj = rank_of[j].astype(np.int64)

        def T_at(k):  # T[-1] == 0
            return np.where(k >= 0, T[np.maximum(k, 0)], 0.0)

        rel_above = ri < rj
        u = np.minimum(ri, rj)
        v = np.maximum(ri, rj)
        # relevant doc above (at u) moving down to v
        d_down = C[v] / (v + 1.0) - C[u] / (u + 1.0) - (T_at(v - 1) - T_at(u))
        # relevant doc below (at v) moving up to u
        d_up = (C[u] + 1.0) / (u + 1.0) - C[v] / (v + 1.0) \
            + (T_at(v - 1) - T_at(u - 1))
        return np.abs(np.where(rel_above, d_down, d_up)) / R