"""External-memory tree growth: the level loop over streamed bin pages.

Counterpart of the reference's external-memory updater flow — histogram
builds and row partitioning iterate over ``SparsePage``/``Ellpack`` batches
fetched through an async prefetch ring (``src/data/sparse_page_source.h:
180-200``, CPU hist loop over pages ``src/tree/updater_quantile_hist.cc``).
TPU shape: per depth, one pass over the host-resident quantized matrix in
row pages (double-buffered host->device upload, ``PagedBinnedMatrix.pages``);
page histograms accumulate on device, split evaluation reuses the resident
``evaluate_splits`` kernel, and positions advance page-by-page with the
gather walk. Device memory stays O(2 pages + per-row vectors).

Scope: row split. Depthwise (``PagedGrower``), loss-guided
(``PagedLossguideGrower``) and vector-leaf (``PagedMultiTargetGrower``)
growth all stream; categorical splits, monotone/interaction constraints
and ``max_leaves`` work on the scalar growers (same kernels as the
resident path; constraint bookkeeping lives on the host beside the tree
arrays). Column split raises ``NotImplementedError`` — train that on
resident matrices.
Scale-out works on BOTH axes:
- Multi-HOST: one process per host, each streaming its own row shard, with
  the per-level histogram and root sum crossing hosts through the
  communicator (reference: SparsePageDMatrix under rabit row split,
  ``src/data/sparse_page_dmatrix.cc``).
- Device MESH: pages shard across the mesh's data axis (each chip streams
  its own row shard from host memory) and per-page kernels run under
  ``shard_map`` with the same per-level ``psum`` as resident mesh training
  — "larger-than-HBM x many chips", the pod-scale configuration
  (``_MeshPageKernels``).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import memory as _mem
from ..obs import trace as _trace
from ..ops.histogram import build_hist
from ..ops.partition import advance_positions_level, update_positions
from ..ops.split import evaluate_splits
from ..utils.fetch import fetch_packed, fetch_struct
from .grow import (TWO_LEVEL_METHODS, GrownTree, TreeGrower,
                   _sample_features, interaction_allowed_host,
                   monotone_child_bounds_host, resolve_schedule)
from .lossguide import LossguideGrower
from .multi import MultiLossguideGrower, MultiTargetGrower
from .param import calc_weight

_EPS = 1e-6


def _make_kernels(grower):
    """One construction path for every paged grower's page kernels — mesh
    growers get the shard_map variant, single-chip growers the plain one.
    The missing-bin sentinel derives from the grower's own (max_nbins,
    has_missing) pair, the same formula as ``PagedBinnedMatrix.missing_bin``.
    """
    missing_bin = (grower.max_nbins - 1 if grower.has_missing
                   else grower.max_nbins)
    method = grower.hist_method
    if method in TWO_LEVEL_METHODS or getattr(grower, "_coarse", False):
        # the coarse/refine page passes are plain narrow-width builds:
        # the per-backend "auto" selection picks their kernel
        method = "auto"
    if grower.mesh is not None:
        return _MeshPageKernels(grower.mesh, grower.max_nbins, missing_bin,
                                method)
    return _PageKernels(grower.max_nbins, missing_bin, method)


def _rel_of(pos, lo, n_level, n_static):
    """Level-relative node slot of each row (``n_static`` = not in level)."""
    return jnp.where((pos >= lo) & (pos < lo + n_level), pos - lo,
                     n_static).astype(jnp.int32)


def _page_packed(paged) -> bool:
    return bool(getattr(paged, "packed", False))


def _page_decoder(paged):
    """In-trace decode of the page transport layout (u4 compressed
    transport, data/binned.py) back to ``[p, F]`` bin ids — applied at the
    top of every kernel body, so XLA fuses the nibble unpack into the
    first consumer's read and the packed page stays the only HBM copy."""
    if not _page_packed(paged):
        return lambda page: page
    F = paged.n_features
    from ..ops.histogram import unpack_u4

    return lambda page: unpack_u4(page, F)


def _page_key(paged):
    """Kernel-cache key bits that change a body's trace: the transport
    layout (packed pages decode in-body) and the logical feature count
    the decoder was built for."""
    return (_page_packed(paged), paged.n_features)


def _coarse_bins(page, missing_bin):
    """Coarse-pass bin ids of one page — the shared two-level mapping
    (ops/split.py coarse_bin_ids), computed in-kernel so the page streams
    once."""
    from ..ops.split import coarse_bin_ids

    return coarse_bin_ids(page.astype(jnp.int32), missing_bin)


def _refine_bins(page, rel, span, n_static, missing_bin):
    """Refine-pass relative bin ids: each row's node picks its WINDOW-bin
    fine window start from ``span`` [n_static, F] (one one-hot MXU
    matmul, no data-dependent gather); the elementwise slot mapping is
    the shared ops/split.py refine_bin_ids."""
    from ..ops.split import refine_bin_ids

    span_pad = jnp.concatenate(
        [span.astype(jnp.float32),
         jnp.zeros((1, span.shape[1]), jnp.float32)])       # [N+1, F]
    oh_rel = (rel[:, None] == jnp.arange(
        n_static + 1, dtype=jnp.int32)[None, :]).astype(jnp.float32)
    c_row = jax.lax.dot_general(
        oh_rel, span_pad, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST)                # [p, F]
    return refine_bin_ids(page.astype(jnp.int32),
                          c_row.astype(jnp.int32), missing_bin)


def _advance_rows(page, pos_pg, kind, arrs, cat_args, lo_prev, nl_prev,
                  n_static, missing_bin):
    """One page's position advance for an evaluated level — the traced core
    shared by the plain and shard_map kernels. ``kind`` picks the dense
    matmul advance (static level width <= 64) or the per-row gather walk
    (deep levels, O(page) memory)."""
    if kind == "dense":
        feat_d, thr_d, dl_d, cs_d = arrs
        rel_prev = _rel_of(pos_pg, lo_prev, nl_prev, n_static)
        kw = ({} if not cat_args
              else dict(is_cat=cat_args[0], cat_words=cat_args[1]))
        return advance_positions_level(
            page.astype(jnp.float32), pos_pg, rel_prev, feat_d, thr_d,
            dl_d, cs_d, missing_bin, **kw)
    sf_d, sb_d, dl_d, isf_d = arrs
    kw = ({} if not cat_args
          else dict(is_cat_split=cat_args[0], cat_words=cat_args[1]))
    return update_positions(page, pos_pg, sf_d, sb_d, dl_d, isf_d,
                            missing_bin, **kw)


def _pack_level_splits(idx, can_split, n_static, n_level, split_feature,
                       split_bin, default_left, max_nodes, lo,
                       cat_state=None):
    """Device split vectors for one freshly evaluated level — the inputs of
    the NEXT pass's fused advance. ``n_static <= 64``: static-width padded
    per-level vectors for the dense matmul advance; deeper: the full tree
    arrays for the gather walk. ``cat_state`` is an optional
    ``(is_cat_split, cat_words)`` pair of full host arrays."""
    if n_static <= 64:
        feat_pad = np.full(n_static, -1, np.int32)
        bin_pad = np.zeros(n_static, np.int32)
        dl_pad = np.zeros(n_static, bool)
        cs_pad = np.zeros(n_static, bool)
        feat_pad[:n_level] = split_feature[idx]
        bin_pad[:n_level] = split_bin[idx]
        dl_pad[:n_level] = default_left[idx]
        cs_pad[:n_level] = can_split
        cat = None
        if cat_state is not None:
            is_cat_split, cat_words = cat_state
            ic_pad = np.zeros(n_static, bool)
            cw_pad = np.zeros((n_static, cat_words.shape[1]), np.uint32)
            ic_pad[:n_level] = is_cat_split[idx]
            cw_pad[:n_level] = cat_words[idx]
            cat = (jnp.asarray(ic_pad), jnp.asarray(cw_pad))
        return {"kind": "dense", "lo": lo, "n_level": n_level,
                "arrs": (jnp.asarray(feat_pad), jnp.asarray(bin_pad),
                         jnp.asarray(dl_pad), jnp.asarray(cs_pad)),
                "cat": cat}
    is_split_full = np.zeros(max_nodes, bool)
    is_split_full[idx] = can_split
    cat = None
    if cat_state is not None:
        is_cat_split, cat_words = cat_state
        cat = (jnp.asarray(is_cat_split), jnp.asarray(cat_words))
    return {"kind": "walk", "lo": lo, "n_level": n_level,
            "arrs": (jnp.asarray(split_feature), jnp.asarray(split_bin),
                     jnp.asarray(default_left), jnp.asarray(is_split_full)),
            "cat": cat}


class _LevelEvaluator:
    """Device-resident split evaluation + eval-feeding state for the paged
    depthwise growers.

    The round-3 paged tier pulled every level's split decisions to the host
    (to update tree bookkeeping) and re-uploaded the split vectors for the
    next advance — 8-10 blocking host round trips per LEVEL. Here the
    whole eval side lives on device, exactly like the resident ``_grow``:
    one jitted program per level consumes the level histogram and the
    carried state (active slots, parent sums, monotone bounds, constraint
    paths, deep-walk tree arrays), emits the NEXT pass's advance vectors as
    device arrays, and stashes the host-needed decision arrays. The host
    pulls ALL levels' stashes in ONE packed transfer at tree end and replays
    the bookkeeping. In-loop blocking syncs per tree: zero on a single host
    (the cross-host allreduce still syncs per level when a communicator is
    active, as it must).

    Slot convention: every level uses the same static width ``n_static``
    (the widest level); slot ``i`` of level ``d`` is heap node ``lo + i``,
    and the children of slot ``i`` are slots ``2i``/``2i+1`` of the next
    level. Pad slots carry ``active=False`` and can never win a split."""

    def __init__(self, grower, n_static: int, max_nodes: int,
                 deep: bool, n_real_bins, coarse: bool = False) -> None:
        self.param = grower.param
        self.cat = grower.cat
        self.monotone = getattr(grower, "monotone", None)
        self.cons = getattr(grower, "constraint_sets", None)
        self.has_missing = grower.has_missing
        self.n_static = n_static
        self.max_nodes = max_nodes
        self.deep = deep
        self.coarse = coarse
        self.n_real_d = jnp.asarray(np.asarray(n_real_bins))
        if self.cat is not None:
            n_real_slots = (grower.max_nbins - 1 if grower.has_missing
                            else grower.max_nbins)
            self.n_words = (n_real_slots - 1) // 32 + 1
        else:
            self.n_words = 1
        self._fn = None
        self._init_fn = None
        self._win_fn = None

    def _window_body(self, hc, parent):
        """Traced refine-window choice — shared by the standalone
        ``choose_window`` jit and the page-major whole-level program
        (``_PageKernels.level_full``), so both paths pick bit-identical
        windows."""
        from ..ops.split import choose_refine_window

        return choose_refine_window(hc, parent, self.n_real_d, self.param,
                                    self.has_missing)

    def choose_window(self, hist_c, state):
        """Refine-window starts [n_static, F] from the GLOBAL coarse
        histogram and the carried parent sums (paged two-level histogram:
        the window choice is node-level, after the coarse page pass)."""
        if self._win_fn is None:
            self._win_fn = jax.jit(self._window_body)
        return self._win_fn(hist_c, state[1])

    def init_state(self, root_sum):
        """Level-0 state from the device root gradient sum."""
        if self._init_fn is None:
            n_static, max_nodes = self.n_static, self.max_nodes

            def init(root):
                active = jnp.zeros((n_static,), bool).at[0].set(True)
                parent = jnp.zeros((n_static, 2),
                                   jnp.float32).at[0].set(root)
                mlo = jnp.full((n_static,), -jnp.inf, jnp.float32)
                mhi = jnp.full((n_static,), jnp.inf, jnp.float32)
                path = (jnp.zeros((n_static, self.cons.shape[1]), bool)
                        if self.cons is not None else jnp.zeros((1,), bool))
                if self.deep:
                    full = (jnp.full((max_nodes,), -1, jnp.int32),
                            jnp.zeros((max_nodes,), jnp.int32),
                            jnp.zeros((max_nodes,), bool),
                            jnp.zeros((max_nodes,), bool),
                            jnp.zeros((max_nodes,), bool),
                            jnp.zeros((max_nodes, self.n_words),
                                      jnp.uint32))
                else:
                    full = jnp.zeros((1,), bool)
                return (active, parent, mlo, mhi, path, full)

            self._init_fn = jax.jit(init)
        return self._init_fn(root_sum)

    def __call__(self, hist, state, tree_mask, key, depth, lo, n_level):
        """-> (stash dict of device arrays, next state, prev dict).

        ``hist`` is the [n_static, F, B, 2] level histogram — or, in
        coarse mode, the ``(hist_c, hist_r, span)`` triple assembled
        on device inside the jitted program."""
        if self._fn is None:
            self._fn = jax.jit(self._build())
        hist = hist if isinstance(hist, tuple) else (hist,)
        outs = self._fn(*hist, state, tree_mask, key, depth, lo, n_level)
        return self._package(outs, lo, n_level)

    def _package(self, outs, lo, n_level):
        """Wrap the traced eval outputs into (stash, next state, prev
        advance payload) — shared by the standalone per-level jit above
        and the page-major whole-level program, which embeds the same
        traced eval and returns the same output tuple."""
        stash, state_n, feat_v, bin_v, dl_v, cs_v, ic_v, cw_v = outs
        cat_prev = None if self.cat is None else (ic_v, cw_v)
        if self.deep:
            sf, sb, dl, isf, icf, cwf = state_n[5]
            prev = {"kind": "walk", "lo": lo, "n_level": n_level,
                    "arrs": (sf, sb, dl, isf),
                    "cat": (icf, cwf) if self.cat is not None else None}
        else:
            prev = {"kind": "dense", "lo": lo, "n_level": n_level,
                    "arrs": (feat_v, bin_v, dl_v, cs_v), "cat": cat_prev}
        return stash, state_n, prev

    def _build(self):
        param = self.param
        cat = self.cat
        monotone = self.monotone
        cons = self.cons
        n_static = self.n_static
        eps = float(max(param.gamma, _EPS))

        def fn(*args):
            from .grow import _sample_features
            from .param import calc_weight as _cw

            if self.coarse:
                (hist_c, hist_r, span, state, tree_mask, key, depth, lo,
                 n_level) = args
            else:
                hist, state, tree_mask, key, depth, lo, n_level = args
            active, parent, mlo, mhi, path, full = state
            level_key = jax.random.fold_in(key, depth)
            fmask_level = _sample_features(level_key, tree_mask,
                                           param.colsample_bylevel)
            if param.colsample_bynode < 1.0:
                # NOTE: draws n_static per-node masks (static width); the
                # resident path draws n_level — same distribution, a
                # different stream, so bynode paged runs are valid but not
                # bit-identical to resident (none of the parity suites
                # combine paged with colsample_bynode)
                node_keys = jax.random.split(
                    jax.random.fold_in(level_key, 1), n_static)
                fmask = jax.vmap(
                    lambda k: _sample_features(k, fmask_level,
                                               param.colsample_bynode)
                )(node_keys)
            else:
                fmask = fmask_level[None, :]
            if cons is not None:
                from .grow import interaction_allowed_dev

                fmask = fmask & interaction_allowed_dev(path, cons)
            mono_kw = {}
            if monotone is not None:
                mono_kw = dict(monotone=monotone, node_lower=mlo,
                               node_upper=mhi)
            if self.coarse:
                from ..ops.split import (assemble_two_level,
                                         decode_two_level_bin)

                hist, n_real_eval = assemble_two_level(
                    hist_c, hist_r, span, self.n_real_d, self.has_missing)
            else:
                n_real_eval = self.n_real_d
            res = evaluate_splits(hist, parent, n_real_eval, param,
                                  feature_mask=fmask, cat=cat,
                                  has_missing=self.has_missing, **mono_kw)
            if self.coarse:
                # synthetic slot -> fine bin, per node's span for its
                # winning feature (same decode as the resident path)
                span_sel = jnp.take_along_axis(
                    span, jnp.maximum(res.feature, 0)[:, None],
                    axis=1)[:, 0]
                res = res._replace(
                    bin=decode_two_level_bin(res.bin, span_sel))

            can_split = active & (res.gain > eps) & jnp.isfinite(res.gain)
            feat_v = jnp.where(can_split, res.feature, -1).astype(jnp.int32)
            bin_v = jnp.where(can_split, res.bin, 0).astype(jnp.int32)
            dl_v = can_split & res.default_left
            stash = dict(gain=res.gain, feature=res.feature,
                         bin=res.bin, default_left=res.default_left,
                         left_sum=res.left_sum, right_sum=res.right_sum,
                         can_split=can_split)
            if cat is not None:
                ic_v = can_split & res.is_cat
                cw_v = jnp.where(ic_v[:, None], res.cat_words,
                                 jnp.uint32(0))
                stash["is_cat"] = res.is_cat
                stash["cat_words"] = res.cat_words
            else:
                ic_v = jnp.zeros((n_static,), bool)
                cw_v = jnp.zeros((n_static, self.n_words), jnp.uint32)

            # ---- next level's state: slot j <- child j%2 of slot j//2 ----
            j = jnp.arange(n_static)
            half = j // 2
            is_left = (j % 2) == 0
            cs_h = can_split[half] & (j < 2 * n_level)
            ls, rs = res.left_sum, res.right_sum
            parent_n = jnp.where(
                cs_h[:, None],
                jnp.where(is_left[:, None], ls[half], rs[half]), 0.0)
            active_n = cs_h
            if monotone is not None:
                wl = jnp.clip(_cw(ls[:, 0], ls[:, 1], param), mlo, mhi)
                wr = jnp.clip(_cw(rs[:, 0], rs[:, 1], param), mlo, mhi)
                mid = (wl + wr) * 0.5
                mc = monotone[jnp.maximum(feat_v, 0)]
                l_hi = jnp.where(mc > 0, mid, mhi)
                r_lo = jnp.where(mc > 0, mid, mlo)
                l_lo = jnp.where(mc < 0, mid, mlo)
                r_hi = jnp.where(mc < 0, mid, mhi)
                mlo_n = jnp.where(cs_h, jnp.where(is_left, l_lo[half],
                                                  r_lo[half]), 0.0)
                mhi_n = jnp.where(cs_h, jnp.where(is_left, l_hi[half],
                                                  r_hi[half]), 0.0)
            else:
                mlo_n, mhi_n = mlo, mhi
            if cons is not None:
                fsel = (jnp.arange(cons.shape[1],
                                   dtype=jnp.int32)[None, :]
                        == jnp.maximum(feat_v, 0)[:, None]) \
                    & can_split[:, None]
                child_path = path | fsel
                path_n = child_path[half]
            else:
                path_n = path
            if self.deep:
                sf, sb, dl, isf, icf, cwf = full
                upd = jax.lax.dynamic_update_slice_in_dim
                full_n = (upd(sf, feat_v, lo, 0), upd(sb, bin_v, lo, 0),
                          upd(dl, dl_v, lo, 0), upd(isf, can_split, lo, 0),
                          upd(icf, ic_v, lo, 0), upd(cwf, cw_v, lo, 0))
            else:
                full_n = full
            state_n = (active_n, parent_n, mlo_n, mhi_n, path_n, full_n)
            return stash, state_n, feat_v, bin_v, dl_v, can_split, ic_v, cw_v

        return fn


class _PageKernels:
    """Single-chip per-page programs with IN-JIT page windowing.

    The host passes the FULL per-row vectors plus a dynamic page offset and
    every slice/rel/update happens inside the jitted program — each eager
    op between kernels is a dispatch of its own, and the round-3 paged
    tier spent most of its round in exactly that op soup (dispatch cost
    on the attached chip: not measured). The first level builds the root
    histogram; later levels FUSE
    the previous level's position advance with this level's histogram, so
    a page is read once per level and a round costs (depth+1) passes
    instead of 2*depth. Since round 5 each pass is ONE dispatch over ALL
    HBM-cached pages (``_drive``) — with a warm page cache the per-page
    dispatch RTT, not H2D, was the whole remaining gap to the resident
    tier — and only cache-overflow pages go one-dispatch-per-page through
    the prefetch ring, upload overlapped one page ahead (reference: the
    prefetch ring hides page IO behind compute,
    ``src/data/sparse_page_source.h:180-200``)."""

    def __init__(self, max_nbins: int, missing_bin: int,
                 hist_kernel: str) -> None:
        self.max_nbins = max_nbins
        self.missing_bin = missing_bin
        self.hist_kernel = hist_kernel
        self._fns: dict = {}

    def init_positions(self, n: int):
        return jnp.zeros((n,), jnp.int32)

    def _cached(self, key, build):
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = build()
        return fn

    def _builder(self, multi):
        from ..ops.histogram import build_hist_multi

        return build_hist_multi if multi else build_hist

    def _acc_zeros(self, paged, gpair, n_nodes, multi, nbins=None):
        shape = ((n_nodes, paged.n_features, nbins or self.max_nbins)
                 + ((gpair.shape[1], 2) if multi else (2,)))
        return jnp.zeros(shape, jnp.float32)

    def _drive(self, paged, key, make_body, carry, consts):
        """Run ``body(carry, page, start, consts)`` over every page: ONE
        fused jitted dispatch covering all HBM-cached pages (r5: with a
        warm cache the per-page dispatch latency — not H2D — was the
        paged tier's whole gap to the resident path; unverified on the
        attached chip), then the prefetch ring for the
        cache overflow, one dispatch each with uploads overlapped through
        the depth-3 ring. Pages arrive in transport layout and decode
        in-trace; the carry pytree is donated both ways."""
        dec = _page_decoder(paged)
        key = key + _page_key(paged)
        cached, streamed = paged.cached_split()
        if cached:
            def build_fused():
                body = make_body()

                def fn(carry, consts, starts, pages):
                    for st, page in zip(starts, pages):
                        carry = body(carry, dec(page), st, consts)
                    return carry

                return jax.jit(fn, donate_argnums=0)

            fused = self._cached(key + ("fused",), build_fused)
            carry = fused(carry, consts,
                          tuple(jnp.int32(s) for s, _, _ in cached),
                          tuple(p for _, _, p in cached))
        if streamed:
            def build_single():
                body = make_body()
                return jax.jit(
                    lambda carry, page, s, consts:
                    body(carry, dec(page), s, consts), donate_argnums=0)

            single = self._cached(key + ("single",), build_single)
            for s, e, page in paged.stream_pages(streamed):
                carry = single(carry, page, jnp.int32(s), consts)
        return carry

    def level_hist(self, paged, gpair, positions, lo, n_level, n_static,
                   multi=False):
        """Histogram-only pass (the root level of each tree, one-pass
        scheme; the two-level coarse scheme routes through
        ``coarse_pass``/``refine_pass``/``level_full`` instead)."""
        def make_body():
            builder = self._builder(multi)

            def body(acc, page, s, consts):
                gp, pos, lo_d, nl_d = consts
                p = page.shape[0]
                pos_pg = jax.lax.dynamic_slice_in_dim(pos, s, p)
                gp_pg = jax.lax.dynamic_slice_in_dim(gp, s, p)
                rel = _rel_of(pos_pg, lo_d, nl_d, n_static)
                return acc + builder(page, gp_pg, rel, n_static,
                                     self.max_nbins,
                                     method=self.hist_kernel)

            return body

        acc = self._acc_zeros(paged, gpair, n_static, multi)
        return self._drive(
            paged, ("hist", n_static, multi), make_body, acc,
            (gpair, positions, jnp.int32(lo), jnp.int32(n_level)))

    def adv_hist(self, paged, gpair, positions, prev, lo, n_level, n_static,
                 multi=False):
        """The fused pass: advance rows below the PREVIOUS level's splits,
        then build THIS level's histogram — one page read per level."""
        kind = prev["kind"]
        cat = prev["cat"]
        n_arr = len(prev["arrs"])
        W = None if cat is None else int(cat[1].shape[1])

        def make_body():
            builder = self._builder(multi)

            def body(carry, page, s, consts):
                pos, acc = carry
                gp, lo_prev, nl_prev, lo_d, nl_d = consts[:5]
                arrs = consts[5:5 + n_arr]
                cat_args = consts[5 + n_arr:]
                p = page.shape[0]
                pos_pg = jax.lax.dynamic_slice_in_dim(pos, s, p)
                gp_pg = jax.lax.dynamic_slice_in_dim(gp, s, p)
                newp = _advance_rows(page, pos_pg, kind, arrs, cat_args,
                                     lo_prev, nl_prev, n_static,
                                     self.missing_bin)
                pos = jax.lax.dynamic_update_slice_in_dim(pos, newp, s, 0)
                rel = _rel_of(newp, lo_d, nl_d, n_static)
                h = builder(page, gp_pg, rel, n_static, self.max_nbins,
                            method=self.hist_kernel)
                return pos, acc + h

            return body

        acc = self._acc_zeros(paged, gpair, n_static, multi)
        extra = prev["arrs"] + (() if cat is None else tuple(cat))
        consts = (gpair, jnp.int32(prev["lo"]), jnp.int32(prev["n_level"]),
                  jnp.int32(lo), jnp.int32(n_level)) + extra
        return self._drive(
            paged, ("advhist", kind, n_static, multi, W),
            make_body, (positions, acc), consts)

    # -- page-major two-level (coarse) schedule ------------------------------
    # The r5/r6 schedule swept the data TWICE per level boundary
    # (advance+coarse, then refine), so a forced-streaming round at depth 6
    # re-uploaded the matrix ~13 times. Page-major: a streamed page's ONE
    # visit per level carries the advance, the direct coarse partial, AND a
    # full fine-histogram partial; after the (tiny) cross-page coarse
    # reduction picks the refine window, the streamed refine contribution
    # is a window SLICE of the fine accumulator — bit-equal to the direct
    # refine build of the same rows (ops/split.py refine_from_fine) — so
    # only HBM-cached pages run a second (free) sweep. Uploads/round drop
    # from ~2*depth+1 to depth+1 matrix-equivalents before packing.

    def coarse_pass(self, paged, gpair, positions, prev, lo, n_level,
                    n_static, cached, streamed):
        """First sweep of a level boundary: advance below the previous
        level's splits (when ``prev``) + the level's direct coarse
        histogram. Cached pages run as ONE fused dispatch; streamed pages
        upload once and also accumulate their fine partial.
        -> (positions, hist_c, fine-or-None). The (cached, streamed)
        partition is frozen by the caller for the whole level."""
        from ..ops.split import COARSE_B

        kind = None if prev is None else prev["kind"]
        cat = None if prev is None else prev["cat"]
        n_arr = 0 if prev is None else len(prev["arrs"])
        W = None if cat is None else int(cat[1].shape[1])
        dec = _page_decoder(paged)
        mb = self.missing_bin
        hk = self.hist_kernel

        def make_body(fine):
            def body(carry, page, s, consts):
                pos, acc = carry[0], carry[1]
                gp, lo_prev, nl_prev, lo_d, nl_d = consts[:5]
                arrs = consts[5:5 + n_arr]
                cat_args = consts[5 + n_arr:]
                page = dec(page)
                p = page.shape[0]
                pos_pg = jax.lax.dynamic_slice_in_dim(pos, s, p)
                gp_pg = jax.lax.dynamic_slice_in_dim(gp, s, p)
                if kind is not None:
                    pos_pg = _advance_rows(page, pos_pg, kind, arrs,
                                           cat_args, lo_prev, nl_prev,
                                           n_static, mb)
                    pos = jax.lax.dynamic_update_slice_in_dim(pos, pos_pg,
                                                              s, 0)
                rel = _rel_of(pos_pg, lo_d, nl_d, n_static)
                acc = acc + build_hist(_coarse_bins(page, mb), gp_pg, rel,
                                       n_static, COARSE_B, method=hk)
                if not fine:
                    return pos, acc
                af = carry[2] + build_hist(page, gp_pg, rel, n_static,
                                           self.max_nbins, method=hk)
                return pos, acc, af

            return body

        consts = (gpair,
                  jnp.int32(0 if prev is None else prev["lo"]),
                  jnp.int32(0 if prev is None else prev["n_level"]),
                  jnp.int32(lo), jnp.int32(n_level))
        if prev is not None:
            consts = consts + prev["arrs"] + (() if cat is None
                                              else tuple(cat))
        key = ("cpass", kind, n_static, W) + _page_key(paged)
        carry = (positions,
                 self._acc_zeros(paged, gpair, n_static, False,
                                 nbins=COARSE_B))
        if cached:
            def build_fused():
                body = make_body(False)

                def fn(carry, consts, starts, pages):
                    for st, page in zip(starts, pages):
                        carry = body(carry, page, st, consts)
                    return carry

                return jax.jit(fn, donate_argnums=0)

            fused = self._cached(key + ("fused",), build_fused)
            carry = fused(carry, consts,
                          tuple(jnp.int32(s) for s, _, _ in cached),
                          tuple(p for _, _, p in cached))
        fine = None
        if streamed:
            carry = carry + (self._acc_zeros(paged, gpair, n_static,
                                             False),)

            def build_single():
                return jax.jit(make_body(True), donate_argnums=0)

            single = self._cached(key + ("single",), build_single)
            for s, e, page in paged.stream_pages(streamed):
                carry = single(carry, page, jnp.int32(s), consts)
            fine = carry[2]
        return carry[0], carry[1], fine

    def refine_pass(self, paged, gpair, positions, span, lo, n_level,
                    n_static, cached, fine=None):
        """Second sweep of a coarse-mode level: direct refine build over
        the level's CACHED pages only (HBM re-reads, no H2D) plus the
        window slice of the streamed pages' fine accumulator — streamed
        pages are never re-uploaded."""
        from ..ops.split import WINDOW, refine_from_fine

        dec = _page_decoder(paged)
        mb = self.missing_bin
        hk = self.hist_kernel
        acc = self._acc_zeros(paged, gpair, n_static, False,
                              nbins=WINDOW + 4)
        key = ("rpass", n_static) + _page_key(paged)
        if cached:
            def build_fused():
                def body(acc, page, s, consts):
                    gp, pos, lo_d, nl_d, span_d = consts
                    page = dec(page)
                    p = page.shape[0]
                    pos_pg = jax.lax.dynamic_slice_in_dim(pos, s, p)
                    gp_pg = jax.lax.dynamic_slice_in_dim(gp, s, p)
                    rel = _rel_of(pos_pg, lo_d, nl_d, n_static)
                    rb = _refine_bins(page, rel, span_d, n_static, mb)
                    return acc + build_hist(rb, gp_pg, rel, n_static,
                                            WINDOW + 4, method=hk)

                def fn(acc, consts, starts, pages):
                    for st, page in zip(starts, pages):
                        acc = body(acc, page, st, consts)
                    return acc

                return jax.jit(fn, donate_argnums=0)

            fused = self._cached(key, build_fused)
            acc = fused(acc,
                        (gpair, positions, jnp.int32(lo),
                         jnp.int32(n_level), span),
                        tuple(jnp.int32(s) for s, _, _ in cached),
                        tuple(p for _, _, p in cached))
        if fine is None:
            return acc[:, :, :WINDOW, :]

        def build_combine():
            # no donation: the combined output is a SLICE of the direct
            # accumulator's shape, so the donated buffer could never be
            # reused anyway
            return jax.jit(
                lambda acc, fine, span_d:
                acc[:, :, :WINDOW, :] + refine_from_fine(fine, span_d, mb))

        return self._cached(("rslice", n_static), build_combine)(
            acc, fine, span)

    def level_full(self, paged, gpair, positions, prev, lo, n_level,
                   n_static, ev, state, tree_mask, key, depth, cached):
        """The all-cached page-major fast path: ONE jitted dispatch runs
        the whole level boundary — advance below the previous level's
        splits, the coarse (or one-pass full-width) histogram over every
        HBM-cached page, the refine-window choice, the refine build, and
        the split evaluation / carried-state update — with ``lo`` /
        ``n_level`` / ``depth`` traced so a single compiled program
        serves every level of every tree. This is what closes the
        dispatch-granularity gap of the r5/r6 streaming tier against a
        remote device: ~4 kernel dispatches plus an eval dispatch per
        level collapse into one program launch per level.
        -> (positions, stash, next_state, prev-dict)."""
        kind = None if prev is None else prev["kind"]
        cat = None if prev is None else prev["cat"]
        n_arr = 0 if prev is None else len(prev["arrs"])
        W = None if cat is None else int(cat[1].shape[1])
        fused = self.level_full_fn(paged, ev, n_static, kind, W, n_arr,
                                   len(cached))
        consts = (gpair,
                  jnp.int32(0 if prev is None else prev["lo"]),
                  jnp.int32(0 if prev is None else prev["n_level"]),
                  jnp.int32(lo), jnp.int32(n_level), jnp.int32(depth))
        if prev is not None:
            consts = consts + prev["arrs"] + (() if cat is None
                                              else tuple(cat))
        outs = fused(positions, state, tree_mask, key, consts,
                     tuple(jnp.int32(s) for s, _, _ in cached),
                     tuple(p for _, _, p in cached))
        stash, state_n, prev_n = ev._package(tuple(outs[1:]), lo, n_level)
        return outs[0], stash, state_n, prev_n

    def level_full_fn(self, paged, ev, n_static, kind, W, n_arr, n_cached):
        """Build (and cache) the whole-level compiled program WITHOUT
        dispatching it: ``level_full`` above invokes exactly this cached
        object, and ``xgboost_tpu/tree/programs.py`` exports it as the
        traceable handle behind the paged dispatch-budget /
        uploads-per-level contracts (tools/xtpuverify)."""
        from ..ops.split import COARSE_B, WINDOW

        coarse = ev.coarse
        dec = _page_decoder(paged)
        mb = self.missing_bin
        hk = self.hist_kernel
        F = paged.n_features
        B = COARSE_B if coarse else self.max_nbins

        def build():
            eval_fn = ev._build()

            def fn(positions, state, tree_mask, keyv, consts, starts,
                   pages):
                gp, lo_prev, nl_prev, lo_d, nl_d, depth_d = consts[:6]
                arrs = consts[6:6 + n_arr]
                cat_args = consts[6 + n_arr:]
                pages_d = [dec(pg) for pg in pages]
                pos = positions
                pos_pgs = []
                for st, page in zip(starts, pages_d):
                    pos_pg = jax.lax.dynamic_slice_in_dim(
                        pos, st, page.shape[0])
                    if kind is not None:
                        pos_pg = _advance_rows(page, pos_pg, kind, arrs,
                                               cat_args, lo_prev, nl_prev,
                                               n_static, mb)
                        pos = jax.lax.dynamic_update_slice_in_dim(
                            pos, pos_pg, st, 0)
                    pos_pgs.append(pos_pg)
                acc = jnp.zeros((n_static, F, B, 2), jnp.float32)
                for st, page, pos_pg in zip(starts, pages_d, pos_pgs):
                    gp_pg = jax.lax.dynamic_slice_in_dim(gp, st,
                                                         page.shape[0])
                    rel = _rel_of(pos_pg, lo_d, nl_d, n_static)
                    data = _coarse_bins(page, mb) if coarse else page
                    acc = acc + build_hist(data, gp_pg, rel, n_static, B,
                                           method=hk)
                if coarse:
                    span = ev._window_body(acc, state[1])
                    accr = jnp.zeros((n_static, F, WINDOW + 4, 2),
                                     jnp.float32)
                    for st, page, pos_pg in zip(starts, pages_d, pos_pgs):
                        gp_pg = jax.lax.dynamic_slice_in_dim(
                            gp, st, page.shape[0])
                        rel = _rel_of(pos_pg, lo_d, nl_d, n_static)
                        rb = _refine_bins(page, rel, span, n_static, mb)
                        accr = accr + build_hist(rb, gp_pg, rel, n_static,
                                                 WINDOW + 4, method=hk)
                    hist = (acc, accr[:, :, :WINDOW, :], span)
                else:
                    hist = (acc,)
                outs = eval_fn(*hist, state, tree_mask, keyv, depth_d,
                               lo_d, nl_d)
                return (pos,) + tuple(outs)

            # deep (walk) mode: prev["arrs"] alias the carried state's
            # full tree arrays, which also arrive as consts — donating
            # state would just trip jax's alias check every level
            return jax.jit(fn, donate_argnums=(0,) if ev.deep else (0, 1))

        return self._cached(
            ("levelfull", kind, n_static, W, coarse, n_cached, ev.deep)
            + _page_key(paged), build)

    def final_advance(self, paged, positions, prev, n_static):
        """Advance-only pass for the LAST evaluated level (leaf routing)."""
        kind = prev["kind"]
        cat = prev["cat"]
        n_arr = len(prev["arrs"])
        W = None if cat is None else int(cat[1].shape[1])

        def make_body():
            def body(pos, page, s, consts):
                lo_prev, nl_prev = consts[:2]
                arrs = consts[2:2 + n_arr]
                cat_args = consts[2 + n_arr:]
                p = page.shape[0]
                pos_pg = jax.lax.dynamic_slice_in_dim(pos, s, p)
                newp = _advance_rows(page, pos_pg, kind, arrs, cat_args,
                                     lo_prev, nl_prev, n_static,
                                     self.missing_bin)
                return jax.lax.dynamic_update_slice_in_dim(pos, newp, s, 0)

            return body

        extra = prev["arrs"] + (() if cat is None else tuple(cat))
        return self._drive(
            paged, ("adv", kind, n_static, W), make_body, positions,
            (jnp.int32(prev["lo"]), jnp.int32(prev["n_level"])) + extra)

    def pair_hist(self, paged, gpair, positions, i0, i1, multi=False):
        """Two-node (lossguide sibling pair) histogram over the pages
        (K-channel with ``multi`` — the vector-leaf lossguide)."""
        def make_body():
            builder = self._builder(multi)

            def body(acc, page, s, consts):
                gp, pos, i0_d, i1_d = consts
                p = page.shape[0]
                pos_pg = jax.lax.dynamic_slice_in_dim(pos, s, p)
                gp_pg = jax.lax.dynamic_slice_in_dim(gp, s, p)
                rel = jnp.where(pos_pg == i0_d, 0,
                                jnp.where(pos_pg == i1_d, 1, 2)
                                ).astype(jnp.int32)
                return acc + builder(page, gp_pg, rel, 2, self.max_nbins,
                                     method=self.hist_kernel)

            return body

        acc = self._acc_zeros(paged, gpair, 2, multi)
        return self._drive(
            paged, ("hist2", multi), make_body, acc,
            (gpair, positions, jnp.int32(i0), jnp.int32(i1)))

    def apply1(self, paged, positions, nid, feat, sbin, dleft, is_cat,
               words, left_id, right_id, missing_bin):
        """Lossguide one-node advance over the pages."""
        from .lossguide import _apply1

        W = int(np.asarray(words).shape[0])

        def make_body():
            def body(pos, page, s, consts):
                (nid_d, feat_d, sbin_d, dl_d, ic_d, words_d, li_d, ri_d,
                 mb_d) = consts
                p = page.shape[0]
                pos_pg = jax.lax.dynamic_slice_in_dim(pos, s, p)
                newp = _apply1(page, pos_pg, nid_d, feat_d, sbin_d, dl_d,
                               ic_d, words_d, li_d, ri_d, mb_d)
                return jax.lax.dynamic_update_slice_in_dim(pos, newp, s, 0)

            return body

        return self._drive(
            paged, ("apply1", W), make_body, positions,
            (nid, feat, sbin, dleft, is_cat, jnp.asarray(words), left_id,
             right_id, missing_bin))


def _host_allreduce(arr: jnp.ndarray) -> jnp.ndarray:
    """Sum across hosts through the CURRENT thread-local communicator —
    re-read on every call, never cached: growers persist on the booster
    across training continuations, and a communicator captured at
    construction would go stale (silently skipping the allreduce, or
    calling a dead one). The op is labeled for the resilient layer's
    integrity header: a rank stuck in the paged histogram reduce while a
    peer entered e.g. the sketch merge surfaces as a typed
    ``CollectiveDesync`` naming both call sites (docs/reliability.md)."""
    from ..parallel import collective
    from ..parallel.resilience import op_context

    comm = collective.get_communicator()
    if not comm.is_distributed():
        return arr
    with op_context("paged/hist"):
        return jnp.asarray(comm.allreduce(np.asarray(arr, np.float32),
                                          op="sum"))


class _MeshPageKernels:
    """Per-page shard_map kernels for external-memory training under a
    device mesh (VERDICT r3 #1): pages are ``[world*p_loc, F]`` arrays
    sharded over the mesh data axis, per-row vectors are ``[n_pad]``
    sharded, and every kernel slices its shard's page window out of the
    local per-row block at a DYNAMIC offset — so the whole run compiles
    ONE program per kernel family regardless of page count. The per-page
    histogram ends in the same ``lax.psum`` the resident mesh grower
    issues per level; pages stream per-shard exactly as they stream
    per-host in the communicator path (reference: SparsePageDMatrix feeds
    any updater under rabit row split with the async prefetch ring,
    ``src/data/sparse_page_source.h:180-200``)."""

    def __init__(self, mesh, max_nbins: int, missing_bin: int,
                 hist_kernel: str) -> None:
        from ..context import DATA_AXIS

        self.mesh = mesh
        self.axis = DATA_AXIS
        self.world = mesh.shape.get(DATA_AXIS, 1)
        self.max_nbins = max_nbins
        self.missing_bin = missing_bin
        self.hist_kernel = hist_kernel
        self._fns: dict = {}

    def init_positions(self, n_pad: int):
        import jax.sharding as jsh

        sharding = jsh.NamedSharding(self.mesh,
                                     jsh.PartitionSpec(self.axis))
        return jax.device_put(np.zeros(n_pad, np.int32), sharding)

    def _cached(self, key, build):
        fn = self._fns.get(key)
        if fn is None:
            fn = self._fns[key] = build()
        return fn

    # -- histograms ----------------------------------------------------------
    # Shard-LOCAL partial histograms accumulate across pages under a dummy
    # leading [world] axis sharded over the mesh (each device owns its
    # [1, ...] slice), and ONE psum per level folds them — not one
    # collective per page. The accumulator buffer is donated page-to-page.
    def _acc_zeros(self, shape):
        import jax.sharding as jsh

        def build():
            sh = jsh.NamedSharding(
                self.mesh,
                jsh.PartitionSpec(self.axis, *([None] * (len(shape) - 1))))
            return jax.jit(lambda: jnp.zeros(shape, jnp.float32),
                           out_shardings=sh)

        return self._cached(("zeros", shape), build)()

    def _drive(self, paged, key, make_body, carry, carry_spec, consts,
               consts_spec):
        """Mesh twin of ``_PageKernels._drive``: one fused shard_map
        dispatch over every HBM-cached page, then the prefetch ring for
        the overflow — the per-page dispatch RTT is the same tax on every
        tier. ``body(carry, page, s_loc, consts)`` is shard-local.

        Carry donation is skipped on the CPU backend: XLA:CPU aborts
        executing donated shard_map programs under the 8-virtual-device
        test platform (jax 0.4.x; deterministic — the page loop of the
        uneven-rows paged-mesh test dies inside the runtime, not in
        trace/compile). Donation only saves an HBM copy of the carry on
        real accelerators, so CPU keeps the copy and its stability."""
        P = jax.sharding.PartitionSpec
        dec = _page_decoder(paged)
        key = key + _page_key(paged)
        donate = ({} if jax.default_backend() == "cpu"
                  else {"donate_argnums": 0})
        page_spec = P(self.axis, None)
        cached, streamed = paged.cached_split_mesh(self.world)
        if cached:
            def build_fused():
                body = make_body()

                def fn(carry, consts, starts, pages):
                    for st, page in zip(starts, pages):
                        carry = body(carry, dec(page), st, consts)
                    return carry

                return jax.jit(jax.shard_map(
                    fn, mesh=self.mesh,
                    in_specs=(carry_spec, consts_spec, P(), page_spec),
                    out_specs=carry_spec), **donate)

            fused = self._cached(key + ("fused",), build_fused)
            carry = fused(carry, consts,
                          tuple(jnp.int32(s) for s, _ in cached),
                          tuple(p for _, p in cached))
        if streamed:
            def build_single():
                body = make_body()
                return jax.jit(jax.shard_map(
                    lambda carry, page, s, consts:
                    body(carry, dec(page), s, consts),
                    mesh=self.mesh,
                    in_specs=(carry_spec, page_spec, P(), consts_spec),
                    out_specs=carry_spec), **donate)

            single = self._cached(key + ("single",), build_single)
            for s_loc, page in paged.stream_pages_sharded(
                    streamed, self.mesh, self.axis):
                carry = single(carry, page, jnp.int32(s_loc), consts)
        return carry

    def _hist_over_pages(self, paged, gpair, positions, rel_fn, n_nodes,
                         multi, key, extra, nbins=None, data_fn=None):
        """Shared page loop: ``rel_fn(pos_page, *extra)`` maps positions to
        node slots; ``extra`` are traced scalars (level bounds / node ids)
        or replicated arrays. ``data_fn(page, rel, *extra)`` optionally
        rewrites the binned page before the build (two-level coarse /
        refine passes); ``nbins`` overrides the histogram width.
        """
        P = jax.sharding.PartitionSpec
        axis = self.axis
        K = gpair.shape[1] if multi else None
        B = nbins or self.max_nbins
        gspec = P(axis, None, None) if multi else P(axis, None)
        acc_spec = P(axis, *([None] * (4 + int(multi))))

        def make_body():
            from ..ops.histogram import build_hist_multi

            builder = build_hist_multi if multi else build_hist

            def body(acc, page, s_loc, consts):
                gp, pos = consts[:2]
                extra_d = consts[2:]
                p = page.shape[0]
                gp_pg = jax.lax.dynamic_slice_in_dim(gp, s_loc, p)
                pos_pg = jax.lax.dynamic_slice_in_dim(pos, s_loc, p)
                rel = rel_fn(pos_pg, *extra_d)
                data = page if data_fn is None else data_fn(page, rel,
                                                            *extra_d)
                h = builder(data, gp_pg, rel, n_nodes, B,
                            method=self.hist_kernel)
                return acc + h[None]

            return body

        def build_fin():
            return jax.jit(jax.shard_map(
                lambda acc: jax.lax.psum(acc[0], axis), mesh=self.mesh,
                in_specs=(acc_spec,), out_specs=P()))

        fin = self._cached(key + ("fin", K), build_fin)
        shape = ((self.world, n_nodes, paged.n_features, B)
                 + ((K, 2) if multi else (2,)))
        acc = self._acc_zeros(shape)
        acc = self._drive(
            paged, key + ("acc", K), make_body, acc, acc_spec,
            (gpair, positions) + tuple(extra),
            (gspec, P(axis)) + (P(),) * len(extra))
        return fin(acc)

    def level_hist(self, paged, gpair, positions, lo: int, n_level: int,
                   n_static: int, multi: bool = False):
        """One depthwise level histogram over the pages (one-pass scheme;
        the two-level coarse schedule routes through
        ``coarse_pass``/``refine_pass``)."""
        def rel_fn(pos_pg, lo_d, n_level_d):
            return _rel_of(pos_pg, lo_d, n_level_d, n_static)

        return self._hist_over_pages(
            paged, gpair, positions, rel_fn, n_static, multi,
            ("hist", n_static), (jnp.int32(lo), jnp.int32(n_level)))

    def adv_hist(self, paged, gpair, positions, prev, lo, n_level, n_static,
                 multi=False):
        """Fused advance(previous level) + histogram(this level);
        shard-local partials accumulate across pages and psum once at
        level end."""
        P = jax.sharding.PartitionSpec
        axis = self.axis
        kind = prev["kind"]
        cat = prev["cat"]
        n_arr = len(prev["arrs"])
        W = None if cat is None else int(cat[1].shape[1])
        K = gpair.shape[1] if multi else None
        B = self.max_nbins
        gspec = P(axis, None, None) if multi else P(axis, None)
        acc_spec = P(axis, *([None] * (4 + int(multi))))

        def make_body():
            from ..ops.histogram import build_hist_multi

            builder = build_hist_multi if multi else build_hist

            def body(carry, page, s_loc, consts):
                pos, acc = carry
                gp, lo_prev, nl_prev, lo_d, nl_d = consts[:5]
                arrs = consts[5:5 + n_arr]
                cat_args = consts[5 + n_arr:]
                p = page.shape[0]
                pos_pg = jax.lax.dynamic_slice_in_dim(pos, s_loc, p)
                gp_pg = jax.lax.dynamic_slice_in_dim(gp, s_loc, p)
                newp = _advance_rows(page, pos_pg, kind, arrs, cat_args,
                                     lo_prev, nl_prev, n_static,
                                     self.missing_bin)
                pos = jax.lax.dynamic_update_slice_in_dim(pos, newp, s_loc,
                                                          0)
                rel = _rel_of(newp, lo_d, nl_d, n_static)
                h = builder(page, gp_pg, rel, n_static, B,
                            method=self.hist_kernel)
                return pos, acc + h[None]

            return body

        def build_fin():
            return jax.jit(jax.shard_map(
                lambda acc: jax.lax.psum(acc[0], axis), mesh=self.mesh,
                in_specs=(acc_spec,), out_specs=P()))

        fin = self._cached(("hist", n_static, "fin", K), build_fin)
        shape = ((self.world, n_static, paged.n_features, B)
                 + ((K, 2) if multi else (2,)))
        acc = self._acc_zeros(shape)
        extra = prev["arrs"] + (() if cat is None else tuple(cat))
        consts = (gpair, jnp.int32(prev["lo"]), jnp.int32(prev["n_level"]),
                  jnp.int32(lo), jnp.int32(n_level)) + extra
        positions, acc = self._drive(
            paged, ("advhist", kind, n_static, multi, W),
            make_body, (positions, acc), (P(axis), acc_spec),
            consts, (gspec,) + (P(),) * (len(consts) - 1))
        return positions, fin(acc)

    # -- page-major two-level (coarse) schedule ------------------------------
    # Mesh twin of _PageKernels.coarse_pass/refine_pass: each shard's
    # streamed pages upload ONCE per level (advance + direct coarse +
    # fine partial in one shard_map dispatch); the refine fold adds each
    # shard's fine window slice to its cached-page direct partial BEFORE
    # the single psum, so the cross-shard reduction happens on the small
    # refine accumulator, never by re-streaming bins.

    def coarse_pass(self, paged, gpair, positions, prev, lo, n_level,
                    n_static, cached, streamed):
        """-> (positions, hist_c replicated, fine-or-None). ``fine`` keeps
        its leading [world] shard axis — ``refine_pass`` slices it
        shard-locally and folds it into the refine psum."""
        from ..ops.split import COARSE_B

        P = jax.sharding.PartitionSpec
        axis = self.axis
        kind = None if prev is None else prev["kind"]
        cat = None if prev is None else prev["cat"]
        n_arr = 0 if prev is None else len(prev["arrs"])
        W = None if cat is None else int(cat[1].shape[1])
        dec = _page_decoder(paged)
        mb = self.missing_bin
        hk = self.hist_kernel
        F = paged.n_features
        donate = ({} if jax.default_backend() == "cpu"
                  else {"donate_argnums": 0})
        page_spec = P(axis, None)
        acc_spec = P(axis, None, None, None, None)
        gspec = P(axis, None)

        def make_body(fine):
            def body(carry, page, s_loc, consts):
                pos, acc = carry[0], carry[1]
                gp, lo_prev, nl_prev, lo_d, nl_d = consts[:5]
                arrs = consts[5:5 + n_arr]
                cat_args = consts[5 + n_arr:]
                page = dec(page)
                p = page.shape[0]
                pos_pg = jax.lax.dynamic_slice_in_dim(pos, s_loc, p)
                gp_pg = jax.lax.dynamic_slice_in_dim(gp, s_loc, p)
                if kind is not None:
                    pos_pg = _advance_rows(page, pos_pg, kind, arrs,
                                           cat_args, lo_prev, nl_prev,
                                           n_static, mb)
                    pos = jax.lax.dynamic_update_slice_in_dim(
                        pos, pos_pg, s_loc, 0)
                rel = _rel_of(pos_pg, lo_d, nl_d, n_static)
                acc = acc + build_hist(_coarse_bins(page, mb), gp_pg, rel,
                                       n_static, COARSE_B,
                                       method=hk)[None]
                if not fine:
                    return pos, acc
                af = carry[2] + build_hist(page, gp_pg, rel, n_static,
                                           self.max_nbins,
                                           method=hk)[None]
                return pos, acc, af

            return body

        consts = (gpair,
                  jnp.int32(0 if prev is None else prev["lo"]),
                  jnp.int32(0 if prev is None else prev["n_level"]),
                  jnp.int32(lo), jnp.int32(n_level))
        if prev is not None:
            consts = consts + prev["arrs"] + (() if cat is None
                                              else tuple(cat))
        consts_spec = (gspec,) + (P(),) * (len(consts) - 1)
        key = ("cpass", kind, n_static, W) + _page_key(paged)
        carry = (positions,
                 self._acc_zeros((self.world, n_static, F, COARSE_B, 2)))
        carry_spec = (P(axis), acc_spec)
        if cached:
            def build_fused():
                body = make_body(False)

                def fn(carry, consts, starts, pages):
                    for st, page in zip(starts, pages):
                        carry = body(carry, page, st, consts)
                    return carry

                return jax.jit(jax.shard_map(
                    fn, mesh=self.mesh,
                    in_specs=(carry_spec, consts_spec, P(), page_spec),
                    out_specs=carry_spec), **donate)

            fused = self._cached(key + ("fused",), build_fused)
            carry = fused(carry, consts,
                          tuple(jnp.int32(s) for s, _ in cached),
                          tuple(p for _, p in cached))
        fine = None
        if streamed:
            carry = carry + (self._acc_zeros(
                (self.world, n_static, F, self.max_nbins, 2)),)
            carry_spec = carry_spec + (acc_spec,)

            def build_single():
                body = make_body(True)
                return jax.jit(jax.shard_map(
                    lambda carry, page, s, consts:
                    body(carry, page, s, consts),
                    mesh=self.mesh,
                    in_specs=(carry_spec, page_spec, P(), consts_spec),
                    out_specs=carry_spec), **donate)

            single = self._cached(key + ("single",), build_single)
            for s_loc, page in paged.stream_pages_sharded(
                    streamed, self.mesh, self.axis):
                carry = single(carry, page, jnp.int32(s_loc), consts)
            fine = carry[2]

        def build_fin():
            return jax.jit(jax.shard_map(
                lambda acc: jax.lax.psum(acc[0], axis), mesh=self.mesh,
                in_specs=(acc_spec,), out_specs=P()))

        fin = self._cached(("cpass_fin", n_static), build_fin)
        return carry[0], fin(carry[1]), fine

    def refine_pass(self, paged, gpair, positions, span, lo, n_level,
                    n_static, cached, fine=None):
        """Refine fold: direct build over the level's CACHED pages plus
        each shard's fine window slice, combined shard-locally and summed
        in ONE psum — streamed pages are never re-uploaded."""
        from ..ops.split import WINDOW, refine_from_fine

        P = jax.sharding.PartitionSpec
        axis = self.axis
        dec = _page_decoder(paged)
        mb = self.missing_bin
        hk = self.hist_kernel
        F = paged.n_features
        donate = ({} if jax.default_backend() == "cpu"
                  else {"donate_argnums": 0})
        page_spec = P(axis, None)
        acc_spec = P(axis, None, None, None, None)
        consts_spec = (P(axis, None), P(axis), P(), P(), P())
        acc = self._acc_zeros((self.world, n_static, F, WINDOW + 4, 2))
        consts = (gpair, positions, jnp.int32(lo), jnp.int32(n_level),
                  span)
        key = ("rpass", n_static) + _page_key(paged)
        if cached:
            def build_fused():
                def body(acc, page, s_loc, consts):
                    gp, pos, lo_d, nl_d, span_d = consts
                    page = dec(page)
                    p = page.shape[0]
                    pos_pg = jax.lax.dynamic_slice_in_dim(pos, s_loc, p)
                    gp_pg = jax.lax.dynamic_slice_in_dim(gp, s_loc, p)
                    rel = _rel_of(pos_pg, lo_d, nl_d, n_static)
                    rb = _refine_bins(page, rel, span_d, n_static, mb)
                    return acc + build_hist(rb, gp_pg, rel, n_static,
                                            WINDOW + 4, method=hk)[None]

                def fn(acc, consts, starts, pages):
                    for st, page in zip(starts, pages):
                        acc = body(acc, page, st, consts)
                    return acc

                return jax.jit(jax.shard_map(
                    fn, mesh=self.mesh,
                    in_specs=(acc_spec, consts_spec, P(), page_spec),
                    out_specs=acc_spec), **donate)

            fused = self._cached(key, build_fused)
            acc = fused(acc, consts,
                        tuple(jnp.int32(s) for s, _ in cached),
                        tuple(p for _, p in cached))
        has_fine = fine is not None

        def build_fin():
            if has_fine:
                def fin(acc, fine, span_d):
                    local = (acc[0][:, :, :WINDOW, :]
                             + refine_from_fine(fine[0], span_d, mb))
                    return jax.lax.psum(local, axis)

                return jax.jit(jax.shard_map(
                    fin, mesh=self.mesh,
                    in_specs=(acc_spec, acc_spec, P()), out_specs=P()))
            return jax.jit(jax.shard_map(
                lambda acc: jax.lax.psum(acc[0][:, :, :WINDOW, :], axis),
                mesh=self.mesh, in_specs=(acc_spec,), out_specs=P()))

        fin = self._cached(("rpass_fin", n_static, has_fine), build_fin)
        return fin(acc, fine, span) if has_fine else fin(acc)

    def final_advance(self, paged, positions, prev, n_static):
        """Advance-only pass for the LAST evaluated level (leaf routing)."""
        if prev["kind"] == "dense":
            return self.level_advance(paged, positions, prev["lo"],
                                      prev["n_level"], *prev["arrs"],
                                      cat=prev["cat"])
        sf, sb, dl, isf = prev["arrs"]
        return self.walk_advance(paged, positions, sf, sb, dl, isf,
                                 cat=prev["cat"])

    def pair_hist(self, paged, gpair, positions, i0, i1, multi=False):
        """Two-node (lossguide sibling pair) histogram over the pages
        (K-channel with ``multi`` — the vector-leaf lossguide)."""
        def rel_fn(pos_pg, i0_d, i1_d):
            return jnp.where(pos_pg == i0_d, 0,
                             jnp.where(pos_pg == i1_d, 1, 2)
                             ).astype(jnp.int32)

        return self._hist_over_pages(
            paged, gpair, positions, rel_fn, 2, multi, ("hist2",),
            (jnp.int32(i0), jnp.int32(i1)))

    # -- position advances ---------------------------------------------------
    def level_advance(self, paged, positions, lo, n_level, feat, sbin,
                      dleft, cs, cat=None):
        """Dense (matmul) one-level advance; per-node arrays replicated."""
        P = jax.sharding.PartitionSpec
        n_static = int(feat.shape[0])
        W = None if cat is None else int(cat[1].shape[1])

        def make_body():
            def body(pos, page, s_loc, consts):
                lo_d, n_level_d, feat_d, sbin_d, dl_d, cs_d = consts[:6]
                cat_args = consts[6:]
                p = page.shape[0]
                pos_pg = jax.lax.dynamic_slice_in_dim(pos, s_loc, p)
                rel = jnp.where(
                    (pos_pg >= lo_d) & (pos_pg < lo_d + n_level_d),
                    pos_pg - lo_d, n_static).astype(jnp.int32)
                kw = ({} if not cat_args
                      else dict(is_cat=cat_args[0], cat_words=cat_args[1]))
                newp = advance_positions_level(
                    page.astype(jnp.float32), pos_pg, rel, feat_d, sbin_d,
                    dl_d, cs_d, self.missing_bin, **kw)
                return jax.lax.dynamic_update_slice_in_dim(
                    pos, newp, s_loc, 0)

            return body

        extra = () if cat is None else tuple(cat)
        consts = (jnp.int32(lo), jnp.int32(n_level), feat, sbin, dleft,
                  cs) + extra
        return self._drive(
            paged, ("adv", n_static, W), make_body, positions, P(self.axis),
            consts, (P(),) * len(consts))

    def walk_advance(self, paged, positions, sf, sb, dl, isf, cat=None):
        """Deep-level per-row gather walk; full tree arrays replicated."""
        P = jax.sharding.PartitionSpec
        W = None if cat is None else int(cat[1].shape[1])
        max_nodes = int(sf.shape[0])

        def make_body():
            def body(pos, page, s_loc, consts):
                sf_d, sb_d, dl_d, isf_d = consts[:4]
                cat_args = consts[4:]
                p = page.shape[0]
                pos_pg = jax.lax.dynamic_slice_in_dim(pos, s_loc, p)
                kw = ({} if not cat_args
                      else dict(is_cat_split=cat_args[0],
                                cat_words=cat_args[1]))
                newp = update_positions(page, pos_pg, sf_d, sb_d, dl_d,
                                        isf_d, self.missing_bin, **kw)
                return jax.lax.dynamic_update_slice_in_dim(
                    pos, newp, s_loc, 0)

            return body

        extra = () if cat is None else tuple(cat)
        consts = (sf, sb, dl, isf) + extra
        return self._drive(
            paged, ("walk", max_nodes, W), make_body, positions,
            P(self.axis), consts, (P(),) * len(consts))

    def apply1(self, paged, positions, nid, feat, sbin, dleft, is_cat,
               words, left_id, right_id, missing_bin):
        """Lossguide one-node advance over the pages."""
        from .lossguide import _apply1

        P = jax.sharding.PartitionSpec
        W = int(words.shape[0])

        def make_body():
            def body(pos, page, s_loc, consts):
                (nid_d, feat_d, sbin_d, dl_d, ic_d, words_d, li_d, ri_d,
                 mb_d) = consts
                p = page.shape[0]
                pos_pg = jax.lax.dynamic_slice_in_dim(pos, s_loc, p)
                newp = _apply1(page, pos_pg, nid_d, feat_d, sbin_d, dl_d,
                               ic_d, words_d, li_d, ri_d, mb_d)
                return jax.lax.dynamic_update_slice_in_dim(
                    pos, newp, s_loc, 0)

            return body

        consts = (nid, feat, sbin, dleft, is_cat, jnp.asarray(words),
                  left_id, right_id, missing_bin)
        return self._drive(
            paged, ("apply1", W), make_body, positions, P(self.axis),
            consts, (P(),) * len(consts))


class PagedGrower(TreeGrower):
    """Grows one tree from a ``PagedBinnedMatrix`` (host-resident bins)."""

    def __init__(self, param, max_nbins, cuts, hist_method="auto",
                 mesh=None, monotone=None, constraint_sets=None,
                 has_missing=True, split_mode="row") -> None:
        if split_mode != "row":
            raise NotImplementedError(
                "external-memory training supports data_split_mode=row only")
        # parent keeps mesh=None: its resident shard_map path must never
        # see paged data — the mesh drives _MeshPageKernels instead
        super().__init__(param, max_nbins, cuts, hist_method=hist_method,
                         mesh=None, monotone=monotone,
                         constraint_sets=constraint_sets,
                         has_missing=has_missing, split_mode="row")
        self.mesh = mesh
        self._mk = None
        self._ev: Optional[_LevelEvaluator] = None
        self._coarse = False

    def grow(self, paged, gpair: jnp.ndarray, n_real_bins,
             key: jax.Array) -> GrownTree:
        param = self.param
        # mesh-sharded paging: per-row vectors come padded to the mesh
        # layout (core._make_sharded_train_state), pages stream sharded
        n = gpair.shape[0]
        if self._mk is None:
            # two-level coarse->refine histogram over pages (explicit
            # hist_method="coarse", or the "auto" promotion rule at
            # scale): both passes accumulate across pages, the window
            # choice is node-level after the coarse pass — decided once
            # (n is fixed per DMatrix), before the kernels are built so
            # their underlying builds run the plain kernel selection
            if self.hist_method in TWO_LEVEL_METHODS and (
                    self.cat is not None
                    or self.max_nbins > 256 + int(self.has_missing)):
                raise NotImplementedError(
                    f"hist_method='{self.hist_method}' supports numeric "
                    "features and max_bin <= 256")
            # the promotion threshold is LOCAL rows per shard (the
            # measured crossover is per-device work); on the mesh tier
            # gpair is the padded GLOBAL row count
            if self.mesh is not None:
                from ..context import DATA_AXIS

                n_local = n // self.mesh.shape.get(DATA_AXIS, 1)
            else:
                n_local = n
            # "fused" and "coarse" are one schedule here: the advance +
            # coarse page pass is one body (adv_hist)
            self._coarse = resolve_schedule(
                self.hist_method, n_local, self.max_nbins,
                self.has_missing, numeric=self.cat is None).coarse
            self._mk = _make_kernels(self)
        max_depth = param.max_depth
        max_nodes = 2 ** (max_depth + 1) - 1
        cat = self.cat
        mono_np = (None if self.monotone is None
                   else np.asarray(self.monotone))

        n_real = np.asarray(n_real_bins)
        base_mask = jnp.asarray(n_real) > 0
        tree_mask = _sample_features(jax.random.fold_in(key, 0xC0),
                                     base_mask, param.colsample_bytree)
        key = jax.random.fold_in(key, 0x5EED)

        # One static node width (2^(max_depth-1), the widest level) for
        # EVERY per-page program: per-width jits would compile
        # O(page_shapes x level_widths) programs, and XLA compilation on a
        # single-core host costs ~50 s per program — the dominant cost of
        # the first paged round. Pad nodes carry zero stats so they can
        # never win a split.
        n_static = 2 ** (max_depth - 1) if max_depth > 0 else 1
        deep = n_static > 64
        if self._ev is None:
            self._ev = _LevelEvaluator(self, n_static, max_nodes, deep,
                                       n_real, coarse=self._coarse)

        # Multi-host external memory (reference: rabit row split over
        # SparsePageDMatrix, src/data/sparse_page_dmatrix.cc): each process
        # streams only ITS row shard's pages; the per-level histogram and
        # the root gradient sum cross hosts through the communicator —
        # the same two allreduces the mesh path does with lax.psum.
        positions = self._mk.init_positions(n)  # device-resident [n]
        root_sum = jnp.asarray(_host_allreduce(jnp.sum(gpair, axis=0)),
                               jnp.float32)
        state = self._ev.init_state(root_sum)

        # ---- device loop: ZERO blocking host syncs on a single host ----
        # PAGE-MAJOR schedule per level boundary: when every page sits in
        # the HBM cache (and no host communicator must allreduce between
        # sweeps) the ENTIRE level — advance + histogram(s) + window +
        # eval — runs as ONE jitted dispatch (level_full). Otherwise each
        # streamed page uploads ONCE per level: its single visit carries
        # the advance, the direct coarse partial and a full fine partial,
        # and the refine contribution is a window slice of that fine
        # accumulator (coarse_pass/refine_pass) — the r5/r6 schedule
        # re-uploaded every streamed page twice per level. The host pulls
        # every level's decisions in ONE packed transfer at tree end.
        from ..parallel import collective as _coll

        stashes = []
        prev = None
        single_dev = isinstance(self._mk, _PageKernels)
        for depth in range(max_depth):
            lo = 2 ** depth - 1
            n_level = 2 ** depth
            # freeze the level's page partition: a page uploaded (and
            # cached) during the first sweep must not be double-counted
            # by the refine sweep
            if single_dev:
                cached, streamed = paged.cached_split()
            else:
                cached, streamed = paged.cached_split_mesh(self._mk.world)
            distributed = _coll.get_communicator().is_distributed()
            # Host spans per stage: this loop is the one place tree
            # growth has REAL host-visible stage boundaries (the resident
            # path is one jitted dispatch, labeled with obs.trace.stage
            # scopes instead). The dispatches are async, so a span times
            # the dispatch; the stage's device time is the profiler
            # trace's to give.
            if single_dev and cached and not streamed and not distributed:
                with _trace.span("paged/level_full",
                                 args={"depth": depth}):
                    positions, stash, state, prev = self._mk.level_full(
                        paged, gpair, positions, prev, lo, n_level,
                        n_static, self._ev, state, tree_mask, key, depth,
                        cached)
            elif self._coarse:
                with _trace.span("paged/hist",
                                 args={"depth": depth}):
                    positions, hist_c, fine = self._mk.coarse_pass(
                        paged, gpair, positions, prev, lo, n_level,
                        n_static, cached, streamed)
                with _trace.span("paged/exchange"):
                    hist_c = _host_allreduce(hist_c)
                # node-level window choice from the GLOBAL coarse hist
                # (allreduced above, so every host/shard refines the same
                # windows); cached pages re-read HBM for the refine,
                # streamed pages' refine comes from their fine partials
                with _trace.span("paged/window"):
                    span = self._ev.choose_window(hist_c, state)
                with _trace.span("paged/refine",
                                 args={"depth": depth}):
                    hist_r = self._mk.refine_pass(
                        paged, gpair, positions, span, lo, n_level,
                        n_static, cached, fine=fine)
                with _trace.span("paged/exchange"):
                    hist_r = _host_allreduce(hist_r)
                with _trace.span("paged/eval"):
                    stash, state, prev = self._ev(
                        (hist_c, hist_r, span), state, tree_mask, key,
                        jnp.int32(depth), jnp.int32(lo),
                        jnp.int32(n_level))
            else:
                with _trace.span("paged/hist",
                                 args={"depth": depth}):
                    if prev is None:
                        hist = self._mk.level_hist(paged, gpair,
                                                   positions, lo, n_level,
                                                   n_static)
                    else:
                        positions, hist = self._mk.adv_hist(
                            paged, gpair, positions, prev, lo, n_level,
                            n_static)
                with _trace.span("paged/exchange"):
                    hist = _host_allreduce(hist)
                with _trace.span("paged/eval"):
                    stash, state, prev = self._ev(
                        hist, state, tree_mask, key, jnp.int32(depth),
                        jnp.int32(lo), jnp.int32(n_level))
            stashes.append(stash)
            # level boundary: HBM watermark sample (free when the
            # memory monitor is off — the page cache + ring buffers peak
            # here, between the level's last upload and its eval)
            _mem.sample("paged/level")
            # ONE-BEHIND early stop: the previous level's eval finished
            # long before this level's page passes were even dispatched, so
            # this tiny pull costs one RTT that overlaps the device's
            # current work — and a tree that stops splitting early stops
            # paying full page passes for the remaining depth budget (at
            # most one dead level's passes are wasted)
            if depth > 0 and not np.asarray(
                    stashes[depth - 1]["can_split"]).any():
                prev = None
                break
        if prev is not None:  # route rows below the deepest splits
            with _trace.span("paged/advance"):
                positions = self._mk.final_advance(paged, positions, prev,
                                                   n_static)

        # ---- host bookkeeping replay (one packed pull for the tree) ----
        with _trace.span("paged/fetch"):
            fetched = fetch_packed(stashes + [{"root": root_sum}])
        split_feature = np.full(max_nodes, -1, np.int32)
        split_bin = np.zeros(max_nodes, np.int32)
        default_left = np.zeros(max_nodes, bool)
        is_leaf = np.ones(max_nodes, bool)
        active = np.zeros(max_nodes, bool)
        active[0] = True
        gain = np.zeros(max_nodes, np.float32)
        node_sum = np.zeros((max_nodes, 2), np.float32)
        node_sum[0] = fetched[-1]["root"]
        is_cat_split = np.zeros(max_nodes, bool)
        cat_words = np.zeros((max_nodes, self._ev.n_words), np.uint32)
        if mono_np is not None:
            # per-node weight bounds (reference TreeEvaluator lower/upper)
            node_lower = np.full(max_nodes, -np.inf, np.float32)
            node_upper = np.full(max_nodes, np.inf, np.float32)
        for depth, st in enumerate(fetched[:-1]):
            lo = 2 ** depth - 1
            n_level = 2 ** depth
            can_split = st["can_split"][:n_level]
            res_gain = st["gain"][:n_level]
            idx = lo + np.arange(n_level)
            r_feat = st["feature"][:n_level]
            split_feature[idx] = np.where(can_split, r_feat, -1)
            split_bin[idx] = np.where(can_split, st["bin"][:n_level], 0)
            default_left[idx] = can_split & st["default_left"][:n_level]
            is_leaf[idx] = ~can_split
            gain[idx] = np.where(can_split, res_gain, 0.0)
            if cat is not None:
                r_iscat = st["is_cat"][:n_level]
                is_cat_split[idx] = can_split & r_iscat
                cat_words[idx] = np.where(
                    (can_split & r_iscat)[:, None],
                    st["cat_words"][:n_level], np.uint32(0))
            li, ri = 2 * idx + 1, 2 * idx + 2
            active[li] = can_split
            active[ri] = can_split
            ls = st["left_sum"][:n_level]
            rs = st["right_sum"][:n_level]
            node_sum[li] = np.where(can_split[:, None], ls, 0.0)
            node_sum[ri] = np.where(can_split[:, None], rs, 0.0)
            if mono_np is not None:
                (l_lo, l_hi), (r_lo, r_hi) = monotone_child_bounds_host(
                    ls, rs, r_feat, node_lower[lo:lo + n_level],
                    node_upper[lo:lo + n_level], mono_np, param)
                node_lower[li] = np.where(can_split, l_lo, 0.0)
                node_upper[li] = np.where(can_split, l_hi, 0.0)
                node_lower[ri] = np.where(can_split, r_lo, 0.0)
                node_upper[ri] = np.where(can_split, r_hi, 0.0)
            if not can_split.any():
                break

        w = np.asarray(calc_weight(jnp.asarray(node_sum[:, 0]),
                                   jnp.asarray(node_sum[:, 1]), param))
        if mono_np is not None:
            w = np.clip(w, node_lower, node_upper)
        w = w * param.eta
        leaf_value = np.where(active & is_leaf, w, 0.0).astype(np.float32)
        base_weight = np.where(active, w, 0.0).astype(np.float32)
        delta = jnp.asarray(leaf_value)[positions]  # device gather [n]

        g = GrownTree(
            split_feature=split_feature, split_bin=split_bin,
            default_left=default_left, is_leaf=is_leaf, active=active,
            leaf_value=leaf_value, node_sum=node_sum, gain=gain,
            positions=positions, delta=delta,
            is_cat_split=is_cat_split, cat_words=cat_words,
            base_weight=base_weight)
        if param.max_leaves > 0:
            # reference Driver schedule over the fully grown level tree —
            # the same host-side truncation the resident path applies
            g = self._truncate_max_leaves(g)
        return g


class PagedLossguideGrower(LossguideGrower):
    """Loss-guided growth over a ``PagedBinnedMatrix``: the greedy pop loop
    is unchanged (LossguideGrower.grow), but each split's two device
    kernels — the two-child histogram and the one-node position advance —
    stream over the host-resident pages instead of touching a resident bin
    tensor (reference: the lossguide hist updater drives the same page
    loop as depthwise, ``src/tree/updater_quantile_hist.cc`` +
    ``src/tree/driver.h`` LossGuide ordering). Multi-host: each process
    streams its own row shard; the per-split child histogram crosses hosts
    through the communicator, exactly like ``PagedGrower``."""

    def __init__(self, param, max_nbins, cuts, hist_method="auto",
                 mesh=None, monotone=None, constraint_sets=None,
                 has_missing=True, split_mode="row") -> None:
        if split_mode != "row":
            raise NotImplementedError(
                "external-memory training supports data_split_mode=row only")
        # parent keeps mesh=None: its resident shard_map _functions must
        # never see paged data — the mesh drives _MeshPageKernels instead
        super().__init__(param, max_nbins, cuts, hist_method=hist_method,
                         mesh=None, monotone=monotone,
                         constraint_sets=constraint_sets,
                         has_missing=has_missing)
        if self.hist_method in TWO_LEVEL_METHODS:
            raise NotImplementedError(
                f"hist_method='{self.hist_method}' with grow_policy="
                "lossguide runs on resident matrices only (the paged "
                "per-split kernels use the one-pass build)")
        self._coarse = False  # page kernels ignore the resident auto rule
        self._fused = False   # per-split page loops stay two-dispatch
        self.mesh = mesh
        self._mk: Optional[_MeshPageKernels] = None

    def _init_positions(self, n: int) -> jnp.ndarray:
        if self._mk is None:
            self._mk = _make_kernels(self)
        return self._mk.init_positions(n)

    def _functions(self):
        if self._fns is not None:
            return self._fns
        if self._mk is None:
            self._mk = _make_kernels(self)
        mk = self._mk

        def eval2(paged, gpair, positions, i0, i1, psums, fmask,
                  node_lower, node_upper, n_real_bins, bins_t=None,
                  cb_t=None):
            del bins_t, cb_t  # pages window in-program inside the kernels
            hist = _host_allreduce(mk.pair_hist(paged, gpair, positions,
                                                i0, i1))
            return evaluate_splits(hist, psums, n_real_bins, self.param,
                                   feature_mask=fmask,
                                   monotone=self.monotone,
                                   node_lower=node_lower,
                                   node_upper=node_upper, cat=self.cat,
                                   has_missing=self.has_missing)

        def apply1(paged, positions, nid, feat, sbin, dleft, is_cat,
                   words, left_id, right_id, missing_bin):
            return mk.apply1(paged, positions, nid, feat, sbin, dleft,
                             is_cat, words, left_id, right_id, missing_bin)

        def root_sum(gpair):
            return _host_allreduce(jnp.sum(gpair, axis=0))

        gather = jax.jit(lambda lv, pos: lv[pos])
        self._fns = (eval2, apply1, root_sum, gather)
        return self._fns


class PagedMultiTargetGrower(MultiTargetGrower):
    """Vector-leaf (``multi_strategy=multi_output_tree``) growth over a
    ``PagedBinnedMatrix``: the depthwise level loop of ``PagedGrower`` with
    a K-channel gradient — per depth, one streamed K-target histogram pass
    and one streamed advance pass (reference: ``MultiTargetHistBuilder``
    iterates ``GetBatches<GHistIndexMatrix>`` exactly like the scalar
    builder, ``src/tree/updater_quantile_hist.cc:117-263``). Multi-host
    works the same way as ``PagedGrower``: per-level histogram and root
    sum cross hosts through the communicator."""

    def __init__(self, param, max_nbins, cuts, hist_method="auto",
                 mesh=None, has_missing=True, constraint_sets=None,
                 split_mode="row") -> None:
        if split_mode != "row":
            raise NotImplementedError(
                "external-memory training supports data_split_mode=row only")
        # parent keeps mesh=None: its resident shard_map path must never
        # see paged data — the mesh drives _MeshPageKernels instead
        super().__init__(param, max_nbins, cuts, hist_method=hist_method,
                         mesh=None, has_missing=has_missing,
                         constraint_sets=constraint_sets)
        self.mesh = mesh
        self._mk: Optional[_MeshPageKernels] = None

    def grow(self, paged, gpair: jnp.ndarray, n_real_bins, key: jax.Array):
        from .multi import GrownMulti, evaluate_splits_multi

        param = self.param
        n, K = gpair.shape[0], gpair.shape[1]
        if self._mk is None:
            self._mk = _make_kernels(self)
        max_depth = param.max_depth
        max_nodes = 2 ** (max_depth + 1) - 1
        cons = (None if self.constraint_sets is None
                else np.asarray(self.constraint_sets))
        n_real = np.asarray(n_real_bins)
        F = paged.n_features
        tree_mask = _sample_features(jax.random.fold_in(key, 0xC0),
                                     jnp.ones((F,), bool),
                                     param.colsample_bytree)
        key = jax.random.fold_in(key, 0x5EED)

        split_feature = np.full(max_nodes, -1, np.int32)
        split_bin = np.zeros(max_nodes, np.int32)
        default_left = np.zeros(max_nodes, bool)
        is_leaf = np.ones(max_nodes, bool)
        active = np.zeros(max_nodes, bool)
        active[0] = True
        gain = np.zeros(max_nodes, np.float32)
        node_sum = np.zeros((max_nodes, K, 2), np.float32)
        if cons is not None:
            node_path = np.zeros((max_nodes, cons.shape[1]), bool)
        node_sum[0] = np.asarray(_host_allreduce(jnp.sum(gpair, axis=0)))
        positions = self._mk.init_positions(n)
        n_static = 2 ** (max_depth - 1) if max_depth > 0 else 1

        prev = None
        for depth in range(max_depth):
            lo = 2 ** depth - 1
            n_level = 2 ** depth

            with _trace.span("paged/hist",
                             args={"depth": depth}):
                if prev is None:
                    hist = self._mk.level_hist(paged, gpair, positions,
                                               lo, n_level, n_static,
                                               multi=True)
                else:
                    positions, hist = self._mk.adv_hist(
                        paged, gpair, positions, prev, lo, n_level,
                        n_static, multi=True)
            with _trace.span("paged/exchange"):
                hist = _host_allreduce(hist)

            level_key = jax.random.fold_in(key, depth)
            fmask_level = _sample_features(level_key, tree_mask,
                                           param.colsample_bylevel)
            if param.colsample_bynode < 1.0:
                node_keys = jax.random.split(
                    jax.random.fold_in(level_key, 1), n_level)
                fmask = jax.vmap(
                    lambda k: _sample_features(k, fmask_level,
                                               param.colsample_bynode)
                )(node_keys)
                if n_level < n_static:
                    fmask = jnp.concatenate(
                        [fmask, jnp.zeros((n_static - n_level,
                                           fmask.shape[1]), bool)])
            else:
                fmask = fmask_level[None, :]

            if cons is not None:
                allowed = interaction_allowed_host(
                    node_path[lo:lo + n_level], cons)          # [N, Fc]
                allowed_pad = np.zeros((n_static, allowed.shape[1]), bool)
                allowed_pad[:n_level] = allowed
                if fmask.shape[0] == 1:
                    fmask = jnp.broadcast_to(fmask,
                                             (n_static, fmask.shape[1]))
                fmask = fmask & jnp.asarray(allowed_pad)

            parent_pad = np.zeros((n_static, K, 2), np.float32)
            parent_pad[:n_level] = node_sum[lo:lo + n_level]
            with _trace.span("paged/eval"):
                res = evaluate_splits_multi(hist, jnp.asarray(parent_pad),
                                            jnp.asarray(n_real), param,
                                            feature_mask=fmask,
                                            has_missing=self.has_missing)
                res = fetch_struct(res)  # ONE packed pull of decisions

            _mem.sample("paged/level")   # level boundary; free when off
            res_gain = np.asarray(res.gain)[:n_level]
            can_split = (active[lo:lo + n_level]
                         & (res_gain > max(param.gamma, _EPS))
                         & np.isfinite(res_gain))
            idx = lo + np.arange(n_level)
            split_feature[idx] = np.where(
                can_split, np.asarray(res.feature)[:n_level], -1)
            split_bin[idx] = np.where(
                can_split, np.asarray(res.bin)[:n_level], 0)
            default_left[idx] = can_split \
                & np.asarray(res.default_left)[:n_level]
            is_leaf[idx] = ~can_split
            gain[idx] = np.where(can_split, res_gain, 0.0)
            li, ri = 2 * idx + 1, 2 * idx + 2
            active[li] = can_split
            active[ri] = can_split
            ls = np.asarray(res.left_sum)[:n_level]      # [N, K, 2]
            rs = np.asarray(res.right_sum)[:n_level]
            node_sum[li] = np.where(can_split[:, None, None], ls, 0.0)
            node_sum[ri] = np.where(can_split[:, None, None], rs, 0.0)
            if cons is not None:
                r_feat = np.asarray(res.feature)[:n_level]
                fsel = ((np.arange(cons.shape[1])[None, :]
                         == np.maximum(r_feat, 0)[:, None])
                        & can_split[:, None])
                child_path = node_path[lo:lo + n_level] | fsel
                node_path[li] = child_path
                node_path[ri] = child_path

            if not can_split.any():
                prev = None
                break

            prev = _pack_level_splits(
                idx, can_split, n_static, n_level, split_feature, split_bin,
                default_left, max_nodes, lo)

        if prev is not None:  # route rows below the deepest splits
            with _trace.span("paged/advance"):
                positions = self._mk.final_advance(paged, positions, prev,
                                                   n_static)

        w = np.asarray(calc_weight(jnp.asarray(node_sum[..., 0]),
                                   jnp.asarray(node_sum[..., 1]),
                                   param)) * param.eta      # [max_nodes, K]
        leaf_value = np.where((active & is_leaf)[:, None], w,
                              0.0).astype(np.float32)
        base_weight = np.where(active[:, None], w, 0.0).astype(np.float32)
        delta = jnp.asarray(leaf_value)[positions]          # [n, K]

        g = GrownMulti(
            split_feature=split_feature, split_bin=split_bin,
            default_left=default_left, is_leaf=is_leaf, active=active,
            leaf_value=leaf_value, node_sum=node_sum, gain=gain,
            positions=positions, delta=delta, base_weight=base_weight)
        if param.max_leaves > 0:
            g = self._truncate_max_leaves(g)
        return g


class PagedMultiLossguideGrower(MultiLossguideGrower):
    """Vector-leaf loss-guided growth over a ``PagedBinnedMatrix``: the
    greedy pop loop of ``MultiLossguideGrower`` with the two per-split
    device kernels streaming over pages — the K-channel two-child
    histogram (``pair_hist(multi=True)``, one fused dispatch over cached
    pages + communicator allreduce) and the one-node advance. Reference:
    the LossGuide Driver schedules ``MultiTargetHistBuilder`` over
    ``GetBatches<GHistIndexMatrix>`` exactly like the scalar builder
    (``src/tree/updater_quantile_hist.cc:117-263`` + ``driver.h``)."""

    def __init__(self, param, max_nbins, cuts, hist_method="auto",
                 mesh=None, has_missing=True, constraint_sets=None,
                 split_mode="row") -> None:
        if split_mode != "row":
            raise NotImplementedError(
                "external-memory training supports data_split_mode=row "
                "only")
        super().__init__(param, max_nbins, cuts, hist_method=hist_method,
                         mesh=None, has_missing=has_missing,
                         constraint_sets=constraint_sets)
        if hist_method in TWO_LEVEL_METHODS:
            # same contract as the scalar PagedLossguideGrower (and the
            # core guard already rejects coarse/fused for vector leaves)
            raise NotImplementedError(
                "hist_method='coarse'/'fused' with "
                "grow_policy=lossguide runs on resident matrices only")
        self.mesh = mesh
        self._mk = None

    def _init_positions(self, n: int) -> jnp.ndarray:
        if self._mk is None:
            self._mk = _make_kernels(self)
        return self._mk.init_positions(n)

    def _functions(self):
        if self._fns is not None:
            return self._fns
        if self._mk is None:
            self._mk = _make_kernels(self)
        mk = self._mk
        from ..ops.split import evaluate_splits_multi

        def eval2(paged, gpair, positions, i0, i1, psums, fmask,
                  n_real_bins, bins_t=None):
            del bins_t  # pages window in-program inside the kernels
            hist = _host_allreduce(mk.pair_hist(paged, gpair, positions,
                                                i0, i1, multi=True))
            return evaluate_splits_multi(hist, psums, n_real_bins,
                                         self.param, feature_mask=fmask,
                                         has_missing=self.has_missing)

        def apply1(paged, positions, nid, feat, sbin, dleft, is_cat,
                   words, left_id, right_id, missing_bin):
            return mk.apply1(paged, positions, nid, feat, sbin, dleft,
                             is_cat, words, left_id, right_id, missing_bin)

        def root_sum(gpair):
            return _host_allreduce(jnp.sum(gpair, axis=0))

        gather = jax.jit(lambda lv, pos: lv[pos])
        self._fns = (eval2, apply1, root_sum, gather)
        return self._fns
