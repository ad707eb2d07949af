"""GBTree gradient booster — owns the tree list and the boosting step.

Reference: ``GBTree::DoBoost`` / ``BoostNewTrees`` (``src/gbm/gbtree.cc:226-350``):
one tree per output group per iteration (times ``num_parallel_tree`` for boosted
random forests, with the learning rate divided accordingly), committed with group
ids in ``tree_info`` and per-iteration offsets in ``iteration_indptr``.
"""

from __future__ import annotations

import bisect
import functools
from typing import List, Optional

import os

import jax
import jax.numpy as jnp
import numpy as np

from ..data.binned import BinnedMatrix
from ..obs import trace as _trace
from ..obs.metrics import count_eval_walk, count_tree_flush
from ..ops.histogram import (HEAP_WALK_FIELDS, heap_walk_delta,
                             heap_walk_takes)
from ..registry import BOOSTERS
from ..tree.grow import GrownTree, TreeGrower
from ..tree.param import TrainParam
from ..tree.tree import TreeModel
# One packed transfer per shared array dict — a 7-tree dart round used to
# flush 77 arrays, one blocking transfer each. Shared with the paged level
# loop.
from ..utils.fetch import fetch_packed as _fetch_packed


_GROWN_FIELDS = ("split_feature", "split_bin", "default_left", "is_leaf",
                 "active", "leaf_value", "node_sum", "gain", "is_cat_split",
                 "cat_words", "base_weight")


def sample_gradients(gp: jnp.ndarray, tkey: jax.Array,
                     param: TrainParam) -> jnp.ndarray:
    """Row subsampling on a [n, 2] gradient matrix — shared by the general
    boost loop and the fused round so their PRNG folding and numerics can
    never diverge. ``uniform``: bernoulli zeroing (reference
    ``SampleGradient``, src/tree/hist/sampler.h:48). ``gradient_based``:
    minimal-variance sampling — keep row i with probability
    p_i ∝ sqrt(g_i² + λh_i²) targeting subsample*n rows and rescale kept
    gradients by 1/p_i so histogram sums stay unbiased (reference
    ``GradientBasedSampling``, src/tree/gpu_hist/
    gradient_based_sampler.cuh:33-142)."""
    if param.subsample >= 1.0:
        return gp
    skey = jax.random.fold_in(tkey, 0x5AB)
    n = gp.shape[0]
    if param.sampling_method == "gradient_based":
        u = jnp.sqrt(gp[:, 0] ** 2 + param.reg_lambda * gp[:, 1] ** 2)
        p = jnp.minimum(1.0, param.subsample * n * u / (jnp.sum(u) + 1e-30))
        keep = jax.random.bernoulli(skey, p)
        return gp * jnp.where(keep, 1.0 / jnp.maximum(p, 1e-30),
                              0.0)[:, None]
    mask = jax.random.bernoulli(skey, param.subsample, (n,))
    return gp * mask[:, None].astype(gp.dtype)


def _grow_classes_scan(bins, gpair, n_real, key, monotone, constraint_sets,
                       cat, *, param, max_nbins, hist_method, has_missing):
    """Grow all K class trees of one round as a single traced program —
    ``lax.scan`` over the class axis. Every class tree shares the round's
    margin snapshot (the reference's per-round gradient), and the per-class
    PRNG stream matches the sequential loop exactly
    (tkey = fold_in(key, k), num_parallel_tree == 1 path). Returns
    (stacked per-node arrays with leading [K], margin delta [n, K]).
    Shared by the fused round body and the general/dart boost loop."""
    from ..tree.grow import _grow, _sample_features

    K = gpair.shape[1]

    def body(_, xs):
        k, gp_k = xs
        tkey = jax.random.fold_in(key, k)
        gp = sample_gradients(gp_k, tkey, param)
        tree_mask = _sample_features(jax.random.fold_in(tkey, 0xC0),
                                     n_real > 0, param.colsample_bytree)
        gkey = jax.random.fold_in(tkey, 0x5EED)
        grown = _grow(bins, gp, n_real, tree_mask, gkey, monotone,
                      constraint_sets, cat, param=param, max_nbins=max_nbins,
                      hist_method=hist_method, axis_name=None,
                      has_missing=has_missing)
        out = {f: getattr(grown, f) for f in _GROWN_FIELDS}
        out["__delta"] = grown.delta
        return None, out

    _, stacked = jax.lax.scan(
        body, None, (jnp.arange(K, dtype=jnp.uint32),
                     jnp.moveaxis(gpair, 1, 0)))
    delta = jnp.moveaxis(stacked.pop("__delta"), 0, 1)      # [n, K]
    return stacked, delta


_grow_classes_fn = jax.jit(
    _grow_classes_scan,
    static_argnames=("param", "max_nbins", "hist_method", "has_missing"))




def match_rows(m, n: int):
    """Fit a per-row margin/delta to ``n`` rows: mesh-padded train states
    carry more rows than the logical matrix (pad rows have weight 0), so
    deltas computed at one padding meet caches built at another — trim, or
    extend with zeros (pad rows' values are never read)."""
    if not hasattr(m, "shape") or m.shape[0] == n:
        return m
    if m.shape[0] > n:
        return m[:n]
    return jnp.concatenate(
        [m, jnp.zeros((n - m.shape[0],) + m.shape[1:], m.dtype)])


class _PendingTree:
    """A grown tree whose per-node arrays still live on device.

    ``index`` marks a tree inside a round-batched grow (core.update_batch):
    its ``arrays`` dict is SHARED with its batch siblings and every leaf
    carries a leading [K] axis — _flush fetches the dict once and slices
    host-side, so a K-round batch still costs one device round trip."""

    __slots__ = ("arrays", "grower", "index")

    def __init__(self, grown, grower, arrays=None, index=None) -> None:
        self.arrays = arrays if arrays is not None else {
            f: getattr(grown, f) for f in _GROWN_FIELDS
            if hasattr(grown, f)}
        self.grower = grower
        self.index = index


@functools.partial(
    jax.jit, static_argnames=("missing_bin", "max_depth", "interpret"))
def _heap_margin_delta(heaps, bins, *, missing_bin: int, max_depth: int,
                       interpret: bool = False) -> jnp.ndarray:
    """Margin increment ``[n, 1]`` of pending trees over binned rows, from
    their device heaps (``ops/histogram.py heap_walk_delta``), summed in
    tree order. One program a (trees, rows, F, depth) shape; no round
    program: its device time is the eval's."""
    delta = None
    for heap in heaps:
        d = heap_walk_delta(heap, bins, missing_bin, max_depth,
                            interpret=interpret)
        delta = d if delta is None else delta + d
    return delta[:, None]


class _HostGrown:
    """Host-side view of fetched grown-tree arrays (duck-types GrownTree for
    ``TreeGrower.to_tree_model``)."""

    __slots__ = ("_arrs",)

    def __init__(self, arrs) -> None:
        self._arrs = arrs

    def __getattr__(self, name):
        try:
            return self._arrs[name]
        except KeyError:
            raise AttributeError(name)


@BOOSTERS.register("gbtree")
class GBTree:
    name = "gbtree"

    def __init__(self, tree_param: TrainParam, n_groups: int,
                 num_parallel_tree: int = 1, hist_method: str = "auto",
                 mesh=None, monotone=None, constraint_sets=None,
                 tree_method: str = "hist",
                 multi_strategy: str = "one_output_per_tree",
                 split_mode: str = "row") -> None:
        self.tree_param = tree_param
        self.n_groups = n_groups
        self.num_parallel_tree = num_parallel_tree
        self.hist_method = hist_method
        self.mesh = mesh
        self.monotone = monotone
        self.constraint_sets = constraint_sets
        self.tree_method = tree_method
        self.multi_strategy = multi_strategy
        self.split_mode = split_mode
        self._trees: List = []  # TreeModel | _PendingTree (device-side)
        self.tree_info: List[int] = []
        self.iteration_indptr: List[int] = [0]
        self._grower: Optional[TreeGrower] = None
        self._exact_quant = None
        self._stat_version = 0  # bumped by process_type=update refreshes

    # -- deferred tree materialisation ---------------------------------------
    # Pulling a grown tree to the host costs one blocking transfer per
    # array, so plain-hist training keeps the
    # per-node arrays on device and converts them to TreeModels lazily, in
    # one packed pull a round's (or a round batch's) trees.
    @property
    def trees(self) -> List[TreeModel]:
        self._flush()
        return self._trees

    @trees.setter
    def trees(self, value) -> None:
        self._trees = list(value)

    def _flush(self) -> None:
        pending = [(i, t) for i, t in enumerate(self._trees)
                   if isinstance(t, _PendingTree)]
        if not pending:
            return
        # the round the oldest pending tree was boosted in
        first = bisect.bisect_right(self.iteration_indptr, pending[0][0]) - 1
        with _trace.span("round/flush", "train",
                         {"iteration": first, "trees": len(pending)}):
            # round-batched trees share one stacked-array dict — fetch each
            # distinct dict once, then slice host-side. One packed pull a
            # dict: the pack program then has one shape a dict layout,
            # however many rounds' trees have accumulated (an eval job
            # keeps them pending to its end). Packed into ONE program they
            # compile for 0.2 s a dict for a v5e: 19 s at 92 trees, 119 s
            # at 500 (PERF.md section 6, PR 32).
            unique: dict = {}
            for _, t in pending:
                unique.setdefault(id(t.arrays), t.arrays)
            fetched = {key: _fetch_packed([arrays])[0]
                       for key, arrays in unique.items()}
            for i, t in pending:
                arrs = fetched[id(t.arrays)]
                if t.index is not None:
                    arrs = {k: v[t.index] for k, v in arrs.items()}
                self._trees[i] = t.grower.to_tree_model(_HostGrown(arrs))
        count_tree_flush()

    def _vertical_federated(self) -> bool:
        from ..parallel import collective

        return (self.split_mode == "col" and self.mesh is None
                and collective.is_distributed())

    # -- training -------------------------------------------------------------
    def _grower_for(self, binned: BinnedMatrix) -> TreeGrower:
        if self._grower is None:
            param = self.tree_param
            if self.num_parallel_tree > 1:
                # reference BoostNewTrees: lr /= num_parallel_tree
                param = param.clone()
                param.eta = param.eta / self.num_parallel_tree
            paged = getattr(binned, "is_paged", False)
            kw = {"split_mode": self.split_mode}
            if param.grow_policy == "lossguide":
                if paged:
                    from ..tree.paged import PagedLossguideGrower

                    cls = PagedLossguideGrower
                elif self.split_mode == "col" and self.mesh is None:
                    # vertical federated lossguide: winner allgather +
                    # decision-bit allreduce around the same greedy loop
                    from ..tree.vertical import VerticalLossguideGrower

                    cls = VerticalLossguideGrower
                else:
                    from ..tree.lossguide import LossguideGrower

                    cls = LossguideGrower
            elif paged:
                from ..tree.paged import PagedGrower

                cls = PagedGrower
            elif self.split_mode == "col" and self.mesh is None:
                # column split without a device mesh: parties are separate
                # communicator ranks (vertical federated) — host-level
                # level loop with best-split/decision-bit exchanges
                from ..tree.vertical import VerticalFederatedGrower

                cls = VerticalFederatedGrower
            else:
                cls = TreeGrower
            self._grower = cls(param, binned.max_nbins, binned.cuts,
                               hist_method=self.hist_method,
                               mesh=self.mesh, monotone=self.monotone,
                               constraint_sets=self.constraint_sets,
                               has_missing=binned.has_missing, **kw)
        return self._grower

    def do_boost(self, state: dict, gpair: jnp.ndarray,
                 iteration: int, key: jax.Array, obj=None,
                 margin=None) -> jnp.ndarray:
        """gpair: [n, K, 2] -> margin delta [n, K] for the training data.

        ``obj``/``margin`` enable the adaptive-leaf hook
        (``GBTree::UpdateTreeLeaf``, reference ``src/gbm/gbtree.cc:201``):
        leaf values are replaced by per-leaf residual quantiles using the
        grower's row positions."""
        binned = state["binned"]
        info = state["info"]
        n, K = gpair.shape[0], gpair.shape[1]
        adaptive = obj is not None and hasattr(obj, "update_tree_leaf")
        if self.multi_strategy == "multi_output_tree" and K > 1:
            if adaptive:
                raise NotImplementedError(
                    "multi_output_tree does not support adaptive-leaf "
                    "objectives")
            if self.tree_method in ("exact", "approx"):
                raise NotImplementedError(
                    "multi_output_tree requires tree_method=hist")
            return self._do_boost_multi(state, gpair, key)
        eta = self.tree_param.eta / max(self.num_parallel_tree, 1)
        exact = self.tree_method == "exact"
        if exact:
            if self._exact_quant is None:
                from ..tree.exact import ExactQuantization

                if getattr(state["dm"].X, "is_paged", False) \
                        or np.ndim(state["dm"].X) != 2:
                    raise NotImplementedError(
                        "tree_method=exact rank-encodes the raw matrix "
                        "and does not support external-memory (paged) "
                        "matrices; use tree_method=hist")
                self._exact_quant = ExactQuantization(
                    np.asarray(state["dm"].X))
        elif self.tree_method != "approx":
            grower = self._grower_for(binned)
            n_real = binned.n_real_bins()
            if (K > 1 and not adaptive and self.num_parallel_tree == 1
                    and type(grower) is TreeGrower and grower.mesh is None
                    and grower.param.max_leaves <= 0  # host-side truncation
                    and os.environ.get("XTPU_SCAN_CLASSES", "1") != "0"):
                # all K class grows as ONE dispatch (lax.scan over classes)
                # — same PRNG stream and numerics as the sequential loop
                # below; this is what makes dart multiclass rounds one
                # dispatch even though dart can't use the fused margin path
                stacked, delta = _grow_classes_fn(
                    binned.bins, gpair, n_real, key, grower.monotone,
                    grower.constraint_sets, grower.cat,
                    param=grower.param, max_nbins=grower.max_nbins,
                    hist_method=grower.hist_method,
                    has_missing=grower.has_missing)
                for k in range(K):
                    self._trees.append(
                        _PendingTree(None, grower, arrays=stacked, index=k))
                    self.tree_info.append(k)
                self.iteration_indptr.append(len(self._trees))
                return delta
        deltas = []
        for k in range(K):
            if self.tree_method == "approx":
                # GlobalApproxUpdater: re-sketch cuts every iteration with
                # hessian weights (reference src/tree/updater_approx.cc:55)
                dm = state["dm"]
                # sketch weight is the hessian AS-IS: the objective already
                # folded sample weights into gpair (objective/base.py:61),
                # exactly like the reference's GetHess() extraction
                # (updater_approx.cc:290-295)
                if getattr(dm, "presharded", False):
                    # sharded ingestion: local hessians feed the
                    # distributed sketch merge; the rebinned matrix comes
                    # back mesh-sharded (updater_approx.cc:245 sketch sync)
                    hess = np.asarray(
                        dm.local_rows(gpair[:, k, 1]), np.float64)
                    binned = dm.resketch_binned(self.tree_param.max_bin,
                                                hess)
                    cuts = binned.cuts
                else:
                    from ..data.binned import BinnedMatrix
                    from ..data.quantile import sketch_matrix

                    w = np.asarray(gpair[:, k, 1], np.float64)
                    src = getattr(dm, "_binned", None)
                    if dm.X is None and getattr(src, "is_paged", False):
                        # external memory: re-sketch from the page
                        # iterator (hessian-weighted, cross-host merge
                        # under a communicator) and hand the re-binned
                        # pages to the paged hist driver — the reference
                        # GlobalApproxUpdater trains from GetBatches the
                        # same way (src/tree/updater_approx.cc)
                        if self.mesh is not None:
                            raise NotImplementedError(
                                "tree_method=approx over external-memory "
                                "pages supports row split without a "
                                "device mesh (single- or multi-host)")
                        binned = src.resketch(self.tree_param.max_bin, w,
                                              info.feature_types)
                        cuts = binned.cuts
                    elif dm.X is None and src is not None:
                        # iterator-built resident matrix: raw floats were
                        # never retained; sketch the representative cut
                        # values the quantized matrix reconstructs — the
                        # same operands the paged path sketches page-wise
                        vals = np.asarray(src.to_values())
                        cuts = sketch_matrix(vals, self.tree_param.max_bin,
                                             w, info.feature_types)
                        binned = BinnedMatrix.from_dense(vals, cuts)
                    else:
                        if np.ndim(dm.X) != 2:
                            raise NotImplementedError(
                                "tree_method=approx needs a dense raw "
                                "matrix or an iterator-built "
                                "QuantileDMatrix")
                        cuts = sketch_matrix(np.asarray(dm.X),
                                             self.tree_param.max_bin, w,
                                             info.feature_types)
                        binned = BinnedMatrix.from_dense(np.asarray(dm.X),
                                                         cuts)
                if self.split_mode == "col" and self.mesh is not None:
                    # column-split mesh: the re-sketched matrix lands
                    # feature-sharded exactly like the hist training state
                    # (rows replicate, so the host-side sketch is already
                    # identical everywhere; vertical federated needs no
                    # sync either — each rank sketches only the columns it
                    # owns, reference updater_approx.cc under kCol)
                    from ..context import DATA_AXIS
                    from ..data.binned import pad_features_for_mesh

                    binned = pad_features_for_mesh(binned, self.mesh,
                                                   DATA_AXIS)
                # reuse the grower (and its jitted kernels) across re-sketches
                # when the compiled shapes are unchanged; categorical split
                # sets depend on the cuts, so those rebuild
                g = self._grower
                # paged growers cannot be reused across re-sketches: their
                # _LevelEvaluator bakes the per-feature real-bin counts
                # into its jitted closures as trace constants, and a new
                # sketch changes them
                if (g is not None and g.max_nbins == binned.max_nbins
                        and not getattr(binned, "is_paged", False)
                        and g.cat is None and not cuts.is_cat().any()):
                    # pending trees still reference this grower's cuts for
                    # their raw thresholds — materialise them first
                    self._flush()
                    g.cuts = cuts
                else:
                    self._grower = None
                grower = self._grower_for(binned)
                n_real = binned.n_real_bins()
            delta_k = jnp.zeros((n,), jnp.float32)
            for p in range(self.num_parallel_tree):
                tkey = jax.random.fold_in(key, k * self.num_parallel_tree + p)
                gp = gpair[:, k, :]
                gp = sample_gradients(gp, tkey, self.tree_param)
                if exact:
                    from ..tree.exact import ExactGrower

                    egrower = ExactGrower(self.tree_param, self._exact_quant)
                    grown = egrower.grow(gp, tkey)
                    tree = egrower.to_tree_model(grown)
                elif adaptive:
                    grown = grower.grow(binned.bins, gp, n_real, tkey)
                    tree = grower.to_tree_model(grown)
                else:
                    grown = grower.grow(binned.bins, gp, n_real, tkey)
                    if (isinstance(grown, GrownTree)
                            and isinstance(grown.split_feature, jnp.ndarray)):
                        tree = _PendingTree(grown, grower)  # stays on device
                    else:  # host arrays (lossguide / max_leaves truncation)
                        tree = grower.to_tree_model(grown)
                if adaptive:
                    # grower positions are heap ids; translate to the
                    # committed tree's compact ids first
                    pos = tree.heap_map[np.asarray(grown.positions)]
                    alphas = obj.alphas() if hasattr(obj, "alphas") else [0.5]

                    def _adapt():
                        obj.update_tree_leaf(
                            tree, pos, np.asarray(margin[:, k]), info,
                            eta, alpha=alphas[min(k, len(alphas) - 1)])
                        return np.asarray(tree.leaf_value)

                    if self._vertical_federated():
                        # adaptive leaves are label quantiles: positions and
                        # margins replicate, labels live on the label rank
                        # only (reference UpdateTreeLeaf under
                        # ApplyWithLabels, src/objective/adaptive.cc)
                        from ..parallel.collective import apply_with_labels

                        tree.leaf_value = np.asarray(
                            apply_with_labels(_adapt), np.float32)
                    else:
                        _adapt()
                    delta_k = delta_k + jnp.asarray(
                        tree.leaf_value[pos], dtype=jnp.float32)
                else:
                    delta_k = delta_k + grown.delta
                self._trees.append(tree)
                self.tree_info.append(k)
            deltas.append(delta_k)
        self.iteration_indptr.append(len(self._trees))
        return jnp.stack(deltas, axis=1)

    def _do_boost_multi(self, state: dict, gpair: jnp.ndarray,
                        key: jax.Array) -> jnp.ndarray:
        """One vector-leaf tree covering all K outputs per round (reference
        ``MultiTargetHistBuilder``, ``src/tree/updater_quantile_hist.cc:117``).
        """
        from ..tree.multi import MultiTargetGrower

        binned = state["binned"]
        paged = getattr(binned, "is_paged", False)
        n = gpair.shape[0]
        if self._grower is None:
            param = self.tree_param
            if self.num_parallel_tree > 1:
                param = param.clone()
                param.eta = param.eta / self.num_parallel_tree
            if paged:
                if param.grow_policy == "lossguide":
                    from ..tree.paged import PagedMultiLossguideGrower

                    cls = PagedMultiLossguideGrower
                else:
                    from ..tree.paged import PagedMultiTargetGrower

                    cls = PagedMultiTargetGrower
            elif param.grow_policy == "lossguide":
                from ..tree.multi import MultiLossguideGrower

                cls = MultiLossguideGrower
            else:
                cls = MultiTargetGrower
            self._grower = cls(
                param, binned.max_nbins, binned.cuts,
                hist_method=self.hist_method, mesh=self.mesh,
                has_missing=binned.has_missing,
                constraint_sets=self.constraint_sets,
                split_mode=self.split_mode)
        grower = self._grower
        n_real = binned.n_real_bins()
        delta = jnp.zeros(gpair.shape[:2], jnp.float32)
        for p in range(self.num_parallel_tree):
            tkey = jax.random.fold_in(key, p)
            gp = gpair
            if self.tree_param.subsample < 1.0:
                mask = jax.random.bernoulli(
                    jax.random.fold_in(tkey, 0x5AB),
                    self.tree_param.subsample, (n,))
                gp = gp * mask[:, None, None].astype(gp.dtype)
            grown = grower.grow(binned.bins, gp, n_real, tkey)
            delta = delta + grown.delta
            if getattr(grown, "split_feature", None) is not None \
                    and isinstance(grown.split_feature, jnp.ndarray):
                self._trees.append(_PendingTree(grown, grower))
            else:  # host arrays (paged / lossguide) — materialise now
                self._trees.append(grower.to_tree_model(grown))
            self.tree_info.append(0)
        self.iteration_indptr.append(len(self._trees))
        return delta

    # -- prediction interface (used by core.Booster) --------------------------
    supports_margin_cache = True

    def version(self) -> int:
        """Monotone counter identifying the current model contents (a tree
        count — the margin cache slices trees by it, so in-place updates
        reset caches through the Booster instead of bumping this)."""
        return len(self._trees)

    def training_margin(self, state: dict) -> jnp.ndarray:
        """Margin to compute gradients against (DART overrides: drop trees)."""
        return state["margin"]

    def compute_margin(self, state: dict) -> jnp.ndarray:
        """Full margin recompute for a cache state (non-incremental path)."""
        if state.get("binned") is not None:
            delta = match_rows(
                self.margin_delta_binned(state["binned"], 0,
                                         len(self.trees)),
                state["base"].shape[0])
            return state["base"] + delta
        m, _, _ = self.predict_margin(state["dm"].X,
                                      np.zeros(self.n_groups, np.float32))
        return state["base"] + jnp.asarray(m)

    def margin_delta_raw(self, X, tree_lo: int, tree_hi: int):
        pred = self._predictor(tree_lo, tree_hi)
        if pred is None:
            return 0.0
        delta, _ = pred.margin(X, np.zeros(self.n_groups, np.float32))
        return delta

    def tree_weights(self) -> Optional[np.ndarray]:
        return None

    def _predictor(self, lo: int, hi: int):
        from ..tree.multi import MultiForestPredictor, MultiTargetTreeModel
        from ..tree.tree import stack_forest
        from .predict import ForestPredictor

        trees = self.trees[lo:hi]
        if trees and isinstance(trees[0], MultiTargetTreeModel):
            return MultiForestPredictor(trees, self.n_groups)
        forest = stack_forest(trees)
        if forest is None:
            return None
        w = self.tree_weights()
        return ForestPredictor(forest, np.asarray(self.tree_info[lo:hi]),
                               self.n_groups,
                               tree_weights=None if w is None else w[lo:hi])

    def _tree_range(self, iteration_range=None):
        """iteration_range -> (tree_lo, tree_hi) indices."""
        if iteration_range is not None and iteration_range != (0, 0):
            b, e = iteration_range
            e = min(e if e else self.num_boosted_rounds(),
                    self.num_boosted_rounds())
            return self.iteration_indptr[b], self.iteration_indptr[e]
        return 0, len(self.trees)

    def forest_slice(self, iteration_range=None):
        """-> (trees, tree_info, tree_weights) for contribution APIs."""
        lo, hi = self._tree_range(iteration_range)
        w = self.tree_weights()
        return (self.trees[lo:hi], np.asarray(self.tree_info[lo:hi]),
                None if w is None else w[lo:hi])

    def predict_margin(self, X, base, iteration_range=None):
        """-> (margin [n, K], leaf heap positions [n, T] or None, trees)."""
        lo, hi = self._tree_range(iteration_range)
        pred = self._predictor(lo, hi)
        n = X.shape[0]
        if pred is None:
            return (np.broadcast_to(np.asarray(base, np.float32)[None, :],
                                    (n, self.n_groups)).copy(), None,
                    self.trees[lo:hi])
        m, pos = pred.margin(X, np.asarray(base, np.float32))
        return np.asarray(m), pos, self.trees[lo:hi]

    def _margin_binned_paged(self, pred, binned, base):
        """Streamed prediction over a PagedBinnedMatrix's pages."""
        if self.mesh is not None:
            # mesh pages interleave shards: page row d*p_loc+j is shard d's
            # local row s_loc+j, so restore original (shard-major) row
            # order by stacking pages along the local axis, then trim the
            # mesh-layout pad rows — callers against a PADDED train cache
            # re-extend through match_rows
            from ..context import DATA_AXIS

            world = self.mesh.shape.get(DATA_AXIS, 1)
            outs = []
            for _, page in binned.pages_sharded(self.mesh, DATA_AXIS):
                m, _ = pred.margin_binned(binned.decode_page(page),
                                          binned.missing_bin, base)
                outs.append(m.reshape(world, -1, m.shape[-1]))
            full = jnp.concatenate(outs, axis=1).reshape(
                -1, outs[0].shape[-1])
            return full[:binned.n_rows]
        outs = []
        for _, _, page in binned.pages():
            m, _ = pred.margin_binned(binned.decode_page(page),
                                      binned.missing_bin, base)
            outs.append(m)
        return jnp.concatenate(outs)

    # tests set it: the CPU then runs the heap walk's kernel through the
    # Pallas interpreter
    _heap_walk_interpret = False

    def _heap_walk_heaps(self, binned, tree_lo: int, tree_hi: int):
        """``(device heaps, max_depth)`` of trees [tree_lo, tree_hi) where
        ``heap_walk_delta`` states their margin increment: every one still
        pending and grown by one depthwise ``TreeGrower`` (a heap the host
        will state node for node: no ``max_leaves`` truncation, no
        categorical split, one output), over a resident matrix on one
        device, at a shape the walk takes. None otherwise."""
        trees = self._trees[tree_lo:tree_hi]
        bins = getattr(binned, "bins", None)
        g = getattr(trees[0], "grower", None) if trees else None
        if (type(g) is not TreeGrower or g.mesh is not None
                or g.cat is not None or g.param.max_leaves > 0
                or not all(isinstance(t, _PendingTree) and t.grower is g
                           for t in trees)
                or self.n_groups != 1 or self.mesh is not None
                or getattr(binned, "is_paged", False)
                or not isinstance(bins, jax.Array)
                or len(bins.sharding.device_set) != 1):
            return None
        # the depth the heaps were grown to: ``set_param`` edits the
        # grower's ``param`` in place, the arrays keep their size
        nodes = {t.arrays["leaf_value"].shape[-1] for t in trees}
        max_depth = (min(nodes) + 1).bit_length() - 2
        if len(nodes) != 1 or not heap_walk_takes(
                bins.shape[1], binned.missing_bin, max_depth,
                self._heap_walk_interpret):
            return None
        # a batch-grown tree's arrays carry the batch axis: sliced on the
        # device
        return tuple(
            {f: t.arrays[f] if t.index is None else t.arrays[f][t.index]
             for f in HEAP_WALK_FIELDS} for t in trees), max_depth

    def margin_delta_binned(self, binned, tree_lo: int, tree_hi: int):
        """Margin contribution of trees [tree_lo, tree_hi) on quantized data
        (the prediction-cache increment). Trees still on the device are
        walked there from their heaps and stay pending; anything else is
        flushed and walked by the ``ForestPredictor``
        (``xtpu_eval_walk_total{kind}`` says which)."""
        walk = self._heap_walk_heaps(binned, tree_lo, tree_hi)
        if walk is not None:
            count_eval_walk("heap")
            return _heap_margin_delta(
                walk[0], binned.bins, missing_bin=binned.missing_bin,
                max_depth=walk[1], interpret=self._heap_walk_interpret)
        pred = self._predictor(tree_lo, tree_hi)
        if pred is None:
            return 0.0
        count_eval_walk("forest")
        zero = np.zeros(self.n_groups, np.float32)
        if getattr(binned, "is_paged", False):
            return self._margin_binned_paged(pred, binned, zero)
        delta, _ = pred.margin_binned(binned.bins, binned.missing_bin, zero)
        return delta

    def full_margin_binned(self, binned, base):
        pred = self._predictor(0, len(self.trees))
        n = binned.n_rows
        if pred is None:
            return jnp.broadcast_to(
                jnp.asarray(base, jnp.float32)[None, :], (n, self.n_groups))
        base = np.asarray(base, np.float32)
        if getattr(binned, "is_paged", False):
            return self._margin_binned_paged(pred, binned, base)
        m, _ = pred.margin_binned(binned.bins, binned.missing_bin, base)
        return m

    # -- model container ------------------------------------------------------
    def num_boosted_rounds(self) -> int:
        return len(self.iteration_indptr) - 1

    def tree_slice(self, begin: int, end: Optional[int] = None):
        """Trees of iterations [begin, end) (reference model slicing)."""
        if end is None or end > self.num_boosted_rounds():
            end = self.num_boosted_rounds()
        lo, hi = self.iteration_indptr[begin], self.iteration_indptr[end]
        return self.trees[lo:hi], self.tree_info[lo:hi]

    def to_json(self) -> dict:
        return {
            "name": self.name,
            "num_parallel_tree": self.num_parallel_tree,
            "multi_strategy": self.multi_strategy,
            "trees": [t.to_json() for t in self.trees],
            "tree_info": list(self.tree_info),
            "iteration_indptr": list(self.iteration_indptr),
        }

    def from_json(self, obj: dict) -> None:
        from ..tree.multi import MultiTargetTreeModel

        self.num_parallel_tree = int(obj.get("num_parallel_tree", 1))
        self.multi_strategy = obj.get("multi_strategy",
                                      "one_output_per_tree")
        self.trees = [MultiTargetTreeModel.from_json(t) if "n_targets" in t
                      else TreeModel.from_json(t) for t in obj["trees"]]
        self.tree_info = [int(x) for x in obj["tree_info"]]
        self.iteration_indptr = [int(x) for x in obj["iteration_indptr"]]
