"""DMatrix / QuantileDMatrix — the user-facing data containers.

Analogue of the reference's ``DMatrix`` + ``MetaInfo``
(``include/xgboost/data.h:48-209,508``) and ``IterativeDMatrix``
(``src/data/iterative_dmatrix.cc``): metadata (labels, weights, base_margin,
query groups, feature names/types) rides next to the feature payload; the
quantized ``BinnedMatrix`` is built lazily at first training touch (the reference
builds ``GHistIndexMatrix`` on first ``GetBatches`` call) or eagerly in two
passes for ``QuantileDMatrix`` (pass 1 sketch, pass 2 fill — with ``ref=`` cut
sharing as in ``GetCutsFromRef``, ``src/data/iterative_dmatrix.cc:54-93``).
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass, field
from typing import Any, Iterator, List, Optional

import numpy as np

from ..obs import trace as obs_trace
from .adapters import to_dense
from .binned import BinnedMatrix
from .quantile import (FeatureSummary, HistogramCuts, cover_maxima,
                       cuts_from_summaries, sketch_matrix)


def _ingest(fn):
    """The ``ingest`` container phase around a constructor or a placement:
    what it holds beyond ``ingest/sketch``, ``ingest/bin``, ``ingest/upload``
    and ``ingest/next`` is booked to it, and reads as unattributed set-up
    (docs/observability.md, "Start-up report")."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with obs_trace.phase("ingest", "ingest"):
            return fn(*args, **kwargs)
    return wrapped


@dataclass
class MetaInfo:
    """Labels & friends (reference ``MetaInfo``, ``include/xgboost/data.h:48``)."""

    labels: Optional[np.ndarray] = None        # [n] or [n, n_targets]
    weights: Optional[np.ndarray] = None       # [n] row weights
    base_margin: Optional[np.ndarray] = None   # [n] or [n, n_groups]
    group_ptr: Optional[np.ndarray] = None     # [n_query+1] ranking group offsets
    label_lower_bound: Optional[np.ndarray] = None  # survival AFT
    label_upper_bound: Optional[np.ndarray] = None
    feature_names: Optional[List[str]] = None
    feature_types: Optional[List[str]] = None
    # 'row' (data-parallel) or 'col' (feature-parallel), reference DataSplitMode
    data_split_mode: str = "row"

    def labels_device(self):
        """Device f32 copy of ``labels``, uploaded ONCE per array identity.
        Objectives read labels every boosting round and the stump /
        fused-round setup reads them per train() — without this cache each
        read is an O(n) host->device transfer (44 MB per read at
        HIGGS-11M). ``set_label`` style mutations replace the array
        object, which invalidates by identity."""
        if self.labels is None:
            return None
        import jax.numpy as jnp

        cur = getattr(self, "_labels_dev", None)
        if cur is None or cur[0] is not self.labels:
            self._labels_dev = (self.labels,
                                jnp.asarray(self.labels, jnp.float32))
        return self._labels_dev[1]

    def weights_device(self):
        """Device f32 copy of ``weights`` (see ``labels_device``)."""
        if self.weights is None:
            return None
        import jax.numpy as jnp

        cur = getattr(self, "_weights_dev", None)
        if cur is None or cur[0] is not self.weights:
            self._weights_dev = (self.weights,
                                 jnp.asarray(self.weights, jnp.float32))
        return self._weights_dev[1]

    def __getstate__(self):
        # device caches are rebuilt on demand; never pickle them
        d = dict(self.__dict__)
        d.pop("_labels_dev", None)
        d.pop("_weights_dev", None)
        return d

    def validate(self, n_rows: int) -> None:
        for name in ("labels", "weights", "base_margin",
                     "label_lower_bound", "label_upper_bound"):
            v = getattr(self, name)
            if v is not None and v.shape[0] != n_rows:
                raise ValueError(
                    f"{name} has {v.shape[0]} entries, expected {n_rows}")
        if self.group_ptr is not None and self.group_ptr[-1] != n_rows:
            raise ValueError("group_ptr must cover all rows")

    def set_group(self, group_sizes: np.ndarray) -> None:
        self.group_ptr = np.concatenate(
            [[0], np.cumsum(np.asarray(group_sizes, dtype=np.int64))]).astype(np.int64)


class DMatrix:
    """In-memory data matrix (reference ``SimpleDMatrix``)."""

    _data_split_mode = "row"  # subclasses with their own __init__ inherit

    @_ingest
    def __init__(self, data: Any, label: Any = None, *, weight: Any = None,
                 base_margin: Any = None, missing: float = np.nan,
                 feature_names: Optional[List[str]] = None,
                 feature_types: Optional[List[str]] = None,
                 group: Any = None, qid: Any = None,
                 label_lower_bound: Any = None, label_upper_bound: Any = None,
                 enable_categorical: bool = False,
                 max_bin: int = 256,
                 data_split_mode: str = "row") -> None:
        self._data_split_mode = data_split_mode
        if isinstance(data, DataIter):
            # external-memory path (reference DMatrix-from-DataIter ->
            # SparsePageDMatrix, src/data/sparse_page_dmatrix.cc): stream
            # two passes, keep only the quantized pages (memmap-backed
            # when the iterator carries cache_prefix)
            self._init_from_iter(data, max_bin, None, missing,
                                 cache_prefix=data.cache_prefix)
            return
        if isinstance(data, (str, os.PathLike)):
            # URI load (reference DMatrix::Load, src/data/data.cc:853):
            # libsvm/csv text through the native parser + aux sidecar files
            from .fileio import load_uri

            loaded = load_uri(str(data))
            data = loaded["X"]
            if label is None:
                label = loaded.get("label")
            if weight is None:
                weight = loaded.get("weight")
            if base_margin is None:
                base_margin = loaded.get("base_margin")
            if group is None and qid is None:
                group = loaded.get("group")
                if group is None:
                    qid = loaded.get("qid")
            if label_lower_bound is None:
                label_lower_bound = loaded.get("label_lower_bound")
            if label_upper_bound is None:
                label_upper_bound = loaded.get("label_upper_bound")
            if feature_names is None:
                feature_names = loaded.get("feature_names")
            if feature_types is None:
                feature_types = loaded.get("feature_types")
                if feature_types is not None and "c" in feature_types:
                    enable_categorical = True
        X, names, types = to_dense(data, missing, feature_names, feature_types)
        self.X = X
        self.info = MetaInfo(feature_names=names, feature_types=types,
                             data_split_mode=self._data_split_mode)
        if not enable_categorical and types is not None and "c" in types:
            raise ValueError(
                "categorical features present; pass enable_categorical=True")
        if label is not None:
            # own the storage (reference MetaInfo copies too): aliasing the
            # user's array would let in-place mutations bypass the
            # identity-keyed device cache (labels_device)
            self.info.labels = np.array(label, dtype=np.float32)
        if weight is not None:
            self.info.weights = np.array(weight, dtype=np.float32)
        if base_margin is not None:
            self.info.base_margin = np.asarray(base_margin, dtype=np.float32)
        if label_lower_bound is not None:
            self.info.label_lower_bound = np.asarray(label_lower_bound, np.float32)
        if label_upper_bound is not None:
            self.info.label_upper_bound = np.asarray(label_upper_bound, np.float32)
        if group is not None:
            self.info.set_group(np.asarray(group))
        elif qid is not None:
            qid = np.asarray(qid)
            if np.any(qid[1:] < qid[:-1]):
                raise ValueError("qid must be sorted")
            _, counts = np.unique(qid, return_counts=True)
            self.info.set_group(counts)
        self.info.validate(self.num_row())
        self._binned: Optional[BinnedMatrix] = None
        self._binned_max_bin: Optional[int] = None

    # --- where an iterator's bins live -----------------------------------------
    # ``_init_from_iter`` leaves its bin matrix on the HOST (``_host_bins``):
    # where it goes is the first asker's to say. A booster with a row-split
    # mesh takes it shard by shard (``place_binned``); anyone else who reads
    # ``_binned`` gets it whole on the default device, as it always was.
    @property
    def _binned(self):
        pending = self.__dict__.get("_host_bins")
        if pending is not None:
            self.__dict__["_host_bins"] = None
            local, cuts, max_nbins, has_missing = pending
            with obs_trace.phase("ingest/upload", "ingest",
                                 {"rows": int(local.shape[0]), "shards": 1}):
                self.__dict__["_binned_v"] = BinnedMatrix.from_local_bins(
                    local, cuts, max_nbins=max_nbins,
                    has_missing=has_missing)
        return self.__dict__.get("_binned_v")

    @_binned.setter
    def _binned(self, value) -> None:
        self.__dict__["_binned_v"] = value

    @_ingest
    def place_binned(self, sharding) -> Optional[BinnedMatrix]:
        """An iterator's bin matrix that nobody has asked for yet, placed
        under ``sharding`` (rows over its first axis, padded to a whole
        number a shard with an in-range bin: pad rows carry weight 0). Each
        device is handed its block of the host matrix; no device holds the
        whole and nothing comes back to the host. None when there is
        nothing pending (built in memory, paged, or already placed). With
        no pad rows the placed matrix becomes this DMatrix's own and the
        host copy is dropped."""
        pending = self.__dict__.get("_host_bins")
        if pending is None:
            return None
        from .binned import put_row_shards

        local, cuts, max_nbins, has_missing = pending
        world = sharding.mesh.shape[sharding.spec[0]]
        n = local.shape[0]
        n_pad = -(-n // world) * world
        missing_bin = max_nbins - 1 if has_missing else max_nbins
        with obs_trace.phase("ingest/upload", "ingest",
                             {"rows": int(n), "shards": world}):
            placed = BinnedMatrix(
                bins=put_row_shards(local, sharding, n_pad,
                                    min(missing_bin, max_nbins - 1)),
                cuts=cuts, max_nbins=max_nbins, has_missing=has_missing)
        if n_pad == n:
            self.__dict__["_host_bins"] = None
            self.__dict__["_binned_v"] = placed
        return placed

    # --- shape --------------------------------------------------------------
    def num_row(self) -> int:
        return self.X.shape[0] if self.X is not None else self._n_rows

    def num_col(self) -> int:
        return self.X.shape[1] if self.X is not None else self._n_cols

    def num_nonmissing(self) -> int:
        """Count of present (non-NaN) entries (reference core.py:1222)."""
        if self.X is not None:
            return int(np.count_nonzero(~np.isnan(self.X)))
        b = self._binned
        if not b.has_missing:
            return b.n_rows * b.n_features
        bins = b.bins_host if getattr(b, "is_paged", False) else \
            np.asarray(b.bins)
        return int(np.count_nonzero(bins != b.missing_bin))

    @property
    def shape(self):
        return (self.num_row(), self.num_col())

    # --- feature info (reference core.py:1266-1361) --------------------------
    @property
    def feature_names(self) -> Optional[List[str]]:
        return self.info.feature_names

    @feature_names.setter
    def feature_names(self, names: Optional[List[str]]) -> None:
        if names is not None:
            names = [str(n) for n in names]
            if len(names) != self.num_col():
                raise ValueError(
                    f"feature_names has {len(names)} entries, "
                    f"expected {self.num_col()}")
            if len(set(names)) != len(names):
                raise ValueError("feature_names must be unique")
        self.info.feature_names = names

    @property
    def feature_types(self) -> Optional[List[str]]:
        return self.info.feature_types

    @feature_types.setter
    def feature_types(self, types: Optional[List[str]]) -> None:
        if types is not None:
            if isinstance(types, str):
                types = [types] * self.num_col()
            types = list(types)
            if len(types) != self.num_col():
                raise ValueError(
                    f"feature_types has {len(types)} entries, "
                    f"expected {self.num_col()}")
        self.info.feature_types = types

    # --- meta setters (reference set_info style) ------------------------------
    def set_info(self, **kwargs: Any) -> None:
        for k, v in kwargs.items():
            if k == "group":
                self.info.set_group(np.asarray(v))
            elif k in ("label", "weight", "base_margin"):
                attr = {"label": "labels", "weight": "weights",
                        "base_margin": "base_margin"}[k]
                # np.array (copy): own the storage so the identity-keyed
                # device caches invalidate on every set_* call
                setattr(self.info, attr, np.array(v, dtype=np.float32))
            else:
                setattr(self.info, k, v)
        self.info.validate(self.num_row())

    def get_label(self) -> Optional[np.ndarray]:
        return self.info.labels

    _FLOAT_FIELDS = {"label": "labels", "weight": "weights",
                     "base_margin": "base_margin",
                     "label_lower_bound": "label_lower_bound",
                     "label_upper_bound": "label_upper_bound"}

    def get_float_info(self, field: str) -> np.ndarray:
        """Reference ``XGDMatrixGetFloatInfo`` (core.py:950): unset fields
        come back as empty arrays."""
        if field not in self._FLOAT_FIELDS:
            raise ValueError(f"unknown float field: {field}")
        v = getattr(self.info, self._FLOAT_FIELDS[field])
        return (np.empty(0, np.float32) if v is None
                else np.asarray(v, np.float32))

    def get_uint_info(self, field: str) -> np.ndarray:
        if field != "group_ptr":
            raise ValueError(f"unknown uint field: {field}")
        v = self.info.group_ptr
        return np.empty(0, np.uint32) if v is None else np.asarray(v, np.uint32)

    def set_float_info(self, field: str, data: Any) -> None:
        if field not in self._FLOAT_FIELDS:
            raise ValueError(f"unknown float field: {field}")
        self.set_info(**{field: data})

    def set_uint_info(self, field: str, data: Any) -> None:
        if field != "group_ptr":
            raise ValueError(f"unknown uint field: {field}")
        self.info.group_ptr = np.asarray(data, np.int64)
        self.info.validate(self.num_row())

    def set_label(self, label: Any) -> None:
        self.set_info(label=label)

    def set_weight(self, weight: Any) -> None:
        self.set_info(weight=weight)

    def set_base_margin(self, margin: Any) -> None:
        self.set_info(base_margin=margin)

    def set_group(self, group: Any) -> None:
        self.set_info(group=group)

    def get_weight(self) -> np.ndarray:
        return self.get_float_info("weight")

    def get_base_margin(self) -> np.ndarray:
        return self.get_float_info("base_margin")

    def get_group(self) -> np.ndarray:
        """Per-query group sizes (inverse of ``set_group``)."""
        ptr = self.info.group_ptr
        return (np.empty(0, np.int64) if ptr is None
                else np.diff(np.asarray(ptr, np.int64)))

    def get_data(self):
        """Feature payload as scipy CSR with missing entries absent
        (reference ``get_data``, core.py:1155)."""
        import scipy.sparse

        if self.X is None:
            raise ValueError(
                "raw data is not retained by an iterator-built matrix "
                "(reference IterativeDMatrix has no SparsePage either)")
        present = ~np.isnan(self.X)
        indptr = np.concatenate(
            [[0], np.cumsum(present.sum(axis=1))]).astype(np.int64)
        indices = np.nonzero(present)[1].astype(np.int32)
        return scipy.sparse.csr_matrix(
            (self.X[present], indices, indptr), shape=self.X.shape)

    def save_binary(self, fname: str, silent: bool = True) -> None:
        """Persist this DMatrix for later ``DMatrix(fname)`` loading
        (reference ``XGDMatrixSaveBinary``, core.py:1040; the format here is
        an npz container rather than the reference's internal page format)."""
        if self.X is None:
            raise ValueError(
                "save_binary needs raw data; iterator-built matrices only "
                "hold the quantized representation")
        payload = {"X": self.X}
        for attr in ("labels", "weights", "base_margin", "group_ptr",
                     "label_lower_bound", "label_upper_bound"):
            v = getattr(self.info, attr)
            if v is not None:
                payload[attr] = v
        if self.info.feature_names is not None:
            payload["feature_names"] = np.asarray(self.info.feature_names)
        if self.info.feature_types is not None:
            payload["feature_types"] = np.asarray(self.info.feature_types)
        with open(fname, "wb") as fh:
            np.savez(fh, **payload)

    # --- quantization --------------------------------------------------------
    def get_quantile_cut(self, max_bin: int = 256):
        """-> (indptr [n_features+1] int64, values f32): the quantile cut
        boundaries of the EXISTING quantized representation when one was
        already built (what the trained trees' split_bins index — matching
        the reference ``XGDMatrixGetQuantileCut``); only an unbinned matrix
        sketches fresh cuts with ``max_bin``."""
        cuts = (self._binned.cuts if self._binned is not None
                else self.binned(max_bin).cuts)
        return (np.asarray(cuts.ptrs, np.int64),
                np.asarray(cuts.values, np.float32))

    def binned(self, max_bin: int = 256,
               ref_cuts: Optional[HistogramCuts] = None) -> BinnedMatrix:
        """Lazily build (and cache) the quantized representation. A cached
        matrix built with different cuts than the requested ``ref_cuts`` is
        rebuilt — split_bin indices are only meaningful against the cuts the
        trees were trained with."""
        stale = (self._binned is None
                 or (ref_cuts is not None and self._binned.cuts is not ref_cuts)
                 or (ref_cuts is None and self._binned_max_bin != max_bin))
        if stale:
            if self.X is None:
                raise ValueError(
                    "an iterator-built matrix is quantized once at "
                    "construction; rebuild it with the desired max_bin or "
                    "pass ref= to share cuts")
            with obs_trace.phase("ingest", "ingest"):
                cuts = ref_cuts
                if cuts is None:
                    from .quantile import SKETCH_SAMPLE_ROWS

                    with obs_trace.phase(
                            "ingest/sketch", "ingest",
                            {"max_bin": max_bin,
                             "sample_rows": SKETCH_SAMPLE_ROWS}):
                        cuts = sketch_matrix(self.X, max_bin,
                                             self.info.weights,
                                             self.info.feature_types)
                self._binned = BinnedMatrix.from_dense(self.X, cuts)
                self._binned_max_bin = max_bin
        return self._binned

    def _init_from_iter(self, it: DataIter, max_bin: int,
                        ref: Optional[DMatrix], missing: float,
                        cache_prefix: Optional[str] = None) -> None:
        """Two streaming passes (reference ``IterativeDMatrix``,
        ``src/data/iterative_dmatrix.cc:24-52``): pass 1 sketches cuts and
        gathers metadata, pass 2 quantizes each batch into a preallocated
        bin matrix. The raw float matrix is NEVER materialised whole —
        with ``cache_prefix`` the bin matrix itself is a disk-backed
        memmap (the SparsePageDMatrix disk-spill tier,
        ``src/data/sparse_page_dmatrix.h``)."""
        from ..obs.metrics import set_binned_layout
        from .binned import _dtype_for, count_nan

        # pass 1: metadata + per-batch summaries (or copy ref cuts)
        labels, weights, margins, qids = [], [], [], []
        lbound, ubound = [], []
        summaries = None
        n_rows = 0
        n_feat = 0
        n_batches = 0
        n_nan = 0              # this process's missing entries, all batches
        need_sketch = ref is None
        feature_names: Optional[List[str]] = None
        feature_types: Optional[List[str]] = None
        col_max: Optional[np.ndarray] = None  # exact per-feature maximum
        from concurrent.futures import ThreadPoolExecutor

        from .quantile import SKETCH_SAMPLE_ROWS

        # numpy's sorts and reductions release the interpreter lock: a few
        # threads take a batch's columns side by side
        with ThreadPoolExecutor(max(1, min(16, (os.cpu_count() or 2) - 1))) \
                as pool, obs_trace.phase(
                    "ingest/sketch", "ingest",
                    {"max_bin": max_bin, "sample_rows": SKETCH_SAMPLE_ROWS}):
            for batch in it.collect():
                X, bn, bt = to_dense(batch["data"], missing,
                                     batch.get("feature_names"),
                                     batch.get("feature_types"))
                n_rows += X.shape[0]
                n_feat = X.shape[1]
                n_batches += 1
                if bn is not None:
                    feature_names = list(bn)
                if bt is not None:
                    feature_types = list(bt)
                # every column's TRUE maximum over every batch, NaN ignored.
                # The sketch's strided subsample may skip it: a category's top
                # code would fold rows into the wrong bin (reference:
                # categories bypass the sketch entirely, src/common/
                # hist_util.cc CutsBuilder for categorical), and a numeric
                # column's last cut has to lie above it (``cover_maxima``).
                # Tracked for ALL columns: feature_types may be announced on
                # any batch, and codes seen before the announcement count too.
                step = max(1, -(-X.shape[0] // 16))
                for part_max in pool.map(
                        lambda lo: np.fmax.reduce(X[lo:lo + step], axis=0,
                                                  initial=-np.inf),
                        range(0, X.shape[0], step)):
                    col_max = (part_max if col_max is None
                               else np.fmax(col_max, part_max))
                n_nan += count_nan(X)
                for key, dest in (("label", labels), ("weight", weights),
                                  ("base_margin", margins),
                                  ("label_lower_bound", lbound),
                                  ("label_upper_bound", ubound)):
                    if batch.get(key) is not None:
                        dest.append(np.asarray(batch[key], dtype=np.float32))
                if batch.get("qid") is not None:
                    qids.append(np.asarray(batch["qid"]))
                if need_sketch:
                    # strided subsample PER BATCH (cap = SKETCH_SAMPLE_ROWS/4):
                    # the sketch is approximate by design and per-feature numpy
                    # sorts dominate iterator construction at scale (41 s for
                    # 11M x 28 unsampled). A per-batch cap — rather than a
                    # global budget consumed in stream order — keeps every
                    # batch contributing equally, so time-ordered streams with
                    # distribution drift keep bin resolution over their whole
                    # range; the cost is that long streams sample more total
                    # rows than the resident path would (each batch's sort is
                    # still capped, which is what the limit is for). Weighted
                    # batches are never subsampled: dropping a heavily
                    # weighted row would starve its bin resolution.
                    bw = batch.get("weight")
                    Xs = X
                    ws = None if bw is None else np.asarray(bw, np.float64)
                    cap = SKETCH_SAMPLE_ROWS // 4 if SKETCH_SAMPLE_ROWS else 0
                    if bw is None and cap and X.shape[0] > cap:
                        Xs = X[:: -(-X.shape[0] // cap)]

                    def summarise(f, Xs=Xs, ws=ws, prev=summaries):
                        s = FeatureSummary.from_data(Xs[:, f], ws)
                        return s if prev is None \
                            else prev[f].merge(s).prune(max_bin * 8)
                    summaries = list(pool.map(summarise, range(Xs.shape[1])))
        self.X = None  # external-memory: no whole raw matrix
        self.info = MetaInfo(feature_names=feature_names,
                             feature_types=feature_types,
                             data_split_mode=self._data_split_mode)
        if labels:
            self.info.labels = np.concatenate(labels)
        if weights:
            self.info.weights = np.concatenate(weights)
        if margins:
            self.info.base_margin = np.concatenate(margins)
        if lbound:
            self.info.label_lower_bound = np.concatenate(lbound)
        if ubound:
            self.info.label_upper_bound = np.concatenate(ubound)
        if qids:
            q = np.concatenate(qids)
            _, counts = np.unique(q, return_counts=True)
            self.info.set_group(counts)
        from ..parallel import collective as _collective

        has_missing = n_nan > 0
        if (_collective.is_distributed()
                and self._data_split_mode == "row"):
            # multi-host external memory: every process streams ITS row
            # shard; cuts come from the cross-worker summary merge and the
            # missing-slot layout must agree everywhere (reference:
            # sketch sync inside QuantileDMatrix construction under rabit,
            # src/common/quantile.cc:147-276). Every rank must contribute
            # at least one batch (collectives are symmetric).
            if need_sketch:
                summaries = _collective.merge_summaries(
                    summaries or [], max_bin)
            has_missing = bool(int(_collective.allreduce(
                np.asarray([int(has_missing)]), op="max")[0]))
        if ref is not None:
            cuts = ref.binned(max_bin).cuts
        else:
            if (col_max is not None and _collective.is_distributed()
                    and self._data_split_mode == "row"):
                col_max = _collective.allreduce(
                    np.asarray(col_max, np.float32), op="max")
            if (feature_types is not None and "c" in feature_types
                    and col_max is not None and summaries is not None):
                # override the (possibly subsampled) summary for categorical
                # features with the exact observed code range: the cat
                # branch of cuts_from_summaries only reads values.max()
                for f, t in enumerate(feature_types or []):
                    if t == "c" and f < len(summaries):
                        m = max(float(col_max[f]), 0.0)
                        summaries[f] = FeatureSummary.from_data(
                            np.asarray([0.0, m], np.float32))
            cuts = cuts_from_summaries(summaries or [], max_bin,
                                       feature_types)
            if col_max is not None:
                cuts = cover_maxima(cuts, col_max)

        # pass 2: quantize batch-by-batch into one preallocated matrix
        max_nbins = int(cuts.n_real_bins().max(initial=0)) + int(has_missing)
        dtype = _dtype_for(max(max_nbins - 1, 0))
        if cache_prefix:
            local = np.memmap(f"{cache_prefix}.bins", mode="w+",
                              dtype=dtype, shape=(n_rows, n_feat))
        else:
            local = np.empty((n_rows, n_feat), dtype)
        from .binned import search_bin_into

        row = 0
        set_binned_layout(n_nan, n_rows * n_feat, np.dtype(dtype).itemsize)
        with obs_trace.phase("ingest/bin", "ingest",
                             {"rows": n_rows, "batches": n_batches,
                              "nan": n_nan, "dtype": np.dtype(dtype).name}):
            for batch in it.collect():
                X, _, _ = to_dense(batch["data"], missing)
                search_bin_into(X, cuts, max_nbins - 1,
                                local[row:row + X.shape[0]])
                row += X.shape[0]
        if cache_prefix:
            # external-memory tier: the quantized matrix stays host-resident
            # (disk-backed memmap) and STREAMS to the device in row pages
            # during training (tree/paged.py) — it never lands whole in HBM
            from .binned import PagedBinnedMatrix

            page_rows = int(os.environ.get("XTPU_PAGE_ROWS", 1_000_000))
            self._binned = PagedBinnedMatrix(
                bins_host=local, cuts=cuts, max_nbins=max_nbins,
                has_missing=has_missing,
                page_rows=max(page_rows, 1))
        else:
            self._binned = None
            self._host_bins = (local, cuts, max_nbins, has_missing)
        self._binned_max_bin = max_bin
        self._n_rows = n_rows
        self._n_cols = n_feat
        self.info.validate(self.num_row())

    def values(self) -> np.ndarray:
        """Raw features when retained; otherwise representative values
        reconstructed from the quantized bins (reference
        ``GHistIndexMatrix::GetFvalue`` — how it predicts on quantized-only
        data). Note the reconstruction materialises an [n, F] f32 matrix."""
        if self.X is not None:
            return self.X
        if getattr(self._binned, "is_paged", False):
            return self._binned.to_values_host()
        return np.asarray(self._binned.to_values())

    def append(self, data: Any, label: Any = None, *,
               weight: Any = None, missing: float = np.nan) -> int:
        """Append fresh rows IN PLACE — the continuous-training ingest path
        (docs/pipeline.md). The quantized representation, when already
        built, grows INCREMENTALLY against its existing cuts (the bin
        vocabulary the live booster's trees index into must stay frozen;
        re-sketching would silently reinterpret every committed split), so
        only the new rows are binned: O(page) work per ingest, not O(n).
        Label/weight arrays are REPLACED (not mutated) so the
        identity-keyed device caches invalidate. Returns the new row
        count. An append fingerprint chain (CRC over the appended
        features+labels, chained over the sequence of appends) rides on
        ``dmatrix_fingerprint`` so a training snapshot can never resume
        against a matrix at a different ingest position."""
        import zlib

        X, _, _ = to_dense(data, missing, None, None)
        X = np.ascontiguousarray(X, np.float32)
        if X.shape[1] != self.num_col():
            raise ValueError(
                f"append expects {self.num_col()} features, got {X.shape[1]}")
        info = self.info
        for name in ("base_margin", "group_ptr",
                     "label_lower_bound", "label_upper_bound"):
            if getattr(info, name) is not None:
                raise ValueError(
                    f"append does not support matrices carrying {name}")
        n_new = X.shape[0]
        y = w = None
        if label is not None:
            y = np.asarray(label, np.float32)
            if y.shape[0] != n_new:
                raise ValueError(
                    f"label has {y.shape[0]} entries, expected {n_new}")
        elif info.labels is not None:
            raise ValueError(
                "matrix has labels; append needs label= for the new rows")
        if weight is not None:
            w = np.asarray(weight, np.float32)
        elif info.weights is not None:
            raise ValueError(
                "matrix has weights; append needs weight= for the new rows")
        # grow the quantized representation FIRST — it can reject the rows
        # (e.g. NaNs into a no-missing-slot layout) and must do so before
        # any raw/meta state mutates
        if self._binned is not None:
            b = self._binned
            if getattr(b, "is_paged", False):
                b.append_rows(X)
            else:
                if not b.has_missing and np.isnan(X).any():
                    raise ValueError(
                        "appended rows contain missing values but the "
                        "quantized matrix has no missing slot; rebuild "
                        "from data that includes missing values")
                from .binned import _dtype_for, search_bin_into
                import jax.numpy as jnp

                local = np.empty((n_new, b.n_features),
                                 _dtype_for(max(b.max_nbins - 1, 0)))
                search_bin_into(X, b.cuts, b.max_nbins - 1, local)
                self._binned = BinnedMatrix(
                    bins=jnp.concatenate(
                        [b.bins, jnp.asarray(local).astype(b.bins.dtype)],
                        axis=0),
                    cuts=b.cuts, max_nbins=b.max_nbins,
                    has_missing=b.has_missing)
        if self.X is not None:
            self.X = np.concatenate([self.X, X], axis=0)
        else:
            self._n_rows += n_new
        if y is not None:
            info.labels = (np.array(y) if info.labels is None
                           else np.concatenate([info.labels, y], axis=0))
        if w is not None:
            info.weights = (np.array(w) if info.weights is None
                            else np.concatenate([info.weights, w]))
        crc = zlib.crc32(X.tobytes(), getattr(self, "_append_chain", 0))
        if y is not None:
            crc = zlib.crc32(np.ascontiguousarray(y).tobytes(), crc)
        self._append_chain = crc
        self._n_appends = getattr(self, "_n_appends", 0) + 1
        self.info.validate(self.num_row())
        return self.num_row()

    def slice(self, rindex: np.ndarray) -> "DMatrix":
        if self.X is None:
            raise ValueError(
                "slice needs raw data; iterator-built matrices only hold "
                "the quantized representation")
        rindex = np.asarray(rindex)
        out = DMatrix(self.X[rindex])
        info = self.info
        out.info = MetaInfo(
            labels=None if info.labels is None else info.labels[rindex],
            weights=None if info.weights is None else info.weights[rindex],
            base_margin=(None if info.base_margin is None
                         else info.base_margin[rindex]),
            label_lower_bound=(None if info.label_lower_bound is None
                               else info.label_lower_bound[rindex]),
            label_upper_bound=(None if info.label_upper_bound is None
                               else info.label_upper_bound[rindex]),
            feature_names=info.feature_names, feature_types=info.feature_types)
        return out


class DataIter:
    """External-memory data iterator ABC (reference ``DataIter``, core.py:490).

    Subclasses implement ``next(input_data)`` calling ``input_data(data=..,
    label=.., ...)`` per batch and returning 1, or returning 0 at the end, plus
    ``reset()``. ``cache_prefix`` requests the disk-spill tier: the quantized
    bin matrix lives in a memmap at ``<cache_prefix>.bins`` (reference
    ``SparsePageDMatrix`` page cache)."""

    def __init__(self, cache_prefix: Optional[str] = None) -> None:
        self._batches: List[dict] = []
        self.cache_prefix = cache_prefix

    def next(self, input_data) -> int:  # pragma: no cover - abstract
        raise NotImplementedError

    def reset(self) -> None:  # pragma: no cover - abstract
        raise NotImplementedError

    def collect(self) -> Iterator[dict]:
        """Drive the callback protocol and yield raw batch dicts.

        A ``next()`` that raises (transient read failure on the batch
        source) is retried with backoff before the error propagates —
        external-memory iterators typically front object stores or network
        filesystems where one failed read should not kill an hours-long
        run (docs/reliability.md). Each retry re-invokes ``next`` with a
        fresh collector, so a partially-delivered batch is discarded, not
        duplicated."""
        from .binned import _retry_io

        self.reset()
        while True:
            batches: List[dict] = []

            def input_data(**kwargs: Any) -> None:
                batches.append(kwargs)

            def step() -> int:
                batches.clear()
                return self.next(input_data)

            # the wait for the caller's data, retries included
            with obs_trace.phase("ingest/next", "ingest"):
                more = _retry_io(step, "data iterator next()")
            if not more:
                break
            for b in batches:
                yield b
        self.reset()


class QuantileDMatrix(DMatrix):
    """Two-pass quantized DMatrix (reference ``IterativeDMatrix``): pass 1
    sketches cuts across all batches (or reuses ``ref``'s), pass 2 bins each
    batch; the float matrix is not retained when built from an iterator."""

    @_ingest
    def __init__(self, data: Any, label: Any = None, *, max_bin: int = 256,
                 ref: Optional[DMatrix] = None, missing: float = np.nan,
                 weight: Any = None, base_margin: Any = None,
                 feature_names: Optional[List[str]] = None,
                 feature_types: Optional[List[str]] = None,
                 group: Any = None, qid: Any = None,
                 enable_categorical: bool = False) -> None:
        self.max_bin = max_bin
        if isinstance(data, DataIter):
            self._init_from_iter(data, max_bin, ref, missing,
                                 cache_prefix=data.cache_prefix)
        else:
            super().__init__(data, label, weight=weight, base_margin=base_margin,
                             missing=missing, feature_names=feature_names,
                             feature_types=feature_types, group=group, qid=qid,
                             enable_categorical=enable_categorical)
            ref_cuts = None
            if ref is not None:
                ref_cuts = ref.binned(max_bin).cuts
            self.binned(max_bin, ref_cuts=ref_cuts)

