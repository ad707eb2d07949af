"""Feature x tier support matrix, derived by RUNNING the guards.

VERDICT r4 #7: a hand-written support table drifts from the code (round 4
shipped a doc claiming paged lossguide/mesh gaps that tests disproved).
This tool derives the matrix by actually training every (feature, tier)
combination on tiny data and recording whether the configuration is
accepted or rejected — the guard logic in core.py/growers IS the source,
so the emitted table cannot contradict it. ``tests/test_support_matrix.py``
regenerates the table and asserts it equals the one embedded in
``docs/distributed.md``.

Run from the repo root (CPU, ~3-5 min): ``python tools/support_matrix.py``.
"""

from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":  # force the virtual multi-device CPU mesh
    # SAME device count as tests/conftest.py — the enforcing test
    # regenerates under the conftest mesh, so the tool must match or a
    # world-size-dependent cell would make doc and test disagree
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402


def _force_cpu():
    import jax

    jax.config.update("jax_platforms", "cpu")


# feature rows: name -> extra params (tiny shapes; numeric binary data)
FEATURES = [
    ("depthwise scalar", {}),
    ("lossguide", {"grow_policy": "lossguide", "max_leaves": 4,
                   "max_depth": 0}),
    ("multi_output_tree depthwise", {"multi": True}),
    ("multi_output_tree lossguide", {"multi": True,
                                     "grow_policy": "lossguide",
                                     "max_leaves": 4, "max_depth": 0}),
    ("dart", {"booster": "dart", "rate_drop": 0.5}),
    ("gblinear", {"booster": "gblinear"}),
    ("tree_method=approx", {"tree_method": "approx"}),
    ("tree_method=exact", {"tree_method": "exact"}),
    ("hist_method=coarse", {"hist_method": "coarse"}),
    ("hist_method=coarse + lossguide", {"hist_method": "coarse",
                                        "grow_policy": "lossguide",
                                        "max_leaves": 4, "max_depth": 0}),
    ("categorical", {"categorical": True}),
    ("monotone+interaction", {"monotone_constraints": "(1,-1,0,0)",
                              "interaction_constraints": "[[0,1],[2,3]]"}),
    ("max_leaves (depthwise)", {"max_leaves": 4}),
]

# "mesh row" covers multi-host sharded ingestion too (mesh = world,
# parallel/launch.train_per_host); "multi-host paged" is the
# communicator-synced external-memory stream (one process per host).
# Resident row-split training under a world>1 communicator RAISES (it
# would silently fit local rows only — core._check_row_comm_sync).
TIERS = ["resident", "mesh row", "mesh col", "vertical federated",
         "multi-host paged", "paged", "paged x mesh"]


def _data(multi=False, categorical=False, n=96, f=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    if categorical:
        X[:, -1] = rng.randint(0, 4, n)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    if multi:
        y = np.stack([y, 1.0 - y], axis=1)
    return X, y


def _params(extra, multi):
    p = {"objective": "reg:squarederror" if multi
         else "binary:logistic",
         "max_depth": 3, "max_bin": 16, "eta": 0.3}
    p.update({k: v for k, v in extra.items()
              if k not in ("multi", "categorical")})
    if multi:
        p["multi_strategy"] = "multi_output_tree"
    return p


def _dmatrix(X, y, categorical, **kw):
    import xgboost_tpu as xgb

    if categorical:
        kw["feature_types"] = ["q"] * (X.shape[1] - 1) + ["c"]
        kw["enable_categorical"] = True
    return xgb.DMatrix(X, label=y, **kw)


def _run_tier(tier, extra):
    """Train 1 round in the given tier; '+' if accepted, '—' if the
    configuration is rejected with NotImplementedError/ValueError."""
    import xgboost_tpu as xgb

    multi = bool(extra.get("multi"))
    categorical = bool(extra.get("categorical"))
    X, y = _data(multi=multi, categorical=categorical)
    params = _params(extra, multi)

    def fit(params=params, dm_kw=None, it=None, env=None):
        old = {}
        for k, v in (env or {}).items():
            old[k] = os.environ.get(k)
            os.environ[k] = v
        try:
            if it is not None:
                dm = xgb.QuantileDMatrix(it, max_bin=16)
            else:
                dm = _dmatrix(X, y, categorical, **(dm_kw or {}))
            xgb.train(params, dm, 1, verbose_eval=False)
        finally:
            for k, v in old.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    def paged_iter():
        from xgboost_tpu.data.dmatrix import DataIter

        class It(DataIter):
            def __init__(self, tmp):
                super().__init__()
                self.cache_prefix = os.path.join(tmp, "pc")
                self.parts = np.array_split(np.arange(len(X)), 2)
                self.i = 0

            def next(self, input_data):
                if self.i >= len(self.parts):
                    return 0
                idx = self.parts[self.i]
                kw = {}
                if categorical:
                    kw["feature_types"] = ["q"] * (X.shape[1] - 1) + ["c"]
                    kw["enable_categorical"] = True
                input_data(data=X[idx], label=y[idx], **kw)
                self.i += 1
                return 1

            def reset(self):
                self.i = 0

        return It

    try:
        if tier == "resident":
            fit()
        elif tier == "mesh row":
            fit({**params, "mesh": xgb.make_data_mesh()})
        elif tier == "mesh col":
            fit({**params, "mesh": xgb.make_data_mesh(),
                 "data_split_mode": "col"})
        elif tier == "vertical federated":
            _run_vertical(params, X, y, categorical)
        elif tier == "multi-host paged":
            _run_multihost(params, X, y, categorical, paged_iter())
        elif tier == "paged":
            import tempfile

            # collapse off: the paged row documents the STREAMING tier's
            # guards — with it on, any matrix under the HBM budget would
            # take the resident fast path and the row would just repeat
            # the resident column (docs/distributed.md notes the collapse)
            with tempfile.TemporaryDirectory() as tmp:
                fit(it=paged_iter()(tmp), env={"XTPU_PAGE_ROWS": "48",
                                               "XTPU_PAGED_COLLAPSE": "0"})
        elif tier == "paged x mesh":
            import tempfile

            with tempfile.TemporaryDirectory() as tmp:
                fit({**params, "mesh": xgb.make_data_mesh()},
                    it=paged_iter()(tmp), env={"XTPU_PAGE_ROWS": "48"})
        else:  # pragma: no cover
            raise AssertionError(tier)
        return "+"
    except NotImplementedError:
        return "—"
    except ValueError as e:
        # only DELIBERATE scope guards count as rejection — an incidental
        # numpy/jax ValueError must fail the generation, not get published
        # (and then test-enforced) as "cleanly rejected"
        if re.search(r"not support|supports|requires|only", str(e)):
            return "—"
        raise


def _run_vertical(params, X, y, categorical):
    import threading

    import xgboost_tpu as xgb
    from xgboost_tpu.parallel import collective
    from xgboost_tpu.parallel.collective import InMemoryCommunicator

    comms = InMemoryCommunicator.make_world(2)
    errors = []

    def worker(rank):
        collective.set_thread_local_communicator(comms[rank])
        try:
            lo, hi = (0, 2) if rank == 0 else (2, X.shape[1])
            kw = {}
            if categorical and hi == X.shape[1]:
                kw["feature_types"] = ["q"] * (hi - lo - 1) + ["c"]
                kw["enable_categorical"] = True
            dm = xgb.DMatrix(X[:, lo:hi],
                             label=y if rank == 0 else None,
                             data_split_mode="col", **kw)
            xgb.train({**params, "data_split_mode": "col"}, dm, 1,
                      verbose_eval=False)
        except Exception as e:
            errors.append(e)
        finally:
            collective.set_thread_local_communicator(None)

    _join_or_raise([threading.Thread(target=worker, args=(r,), daemon=True)
                    for r in range(2)], 120, errors)


def _join_or_raise(threads, timeout, errors):
    """A worker that deadlocks on a collective must be reported, never
    recorded as supported (and never block interpreter exit — daemons)."""
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    if any(t.is_alive() for t in threads):
        # neither supported nor cleanly rejected — fail the generation
        # loudly (RuntimeError is NOT caught by _run_tier)
        raise RuntimeError("tier worker deadlocked (timeout)")
    if errors:
        raise errors[0]


def _run_multihost(params, X, y, categorical, it_cls):
    """Per-rank external-memory stream under the communicator (one
    process per host; per-level histogram allreduce in tree/paged.py)."""
    import tempfile
    import threading

    import xgboost_tpu as xgb
    from xgboost_tpu.parallel import collective
    from xgboost_tpu.parallel.collective import InMemoryCommunicator

    comms = InMemoryCommunicator.make_world(2)
    errors = []
    n_half = len(X) // 2
    prior = os.environ.get("XTPU_PAGE_ROWS")
    os.environ["XTPU_PAGE_ROWS"] = "24"

    def worker(rank):
        collective.set_thread_local_communicator(comms[rank])
        try:
            with tempfile.TemporaryDirectory() as tmp:
                it = it_cls(tmp)
                # this rank streams only ITS half of the global rows
                it.parts = [np.arange(n_half) + (0 if rank == 0
                                                 else n_half)]
                dm = xgb.QuantileDMatrix(it, max_bin=16)
                xgb.train(params, dm, 1, verbose_eval=False)
        except Exception as e:
            errors.append(e)
        finally:
            collective.set_thread_local_communicator(None)

    try:
        _join_or_raise(
            [threading.Thread(target=worker, args=(r,), daemon=True)
             for r in range(2)], 180, errors)
    finally:
        if prior is None:
            os.environ.pop("XTPU_PAGE_ROWS", None)
        else:
            os.environ["XTPU_PAGE_ROWS"] = prior


def support_matrix():
    """[(feature, {tier: '+'|'—'})] by running every combination."""
    rows = []
    for name, extra in FEATURES:
        cells = {}
        for tier in TIERS:
            cells[tier] = _run_tier(tier, extra)
        rows.append((name, cells))
    return rows


def to_markdown(rows):
    lines = ["| feature | " + " | ".join(TIERS) + " |",
             "|---|" + "---|" * len(TIERS)]
    for name, cells in rows:
        lines.append("| " + name + " | "
                     + " | ".join(cells[t] for t in TIERS) + " |")
    return "\n".join(lines)


def main():
    _force_cpu()
    print(to_markdown(support_matrix()))


if __name__ == "__main__":
    main()
