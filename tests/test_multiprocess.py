"""True multi-controller training: 2 OS processes, each holding ONLY its row
shard, rendezvous through jax.distributed on CPU, train via
launch.train_per_host -> ShardedDMatrix (VERDICT r1 item 3). The per-host
shards must reproduce the single-host model without any process ever
materialising the global feature matrix."""

import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import xgboost_tpu as xgb

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = textwrap.dedent("""
    import os, sys, json
    sys.path.insert(0, __REPO__)
    import numpy as np

    rank = int(sys.argv[1]); world = int(sys.argv[2]); coord = sys.argv[3]
    out_path = sys.argv[4]
    tree_method = sys.argv[5] if len(sys.argv) > 5 else "hist"

    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=world, process_id=rank)
    assert jax.process_count() == world

    import xgboost_tpu as xgb
    from xgboost_tpu.parallel import launch

    # deterministic global dataset; each process SLICES ONLY ITS SHARD
    rng = np.random.RandomState(42)
    X = rng.randn(803, 6).astype(np.float32)
    y = (X @ rng.randn(6) > 0).astype(np.float32)
    n_half = 401  # uneven split: rank 0 gets 401 rows, rank 1 gets 402
    sl = slice(0, n_half) if rank == 0 else slice(n_half, None)
    X_local, y_local = X[sl], y[sl]

    res = {}
    with launch.CommunicatorContext():
        bst = launch.train_per_host(
            {"objective": "binary:logistic", "max_depth": 4, "eta": 0.3,
             "tree_method": tree_method,
             "eval_metric": ["logloss", "auc"]},
            X_local, y_local, 5,
            evals_result=res, verbose_eval=False)
        # distributed eval: each rank evaluates its LOCAL shard and the
        # metrics aggregate through the communicator (GlobalRatio / exact
        # AUC merge) — every rank must see the GLOBAL value
        from xgboost_tpu.parallel.launch import ShardedDMatrix
        sdm = bst._caches[next(iter(bst._caches))]["dm"]
        assert isinstance(sdm, ShardedDMatrix)
        line = bst.eval_set([(sdm, "train")], 0)
    # local predictions on the local shard (raw-threshold walk)
    preds = np.asarray(bst.predict(xgb.DMatrix(X_local)))
    with open(out_path, "w") as fh:
        json.dump({"rank": rank, "preds": preds.tolist(),
                   "n_trees": len(bst.gbm.trees),
                   "base": float(np.asarray(bst.base_margin_).reshape(-1)[0]),
                   "eval_line": line,
                   }, fh)
""")


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


@pytest.mark.slow
@pytest.mark.parametrize("tree_method", ["hist", "approx"])
def test_two_process_sharded_training(tmp_path, tree_method):
    world = 2
    coord = f"127.0.0.1:{_free_port()}"
    script = tmp_path / "worker.py"
    script.write_text(_WORKER.replace("__REPO__", repr(_REPO)))

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    procs, outs = [], []
    for rank in range(world):
        out = tmp_path / f"out{rank}.json"
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(rank), str(world), coord,
             str(out), tree_method], env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT))
    logs = []
    for p in procs:
        stdout, _ = p.communicate(timeout=420)
        logs.append(stdout.decode(errors="replace"))
    for rank, p in enumerate(procs):
        assert p.returncode == 0, f"rank {rank} failed:\n{logs[rank]}"

    results = [json.load(open(o)) for o in outs]
    preds_dist = np.concatenate(
        [np.asarray(r["preds"]) for r in sorted(results,
                                                key=lambda r: r["rank"])])

    # single-host reference on the SAME global data
    rng = np.random.RandomState(42)
    X = rng.randn(803, 6).astype(np.float32)
    y = (X @ rng.randn(6) > 0).astype(np.float32)
    bst = xgb.train({"objective": "binary:logistic", "max_depth": 4,
                     "eta": 0.3, "tree_method": tree_method},
                    xgb.DMatrix(X, label=y), 5,
                    verbose_eval=False)
    preds_single = np.asarray(bst.predict(xgb.DMatrix(X)))

    assert results[0]["n_trees"] == len(bst.gbm.trees)
    # identical base score on every rank (fit_stump GlobalSum)
    assert results[0]["base"] == pytest.approx(results[1]["base"], abs=1e-6)
    # distributed metrics: both ranks computed the identical GLOBAL eval
    # line from shard-local labels (GlobalRatio + exact AUC merge)
    assert results[0]["eval_line"] == results[1]["eval_line"]
    assert "train-logloss" in results[0]["eval_line"]
    assert "train-auc" in results[0]["eval_line"]
    # sharded cuts differ slightly from single-host cuts (distributed sketch
    # merge), so trees can route borderline rows differently — demand close
    # agreement, not bitwise equality
    assert np.mean(np.abs(preds_dist - preds_single) < 0.05) > 0.97
    acc_d = float(np.mean((preds_dist > 0.5) == y))
    acc_s = float(np.mean((preds_single > 0.5) == y))
    assert acc_d > 0.9 and abs(acc_d - acc_s) < 0.03
