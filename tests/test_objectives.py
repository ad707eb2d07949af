"""Objective family tests: gradients sanity + end-to-end training quality.

Modeled on the reference's CheckObjFunction-style tests (tests/cpp/objective/*)
plus training-convergence checks per family.
"""

import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.objective import get_objective

from conftest import make_regression


class _Info:
    def __init__(self, labels, weights=None, **kw):
        self.labels = np.asarray(labels, dtype=np.float32)
        self.weights = weights
        self.group_ptr = kw.get("group_ptr")
        self.label_lower_bound = kw.get("label_lower_bound")
        self.label_upper_bound = kw.get("label_upper_bound")


def _grad(name, preds, labels, params=None, **kw):
    obj = get_objective(name, params or {})
    info = _Info(labels, **kw)
    preds = np.asarray(preds, dtype=np.float32).reshape(len(labels), -1)
    out = np.asarray(obj.get_gradient(preds, info))
    return out[..., 0], out[..., 1]


def test_squarederror_gradients():
    g, h = _grad("reg:squarederror", [0.5, 1.0], [1.0, 1.0])
    np.testing.assert_allclose(g.ravel(), [-0.5, 0.0])
    np.testing.assert_allclose(h.ravel(), [1.0, 1.0])


def test_logistic_gradients():
    # at margin 0: p=0.5 -> g = 0.5 - y, h = 0.25
    g, h = _grad("binary:logistic", [0.0, 0.0], [0.0, 1.0])
    np.testing.assert_allclose(g.ravel(), [0.5, -0.5])
    np.testing.assert_allclose(h.ravel(), [0.25, 0.25], rtol=1e-5)


def test_poisson_gradients():
    g, h = _grad("count:poisson", [0.0], [2.0])
    np.testing.assert_allclose(g.ravel(), [-1.0])  # exp(0) - 2
    assert h.ravel()[0] > 1.0  # exp(0 + max_delta_step)


def test_softprob_gradients_sum_zero():
    g, h = _grad("multi:softprob", np.zeros((4, 3)), [0, 1, 2, 0],
                 params={"num_class": 3})
    np.testing.assert_allclose(g.sum(axis=1), 0.0, atol=1e-6)
    assert (h > 0).all()


def test_absoluteerror_training_median():
    # asymmetric noise: MAE fit should track the median, not the mean
    rng = np.random.RandomState(0)
    n = 2000
    X = rng.randn(n, 4).astype(np.float32)
    base = X[:, 0] * 2.0
    noise = np.where(rng.rand(n) < 0.9, 0.0, 50.0)  # big one-sided outliers
    y = base + noise
    dm = xgb.DMatrix(X, label=y)
    res = {}
    bst = xgb.train({"objective": "reg:absoluteerror", "max_depth": 4,
                     "eta": 0.3}, dm, 30, evals=[(dm, "train")],
                    evals_result=res, verbose_eval=False)
    assert res["train"]["mae"][-1] < res["train"]["mae"][0]
    preds = bst.predict(dm)
    # median regression ignores the outliers: predictions near base signal
    assert np.median(np.abs(preds - base)) < 2.0


def test_quantile_training_coverage():
    rng = np.random.RandomState(1)
    n = 3000
    X = rng.randn(n, 3).astype(np.float32)
    y = X[:, 0] + rng.randn(n)
    dm = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "reg:quantileerror", "quantile_alpha": 0.9,
                     "max_depth": 4, "eta": 0.3}, dm, 30, verbose_eval=False)
    preds = bst.predict(dm)
    coverage = float((y <= preds).mean())
    assert 0.82 < coverage < 0.97, coverage


def test_multi_quantile_targets():
    rng = np.random.RandomState(2)
    X = rng.randn(1000, 3).astype(np.float32)
    y = X[:, 0] + rng.randn(1000)
    dm = xgb.DMatrix(X, label=y)
    bst = xgb.train({"objective": "reg:quantileerror",
                     "quantile_alpha": [0.1, 0.5, 0.9], "max_depth": 3},
                    dm, 20, verbose_eval=False)
    preds = bst.predict(dm)
    assert preds.shape == (1000, 3)
    # quantile ordering should mostly hold
    frac_ordered = float(((preds[:, 0] <= preds[:, 1])
                          & (preds[:, 1] <= preds[:, 2])).mean())
    assert frac_ordered > 0.7


def test_aft_training():
    rng = np.random.RandomState(3)
    n = 1500
    X = rng.randn(n, 4).astype(np.float32)
    t = np.exp(0.5 * X[:, 0] + 0.1 * rng.randn(n))
    censored = rng.rand(n) < 0.3
    lower = t.copy()
    upper = np.where(censored, np.inf, t)
    dm = xgb.DMatrix(X, label=lower, label_lower_bound=lower,
                     label_upper_bound=upper)
    res = {}
    bst = xgb.train({"objective": "survival:aft",
                     "aft_loss_distribution": "normal",
                     "aft_loss_distribution_scale": 1.0,
                     "max_depth": 3, "eta": 0.2}, dm, 25,
                    evals=[(dm, "train")], evals_result=res,
                    verbose_eval=False)
    nll = res["train"]["aft-nloglik"]
    assert nll[-1] < nll[0]
    preds = bst.predict(dm)  # predicted survival time
    corr = np.corrcoef(np.log(preds), np.log(t))[0, 1]
    assert corr > 0.5, corr


@pytest.mark.parametrize("dist", ["logistic", "extreme"])
def test_aft_distributions_finite(dist):
    rng = np.random.RandomState(4)
    X = rng.randn(300, 3).astype(np.float32)
    t = np.exp(X[:, 0])
    dm = xgb.DMatrix(X, label=t, label_lower_bound=t, label_upper_bound=t)
    bst = xgb.train({"objective": "survival:aft",
                     "aft_loss_distribution": dist, "max_depth": 3},
                    dm, 5, verbose_eval=False)
    assert np.isfinite(bst.predict(dm)).all()


def test_cox_training():
    rng = np.random.RandomState(5)
    n = 1200
    X = rng.randn(n, 4).astype(np.float32)
    hazard = np.exp(X[:, 0])
    t = rng.exponential(1.0 / hazard)
    censored = rng.rand(n) < 0.2
    y = np.where(censored, -t, t).astype(np.float32)
    dm = xgb.DMatrix(X, label=y)
    res = {}
    bst = xgb.train({"objective": "survival:cox", "max_depth": 3,
                     "eta": 0.2}, dm, 20, evals=[(dm, "train")],
                    evals_result=res, verbose_eval=False)
    assert res["train"]["cox-nloglik"][-1] < res["train"]["cox-nloglik"][0]
    # higher predicted hazard should correlate with shorter survival
    hr = bst.predict(dm)
    corr = np.corrcoef(np.log(hr), X[:, 0])[0, 1]
    assert corr > 0.6, corr


def _make_ltr(n_query=30, docs=20, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n_query * docs, f).astype(np.float32)
    w = rng.randn(f).astype(np.float32)
    score = X @ w + 0.5 * rng.randn(n_query * docs)
    # graded relevance 0-3 by within-query quartile
    y = np.zeros(n_query * docs, dtype=np.float32)
    for q in range(n_query):
        s = score[q * docs:(q + 1) * docs]
        y[q * docs:(q + 1) * docs] = np.digitize(
            s, np.quantile(s, [0.5, 0.75, 0.9]))
    qid = np.repeat(np.arange(n_query), docs)
    return X, y, qid


@pytest.mark.parametrize("obj", ["rank:ndcg", "rank:pairwise", "rank:map"])
def test_lambdarank_training(obj):
    X, y, qid = _make_ltr(seed=6)
    ylab = (y > 0).astype(np.float32) if obj == "rank:map" else y
    dm = xgb.DMatrix(X, label=ylab, qid=qid)
    res = {}
    xgb.train({"objective": obj, "max_depth": 3, "eta": 0.3,
               "eval_metric": ["ndcg@5"]},
              dm, 20, evals=[(dm, "train")], evals_result=res,
              verbose_eval=False)
    hist = res["train"]["ndcg@5"]
    assert hist[-1] > hist[0], hist
    assert hist[-1] > 0.8


def test_ndcg_metric_perfect_ranking():
    from xgboost_tpu.metric import get_metric

    info = _Info([3.0, 2.0, 1.0, 0.0],
                 group_ptr=np.asarray([0, 4], dtype=np.int64))
    m = get_metric("ndcg")
    assert m(np.asarray([4.0, 3.0, 2.0, 1.0]), info) == pytest.approx(1.0)
    worst = m(np.asarray([1.0, 2.0, 3.0, 4.0]), info)
    assert worst < 1.0


def test_weighted_training():
    X, y = make_regression(600, 5)
    w = np.ones(600, dtype=np.float32)
    w[:300] = 10.0
    dm = xgb.DMatrix(X, label=y, weight=w)
    bst = xgb.train({"objective": "reg:squarederror", "max_depth": 3}, dm, 10,
                    verbose_eval=False)
    p = bst.predict(dm)
    hi = np.mean((p[:300] - y[:300]) ** 2)
    lo = np.mean((p[300:] - y[300:]) ** 2)
    assert hi < lo  # heavily weighted rows fit better


@pytest.mark.parametrize("obj", ["rank:ndcg", "rank:pairwise", "rank:map"])
@pytest.mark.parametrize("exp_gain,cap", [(True, None), (False, None),
                                          (True, 4)])
def test_lambdarank_device_matches_host_loop(obj, exp_gain, cap, monkeypatch):
    # the padded [C, K, L] device gradient must reproduce the per-group
    # host loop's math, f32 vs f64 tolerance only; ragged groups +
    # per-query weights. ``cap`` None: the topk default (every pair of a
    # group, deterministic); ``cap`` 4: only pairs whose better-ranked doc
    # is currently in the top 4, exactly like the host ``_pairs`` (one case
    # an objective: the cap used to be tested for rank:ndcg alone)
    from xgboost_tpu.objective import get_objective

    rng = np.random.RandomState(3)
    sizes = [1, 7, 30, 2, 13]
    hi = 2 if obj == "rank:map" else 4   # map requires binary relevance
    y = np.concatenate([rng.randint(0, hi, s) for s in sizes]).astype(
        np.float32)
    s = rng.randn(len(y)).astype(np.float32)
    ptr = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    w = rng.rand(len(sizes)).astype(np.float32) + 0.5
    info = _Info(y, group_ptr=ptr, weights=w)
    params = {"ndcg_exp_gain": str(exp_gain).lower(),
              "lambdarank_pair_method": "topk"}
    if cap is not None:
        params["lambdarank_num_pair_per_sample"] = cap

    monkeypatch.delenv("XTPU_RANK_HOST", raising=False)
    o_dev = get_objective(obj, dict(params))
    g_dev = np.asarray(o_dev.get_gradient(s, info))
    monkeypatch.setenv("XTPU_RANK_HOST", "1")
    o_host = get_objective(obj, dict(params))
    g_host = np.asarray(o_host.get_gradient(s, info))
    np.testing.assert_allclose(g_dev, g_host, rtol=2e-4, atol=1e-6)
    if cap is not None:      # and the cap is not ignored
        monkeypatch.delenv("XTPU_RANK_HOST", raising=False)
        del params["lambdarank_num_pair_per_sample"]
        g_all = np.asarray(get_objective(obj, dict(params))
                           .get_gradient(s, info))
        assert np.abs(g_all - g_dev).max() > 1e-3


def _equations(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included (a Pallas
    kernel's body is a program of its own: not descended into)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)     # a ClosedJaxpr's own
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _gradient_shapes(n, G, L, kpos):
    """The argument shapes of ``_lambda_grad_device``: scores, padded
    labels, tile tables, weights, bias."""
    import jax
    import jax.numpy as jnp

    from xgboost_tpu.objective import ranking as rk

    rows = jax.ShapeDtypeStruct((n,), jnp.float32)
    tiles = rk._Tiles(*[jax.ShapeDtypeStruct((G,), jnp.int32)] * 3)
    bias = (jax.ShapeDtypeStruct((kpos,), jnp.float32),) * 2 if kpos else ()
    return (rows, jax.ShapeDtypeStruct((G, L), jnp.float32), tiles, rows,
            *bias)


@pytest.mark.parametrize("objective,kpos", [("ndcg", 0), ("pairwise", 0),
                                            ("map", 0), ("ndcg", 8)])
@pytest.mark.parametrize("kcap", [8, 0])
def test_topk_chunk_works_in_rank_order(objective, kpos, kcap):
    # the chunk loop of the ``topk`` kernel: sorts, a [C, K, L] block and
    # nothing looked up row by row. K = min(kcap, L) anchors a group (L with
    # no truncation), so under a truncation no value of L x L a group exists
    import functools

    import jax
    import jax.numpy as jnp

    from xgboost_tpu.objective import ranking as rk

    n, G, L, C = 600, 24, 40, 4
    closed = jax.make_jaxpr(functools.partial(
        rk._lambda_grad_device, kcap=kcap, L=L, exp_gain=True,
        objective=objective, chunk=C, kpos=kpos))(
            *_gradient_shapes(n, G, L, kpos))
    (loop,) = [e for e in _equations(closed.jaxpr)
               if e.primitive.name == "scan"]
    body = list(_equations(loop.params["jaxpr"].jaxpr))
    names = {e.primitive.name for e in body}
    assert "sort" in names
    assert not {p for p in names if "gather" in p or "scatter" in p}, names
    K = rk._topk_anchors(kcap, L)
    assert max(v.aval.size for e in body for v in e.outvars) == C * K * L
    assert loop.params["length"] == G // C


@pytest.mark.parametrize("obj", ["rank:ndcg", "rank:map"])
def test_lambdarank_mean_device_gradient_properties(obj, monkeypatch):
    """The sampled-pair (mean, the reference default) device gradient:
    per-group gradients sum to zero (pair antisymmetry), hessians are
    positive where pairs exist, and the estimator's EXPECTATION matches
    the host sampler's (same out-of-bucket uniform distribution; averaged
    over many iterations the two means converge)."""
    from xgboost_tpu.objective import get_objective

    rng = np.random.RandomState(11)
    sizes = [5, 12, 3, 20]
    hi = 2 if obj == "rank:map" else 4   # map requires binary relevance
    y = np.concatenate([rng.randint(0, hi, s) for s in sizes]).astype(
        np.float32)
    s = rng.randn(len(y)).astype(np.float32)
    ptr = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    info = _Info(y, group_ptr=ptr)
    params = {"lambdarank_pair_method": "mean",
              "lambdarank_num_pair_per_sample": 2, "seed": 3}

    monkeypatch.delenv("XTPU_RANK_HOST", raising=False)
    o_dev = get_objective(obj, dict(params))
    g0 = np.asarray(o_dev.get_gradient(s, info, 0))
    for a, b in zip(ptr[:-1], ptr[1:]):
        np.testing.assert_allclose(g0[a:b, 0, 0].sum(), 0.0, atol=1e-4)
        assert (g0[a:b, 0, 1] >= 0).all()

    n_iters = 300
    acc_dev = np.zeros((len(y), 2))
    for it in range(n_iters):
        acc_dev += np.asarray(o_dev.get_gradient(s, info, it))[:, 0, :]
    monkeypatch.setenv("XTPU_RANK_HOST", "1")
    o_host = get_objective(obj, dict(params))
    acc_host = np.zeros((len(y), 2))
    for it in range(n_iters):
        acc_host += np.asarray(o_host.get_gradient(s, info, it))[:, 0, :]
    scale = np.abs(acc_host).max()
    np.testing.assert_allclose(acc_dev / n_iters, acc_host / n_iters,
                               atol=0.15 * scale / n_iters)


def test_lambdarank_default_method_is_mean():
    """Reference parity: lambdarank_pair_method defaults to 'mean'
    (doc/parameter.rst:489). Pinned BEHAVIOURALLY: mean resamples rivals
    per iteration, so the default gradient must vary with the iteration
    number while an explicit topk gradient must not."""
    from xgboost_tpu.objective import get_objective

    rng = np.random.RandomState(15)
    y = rng.randint(0, 4, 30).astype(np.float32)
    s = rng.randn(30).astype(np.float32)
    info = _Info(y, group_ptr=np.asarray([0, 30], np.int64))
    o_def = get_objective("rank:ndcg", {})
    g0 = np.asarray(o_def.get_gradient(s, info, 0))
    g1 = np.asarray(o_def.get_gradient(s, info, 1))
    assert not np.array_equal(g0, g1)  # stochastic -> mean sampling
    o_topk = get_objective("rank:ndcg", {"lambdarank_pair_method": "topk"})
    t0 = np.asarray(o_topk.get_gradient(s, info, 0))
    t1 = np.asarray(o_topk.get_gradient(s, info, 1))
    np.testing.assert_array_equal(t0, t1)  # deterministic -> topk
    # and the default config still trains (device mean path)
    X, y, qid = _make_ltr(seed=12)
    dm = xgb.DMatrix(X, label=y, qid=qid)
    res = {}
    xgb.train({"objective": "rank:ndcg", "max_depth": 3, "eta": 0.3,
               "eval_metric": ["ndcg@5"]}, dm, 25,
              evals=[(dm, "train")], evals_result=res, verbose_eval=False)
    hist = res["train"]["ndcg@5"]
    assert hist[-1] > hist[0]


def test_rank_map_rejects_graded_labels():
    """Reference IsBinaryRel (ranking_utils.h:362): |dAP| needs 0/1."""
    y = np.asarray([0.0, 2.0, 1.0, 3.0], np.float32)
    info = _Info(y, group_ptr=np.asarray([0, 4], np.int64))
    with pytest.raises(ValueError, match="binary"):
        get_objective("rank:map", {}).get_gradient(
            np.zeros(4, np.float32), info)


@pytest.mark.parametrize("objective", ["rank:ndcg", "rank:pairwise"])
def test_lambdarank_unbiased_device_matches_host_oracle(objective):
    """The device unbiased path (_debias_dev) must reproduce the host
    loop's gradients and learned ti+/tj- (topk pairs are deterministic,
    so the two paths see the identical pair multiset; f32 vs f64 costs a
    tolerance, not a different answer)."""
    import os

    rng = np.random.RandomState(3)
    n_query, docs = 25, 9
    y = (rng.rand(n_query * docs) < 0.4).astype(np.float32)
    preds = rng.randn(n_query * docs).astype(np.float32)
    ptr = np.arange(0, n_query * docs + 1, docs, dtype=np.int64)
    params = {"lambdarank_pair_method": "topk",
              "lambdarank_unbiased": True}
    obj_d = get_objective(objective, dict(params))
    obj_h = get_objective(objective, dict(params))
    info = _Info(y, group_ptr=ptr)
    for it in range(3):
        gd = np.asarray(obj_d.get_gradient(preds, info, iteration=it))
        os.environ["XTPU_RANK_HOST"] = "1"
        try:
            gh = np.asarray(obj_h.get_gradient(preds, info, iteration=it))
        finally:
            os.environ.pop("XTPU_RANK_HOST", None)
        np.testing.assert_allclose(gd, gh, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(obj_d._ti_plus, obj_h._ti_plus,
                                   rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(obj_d._tj_minus, obj_h._tj_minus,
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("method", ["topk", "mean"])
def test_lambdarank_unbiased_learns_position_bias(method):
    """Unbiased LambdaMART (reference lambdarank_obj.cc:42-89): with
    position-biased click labels, the ti+/tj- ratios move away from 1,
    stay finite/positive, normalize to position 0, and training still
    improves the ranking metric."""
    rng = np.random.RandomState(17)
    n_query, docs = 60, 12
    X = rng.randn(n_query * docs, 5).astype(np.float32)
    w = rng.randn(5).astype(np.float32)
    true_rel = (X @ w > 0.3).astype(np.float32)
    # click labels: true relevance observed with position-decaying
    # probability (docs are presented in data order)
    pos = np.tile(np.arange(docs), n_query)
    observe = rng.rand(n_query * docs) < 1.0 / np.sqrt(pos + 1.0)
    clicks = (true_rel * observe).astype(np.float32)
    qid = np.repeat(np.arange(n_query), docs)
    dm = xgb.DMatrix(X, label=clicks, qid=qid)
    res = {}
    bst = xgb.train({"objective": "rank:ndcg", "max_depth": 3, "eta": 0.3,
                     "lambdarank_unbiased": True,
                     "lambdarank_pair_method": method,
                     "eval_metric": "ndcg@5"}, dm, 15,
                    evals=[(dm, "train")], evals_result=res,
                    verbose_eval=False)
    hist = res["train"]["ndcg@5"]
    assert hist[-1] > hist[0]
    tp = bst.obj._ti_plus
    tm = bst.obj._tj_minus
    assert tp is not None and np.isfinite(tp).all() and (tp > 0).all()
    assert np.isfinite(tm).all() and (tm > 0).all()
    assert tp[0] == pytest.approx(1.0)
    assert not np.allclose(tp, 1.0)  # bias actually learned
    # debiasing changes the gradients: compare against a biased run on the
    # SAME (host) execution path and RNG stream, so the only difference
    # is the ti+/tj- scaling itself
    import os

    os.environ["XTPU_RANK_HOST"] = "1"
    try:
        b2 = xgb.train({"objective": "rank:ndcg", "max_depth": 3,
                        "eta": 0.3, "lambdarank_pair_method": method},
                       dm, 15, verbose_eval=False)
    finally:
        os.environ.pop("XTPU_RANK_HOST", None)
    assert bytes(bst.save_raw("json")) != bytes(b2.save_raw("json"))
    # the learned bias state round-trips through save/load
    b3 = xgb.Booster()
    b3.load_model(bytes(bst.save_raw("json")))
    np.testing.assert_allclose(b3.obj._ti_plus, tp)
    np.testing.assert_allclose(b3.obj._tj_minus, tm)


# ---- the padded layout's tile path against the per-group gather forms ----
#
# Until the tile path, rows reached the padded [Gp, L] layout by one slice a
# group (``vmap`` of ``dynamic_slice``) and left it by a gather of single
# elements. Those forms, kept here as the reference, give the same bits.

def _slices_in(rows, tiles, L, fill):
    """The way in before the tile path: L rows from each group's start."""
    import jax
    import jax.numpy as jnp

    from xgboost_tpu.objective.ranking import LANES

    starts = tiles.first * LANES + tiles.shift
    ext = jnp.concatenate([rows, jnp.zeros((L,), rows.dtype)])
    block = jax.vmap(lambda p: jax.lax.dynamic_slice(ext, (p,), (L,)))(starts)
    return jnp.where(jnp.arange(L)[None, :] < tiles.sizes[:, None], block,
                     fill)


def _elements_out(pad, tiles, n):
    """The way back before the tile path: each row's (group, slot)."""
    import jax.numpy as jnp

    from xgboost_tpu.objective.ranking import LANES

    starts = tiles.first * LANES + tiles.shift
    qidx = jnp.repeat(jnp.arange(pad.shape[0]), tiles.sizes,
                      total_repeat_length=n)
    return pad[qidx, jnp.arange(n) - starts[qidx]]


@pytest.fixture
def gather_forms(monkeypatch):
    """The device gradient with the layout's gather forms in place of the
    tile path, compiled afresh (no trace of the tile path is reused)."""
    import jax

    from xgboost_tpu.objective import ranking as rk

    monkeypatch.setattr(rk, "_padded_layout", _slices_in)
    monkeypatch.setattr(rk, "_rows_of", _elements_out)
    statics = ("L", "exp_gain", "objective", "chunk", "kpos")
    for name, own in (("_lambda_grad_device", "kcap"),
                      ("_lambda_grad_device_mean", "k")):
        monkeypatch.setattr(rk, name, jax.jit(
            getattr(rk, name).__wrapped__, static_argnames=statics + (own,)))


def _groups(case):
    """Group sizes of a layout case and the groups padded on."""
    return {"sizes_1_8_127_128_129_L": ([1, 8, 127, 128, 129, 300, 2], 0),
            "aligned_starts": ([128, 256, 128, 384], 0),
            "window_past_n": ([200, 129, 3], 0),
            "padded_to_chunks": ([5, 130, 77, 1, 64], 3),
            "L_below_128": ([3, 40, 7, 1, 12, 9], 2),
            "one_row_groups": ([1] * 200 + [6], 1),
            "steps_of_groups": ([(7 * i) % 97 + 1 for i in range(150)], 5),
            # groups longer than a kernel's piece of 4,096 slots: moved as
            # pieces, the last one partial or empty
            "longer_than_a_piece": ([4097, 1, 9000, 4096, 130, 8193], 1),
            }[case]


@pytest.mark.parametrize("case", ["sizes_1_8_127_128_129_L",
                                  "aligned_starts", "window_past_n",
                                  "padded_to_chunks", "L_below_128",
                                  "one_row_groups", "steps_of_groups",
                                  "longer_than_a_piece"])
def test_tile_layout_matches_the_gather_forms(case):
    import jax
    import jax.numpy as jnp

    from xgboost_tpu.objective import ranking as rk
    from xgboost_tpu.ops.pallas.rank_layout import PIECE, window_tiles

    sizes, pad_groups = _groups(case)
    ptr = np.concatenate([[0], np.cumsum(sizes)])
    n, L, Gp = int(ptr[-1]), max(sizes), len(sizes) + pad_groups
    tiles = jax.tree.map(jnp.asarray, rk._tile_tables(ptr, Gp))
    if case == "aligned_starts":
        assert not np.asarray(tiles.shift).any()
    assert (L > PIECE) == (case == "longer_than_a_piece")
    if case == "window_past_n":     # the last group's window passes row n
        last = int(tiles.first[len(sizes) - 1])
        assert last + window_tiles(L) > -(-n // rk.LANES)
    rng = np.random.RandomState(len(sizes))
    rows = jnp.asarray(rng.randn(n).astype(np.float32))
    for fill in (-np.inf, 0.0):
        np.testing.assert_array_equal(
            rk._padded_layout(rows, tiles, L, fill),
            _slices_in(rows, tiles, L, fill))
    # what pad slots hold never reaches a row
    pad = rng.randn(Gp, L).astype(np.float32)
    pad[rng.rand(Gp, L) < 0.3] = np.nan
    pad[0, -1] = np.inf
    pad = jnp.where(jnp.arange(L)[None, :] < tiles.sizes[:, None],
                    jnp.asarray(rng.randn(Gp, L).astype(np.float32)), pad)
    np.testing.assert_array_equal(rk._rows_of(pad, tiles, n),
                                  _elements_out(pad, tiles, n))


def _rank_info(weighted, seed=5):
    rng = np.random.RandomState(seed)
    sizes = [1, 7, 130, 2, 13, 129, 128, 40]
    y = np.concatenate([rng.randint(0, 4, s) for s in sizes]).astype(
        np.float32)
    ptr = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    w = rng.rand(len(sizes)).astype(np.float32) + 0.5 if weighted else None
    return rng.randn(len(y)).astype(np.float32), _Info(y, group_ptr=ptr,
                                                       weights=w)


@pytest.mark.parametrize("method", ["topk", "mean"])
@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("unbiased", [False, True])
def test_device_gradient_equals_the_gather_forms(method, weighted, unbiased,
                                                 request, monkeypatch):
    # kpos > 0 where unbiased: the slots' bias estimates ride the pair block
    monkeypatch.delenv("XTPU_RANK_HOST", raising=False)
    s, info = _rank_info(weighted)
    params = {"lambdarank_pair_method": method, "seed": 2,
              "lambdarank_num_pair_per_sample": 4 if method == "topk" else 2,
              "lambdarank_unbiased": unbiased}
    tile = np.asarray(get_objective("rank:ndcg", dict(params))
                      .get_gradient(s, info, 3))
    request.getfixturevalue("gather_forms")
    gather = np.asarray(get_objective("rank:ndcg", dict(params))
                        .get_gradient(s, info, 3))
    assert np.abs(tile).max() > 0
    np.testing.assert_array_equal(tile, gather)


@pytest.mark.parametrize("kpos", [0, 8])
def test_device_gradient_moves_rows_by_whole_tiles(kpos):
    # no gather or scatter over the rows, of single elements or slices: the
    # scores go in by the tile kernel, both sums come out by its mirror
    import functools

    import jax

    from xgboost_tpu.objective import ranking as rk

    n, G, L = 600, 24, 40
    closed = jax.make_jaxpr(functools.partial(
        rk._lambda_grad_device, kcap=8, L=L, exp_gain=True, objective="ndcg",
        chunk=4, kpos=kpos))(*_gradient_shapes(n, G, L, kpos))
    eqns = list(_equations(closed.jaxpr))
    moves = [e for e in eqns
             if "gather" in e.primitive.name or "scatter" in e.primitive.name]
    assert not [e for e in moves if e.invars[0].aval.size >= n], moves
    kernels = [e.params["name"] for e in eqns
               if e.primitive.name == "pallas_call"]
    assert kernels == ["rank_rows_in", "rank_rows_out", "rank_rows_out"]


@pytest.mark.parametrize("method", ["topk", "mean"])
def test_two_rounds_give_the_gather_forms_model(method, request):
    X, y, qid = _make_ltr(seed=9)
    dm = xgb.DMatrix(X, label=y, qid=qid)
    params = {"objective": "rank:ndcg", "lambdarank_pair_method": method,
              "max_depth": 3, "eta": 0.3}
    tile = bytes(xgb.train(params, dm, 2, verbose_eval=False)
                 .save_raw("json"))
    request.getfixturevalue("gather_forms")
    gather = bytes(xgb.train(params, dm, 2, verbose_eval=False)
                   .save_raw("json"))
    assert tile == gather
