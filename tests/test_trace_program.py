"""Names that reach a profiler trace: the round driver's host spans on the
profiler's clock, the ``xtpu.<stage>`` scopes inside the round programs,
and the compile counters by program (docs/observability.md).

The switch is the profiler session: nothing here sets an ``XTPU_*``
variable but the byte-identity test, which arms the ring to show that it
changes nothing either."""

import functools
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import xgboost_tpu as xgb
from xgboost_tpu.obs import metrics as om
from xgboost_tpu.obs import trace as tr


def _data(n=1500, f=9, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(np.float32)
    return X, y


PARAMS = {"objective": "binary:logistic", "max_depth": 3, "max_bin": 32,
          "eval_metric": "logloss"}


def _train(rounds=4, evals=False, **params):
    X, y = _data()
    kw = {}
    if evals:
        kw["evals"] = [(xgb.DMatrix(X[:300], label=y[:300]), "test")]
    return xgb.train({**PARAMS, **params}, xgb.DMatrix(X, label=y), rounds,
                     verbose_eval=False, **kw)


class _Session:
    """A ``jax.profiler`` session on CPU; ``spans()`` gives the program's
    host spans as (name, start, end, stats) in time order."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "trace")

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()

    def spans(self):
        from jax.profiler import ProfileData

        (path,) = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                         "*", "*.xplane.pb"))
        out = []
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("round", "train/")):
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
        return sorted(out, key=lambda s: (s[1], -s[2]))


def _parent(spans, child):
    """The tightest span that covers ``child`` (the enclosing span on the
    thread), or None."""
    covering = [s for s in spans if s is not child
                and s[1] <= child[1] and child[2] <= s[2]]
    return min(covering, key=lambda s: s[2] - s[1])[0] if covering else None


@pytest.mark.parametrize("evals", [False, True], ids=["batched", "evals"])
def test_profiler_session_sees_the_round_driver(tmp_path, evals):
    _train(2, evals)                     # compile outside the session
    with _Session(tmp_path) as session:
        bst = _train(4, evals)
        bst.get_dump()                   # forces the tree flush, if pending
    spans = session.spans()
    names = [s[0] for s in spans]
    parents = {s[0]: _parent(spans, s) for s in spans}
    assert names[0] == "train/call" and spans[0][3]["rounds"] == 4
    assert parents["round"] == "train/call"
    assert parents["round/guard"] == "round"
    assert "round/flush" in names
    # a fresh DMatrix: its state is made inside the first ``round``
    assert parents["train/state"] in ("round", "round/batch", "round/fused")
    for name, _s, _e, stats in spans:
        assert "iteration" in stats or name == "train/state", (name, stats)
    rounds = [s for s in spans if s[0] == "round"]
    if evals:
        assert [s[3]["step_num"] for s in rounds] == [0, 1, 2, 3]
        assert parents["round/fused"] == "round"
        assert parents["round/eval"] == "round"
        assert parents["round/eval/pull"] == "round/eval"
        assert parents["round/callbacks"] == "round"
        assert parents["round/flush"] == "round/eval"
        assert "round/batch" not in names
        assert names.count("round/eval") == 4
    else:
        assert [(s[3]["step_num"], s[3]["rounds"]) for s in rounds] == \
            [(0, 4)]
        assert parents["round/batch"] == "round"
        assert "round/fused" not in names and "round/eval" not in names


def test_general_path_and_bootstrap_have_spans(tmp_path):
    X, y = _data()
    dm = xgb.DMatrix(X, label=y)

    def fobj(margin, dtrain):            # a custom objective: unfused path
        p = 1.0 / (1.0 + np.exp(-margin))
        return p - y, p * (1.0 - p)

    bst = _train(2)
    with _Session(tmp_path) as session:
        xgb.train(PARAMS, dm, 2, obj=fobj, verbose_eval=False,
                  xgb_model=bst)
    names = [s[0] for s in session.spans()]
    assert names.count("round/general") == 2
    assert names.count("train/bootstrap") == 1      # a fresh DMatrix cache


@pytest.mark.parametrize("mode", ["session", "ring"])
def test_models_are_byte_identical_under_tracing(tmp_path, mode):
    plain = [bytes(_train(4, e).save_raw()) for e in (False, True)]
    if mode == "session":
        with _Session(tmp_path):
            traced = [bytes(_train(4, e).save_raw()) for e in (False, True)]
    else:
        tr.enable()
        try:
            traced = [bytes(_train(4, e).save_raw()) for e in (False, True)]
            assert "round/batch" in {s.name for s in tr.tracer().spans()}
        finally:
            tr.disable()
    assert traced == plain


def test_span_site_without_a_session_costs_under_20us():
    tr.disable()
    for _ in range(2000):
        with tr.span("round/fused", "train", {"iteration": 1}):
            pass
    n = 20000
    t0 = time.perf_counter()
    for i in range(n):
        with tr.span("round/fused", "train", {"iteration": i}):
            pass
    per_site = (time.perf_counter() - t0) / n
    assert per_site < 20e-6, f"{per_site * 1e6:.2f} us a span site"


def test_round_counters_count_at_the_spans_boundaries():
    reg = om.get_registry()
    keys = [("xtpu_rounds_total", ()),
            ("xtpu_round_dispatches_total",
             (("program", "_fused_multi_round_fn"),)),
            ("xtpu_round_dispatches_total",
             (("program", "_fused_round_fn"),)),
            ("xtpu_tree_flushes_total", ())]
    before = [reg.get(*k) for k in keys]
    _train(4).get_dump()
    _train(3, evals=True)
    after = [reg.get(*k) for k in keys]
    assert [a - b for a, b in zip(after, before)] == [7, 1, 3, 4]
    text = reg.render_prometheus()
    assert "# TYPE xtpu_rounds_total counter" in text
    assert 'xtpu_round_dispatches_total{program="_fused_round_fn"}' in text


# ------------------------------------------------------------ stage scopes

def _rank_data(groups=12, f=6, seed=3):
    rng = np.random.RandomState(seed)
    sizes = rng.randint(3, 40, groups)
    X = rng.randn(int(sizes.sum()), f).astype(np.float32)
    y = rng.randint(0, 4, len(X)).astype(np.float32)
    return X, y, sizes


RANK = {"objective": "rank:ndcg", "lambdarank_pair_method": "topk",
        "lambdarank_num_pair_per_sample": 8, "max_depth": 3, "eta": 0.3}


def test_ranking_rounds_have_gradient_spans_and_counters(tmp_path):
    """The general path's ``round/gradient`` beside ``round/general``, with
    the objective, its groups and the layout key's cost; ``rank/layout``
    once a dataset; the ranking counters from the group sizes alone."""
    from xgboost_tpu.obs.metrics import rank_counts

    X, y, sizes = _rank_data()
    dm = xgb.DMatrix(X, label=y, group=sizes)
    c0 = rank_counts()
    with _Session(tmp_path) as session:
        xgb.train(RANK, dm, 3, verbose_eval=False)
    spans = session.spans()
    names = [s[0] for s in spans]
    assert names.count("round/gradient") == names.count("round/general") == 3
    parents = {s[0]: _parent(spans, s) for s in spans}
    assert parents["round/gradient"] == parents["round/general"] == "round"
    grads = [s[3] for s in spans if s[0] == "round/gradient"]
    assert all(g["objective"] == "rank:ndcg" and int(g["groups"]) == 12
               for g in grads)
    assert "layout_key_ms" not in grads[0]       # no call before the first
    assert float(grads[1]["layout_key_ms"]) >= 0
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(session.dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    layouts = [e for pl in ProfileData.from_file(path).planes
               for ln in pl.lines for e in ln.events
               if e.name == "rank/layout"]
    assert len(layouts) == 1
    c1 = rank_counts()
    L, G = int(sizes.max()), len(sizes)
    m = np.minimum(8, sizes)
    kept = int(np.sum(m * (sizes - 1) - m * (m - 1) // 2))
    assert c1["dispatches"]["topk"] - c0["dispatches"].get("topk", 0) == 3
    # a round sweeps [C, K, L] blocks in rank order, K = min(8, L) anchors
    # a group, over the groups padded up to whole chunks (one here)
    assert c1["pair_slots"] - c0["pair_slots"] == 3 * G * min(8, L) * L
    assert c1["pairs_kept"] - c0["pairs_kept"] == 3 * kept
    assert c1["fill_ratio"] == pytest.approx(sizes.sum() / (G * L))
    # the scores in, both sums out: float32 rows and [G, L] slots each way
    assert c1["layout_bytes"] - c0["layout_bytes"] == \
        3 * 3 * 4 * (sizes.sum() + G * L)


RANK_SCOPE = re.compile(r"rank\.[a-z_]+")


@pytest.mark.parametrize("method", ["topk", "mean"])
def test_ranking_gradient_carries_its_root_and_parts(method):
    """Every scoped op of the gradient program starts under
    ``xtpu.gradient``, names a part of ``RANK_SCOPES`` below it, and no
    ``rank.`` scope reads as a stage."""
    from xgboost_tpu.objective import get_objective

    X, y, sizes = _rank_data()
    info = type("I", (), {})()
    info.labels, info.weights = y, None
    info.group_ptr = np.concatenate([[0], np.cumsum(sizes)])
    obj = get_objective("rank:ndcg", {"lambdarank_pair_method": method,
                                      "lambdarank_num_pair_per_sample": 2})
    lay = obj._device_layout(info, method, 2)
    from xgboost_tpu.objective import ranking as rk
    s = jnp.zeros(len(y), jnp.float32)
    args = (s, lay["y_pad"], lay["tiles"], lay["w_row"])
    kw = dict(L=lay["L"], exp_gain=True, objective="ndcg", chunk=4)
    if method == "topk":
        low = rk._lambda_grad_device.lower(*args, kcap=2, **kw)
    else:
        low = rk._lambda_grad_device_mean.lower(
            *args, jax.random.key(0), lay["y_order"], lay["n_lefts"],
            lay["n_geq"], k=2, **kw)
    text = low.compile().as_text()
    parts, heavy = set(), 0
    for line in text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if not m:
            continue
        scopes = SCOPE.findall(m.group(1))
        if m.group(1).startswith("jit("):
            assert scopes[:1] == ["xtpu.gradient"], m.group(1)
        assert set(scopes) <= {"xtpu.gradient"}, scopes
        parts.update(RANK_SCOPE.findall(m.group(1)))
        if HEAVY.search(line):
            heavy += 1
            assert RANK_SCOPE.search(m.group(1)), line.strip()[:200]
    assert parts == {"rank." + p for p in tr.RANK_SCOPES}
    assert heavy
    with pytest.raises(ValueError, match="nonsense"):
        tr.rank_scope("nonsense")


def test_general_path_programs_open_the_round_roots():
    """``_grow`` dispatched on its own (the general path) and the margin
    update pass the check the fused programs pass."""
    from xgboost_tpu.core import _add_margin_delta
    from xgboost_tpu.tree.grow import TreeGrower, _grow
    from xgboost_tpu.tree.param import TrainParam
    from xgboost_tpu.tree.programs import _NumericCuts

    n, F = 512, 5
    grower = TreeGrower(TrainParam(max_depth=3), 32, _NumericCuts(F),
                        hist_method="auto", has_missing=False)
    rng = np.random.RandomState(0)
    text = _grow.lower(
        jnp.asarray(rng.randint(0, 31, (n, F)), jnp.uint8),
        jnp.asarray(rng.randn(n, 2), jnp.float32),
        jnp.full((F,), 31, jnp.int32), jnp.ones((F,), bool),
        jax.random.key(0), None, None, None, param=grower.param,
        max_nbins=32, hist_method=grower.hist_method, axis_name=None,
        has_missing=False).compile().as_text()
    assert {"xtpu.grow", "xtpu.leaf"} <= _check_scopes(text)
    text = _add_margin_delta.lower(jnp.zeros((n, 1)),
                                   jnp.zeros((n, 1))).compile().as_text()
    assert 'xtpu.margin' in text


def test_unknown_stage_raises():
    with pytest.raises(ValueError, match="nonsense"):
        tr.stage("nonsense")
    assert set(tr.ROUND_ROOTS) <= set(tr.STAGES)
    assert tr.opened_stages() <= set(tr.STAGES)
    assert "permute" in tr.STAGES and "kernel.scan_hist" in tr.STAGES
    assert "advance_leaf" in tr.KERNELS and "kernel.advance_leaf" in tr.STAGES
    assert {"kernel." + k for k in tr.KERNELS} <= set(tr.STAGES)


SCOPE = re.compile(r"xtpu\.[A-Za-z0-9_.]+")
HEAVY = re.compile(r"= \S+ (gather|sort|custom-call)\(")


def _check_scopes(hlo_text: str):
    """Every scope in a compiled program's op names is in STAGES, and every
    gather, sort and custom call sits under one; every scoped op names
    only stages this process has opened and, where its path is whole (a
    reducer's body carries a cut one), starts with one of ROUND_ROOTS: what
    a trace reader holds a cache-served executable to. Returns the scopes
    seen."""
    seen, heavy = set(), 0
    opened = {"xtpu." + s for s in tr.opened_stages()}
    roots = {"xtpu." + s for s in tr.ROUND_ROOTS}
    for line in hlo_text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        scopes = SCOPE.findall(m.group(1)) if m else []
        seen.update(scopes)
        assert set(scopes) <= opened, scopes
        if scopes and m.group(1).startswith("jit("):
            assert scopes[0] in roots, m.group(1)
        if HEAVY.search(line):
            heavy += 1
            assert scopes, f"outside every stage: {line.strip()[:200]}"
    unknown = {s for s in seen if s[len("xtpu."):] not in tr.STAGES}
    assert not unknown, unknown
    assert heavy, "no gather, sort or custom call in the program at all"
    return seen


def _round_program_text(hist_method: str, batched: bool, **params) -> str:
    """Compiled text of the round program ``train`` runs under
    ``hist_method`` (the batched driver's, or the per-round driver's)."""
    from xgboost_tpu import core

    fn = core._fused_multi_round_fn if batched else core._fused_round_fn
    X, y = _data(n=700)
    dm = xgb.DMatrix(X, label=y)
    bst = xgb.Booster({**PARAMS, "hist_method": hist_method, **params})
    bst._configure(dm)
    state = bst._state_of(dm, is_train=True)
    obj_params, grower, labels, weights, n_real = bst._fused_binding(state)
    it = np.arange(2, dtype=np.int32) if batched else np.int32(0)
    seed = it.astype(np.uint32) if batched else bst.ctx.raw_seed(0)
    return fn.lower(
        state["binned"].bins, state["margin"], labels, weights, n_real,
        seed, it, grower.monotone, grower.constraint_sets, grower.cat,
        obj_cls=type(bst.obj), obj_params=obj_params, param=grower.param,
        max_nbins=grower.max_nbins, hist_method=grower.hist_method,
        has_missing=grower.has_missing).compile().as_text()


@pytest.mark.parametrize("hist_method, batched, want", [
    ("auto", True, {"xtpu.grow", "xtpu.gradient", "xtpu.margin",
                    "xtpu.leaf", "xtpu.eval", "xtpu.advance"}),
    ("fused", False, {"xtpu.advance_hist", "xtpu.advance", "xtpu.hist",
                      "xtpu.refine", "xtpu.window", "xtpu.delta"}),
    ("coarse", False, {"xtpu.hist", "xtpu.refine", "xtpu.advance"}),
    ("onehot", False, {"xtpu.hist", "xtpu.advance"}),
    ("pallas", False, {"xtpu.hist", "xtpu.quantise", "xtpu.fold",
                       "xtpu.kernel.build_hist_int8", "xtpu.advance"}),
])
def test_round_programs_carry_only_known_scopes(monkeypatch, hist_method,
                                                batched, want):
    if hist_method == "pallas":
        # no Mosaic on the CPU: the kernel runs interpreted, under the
        # name and the scopes the chip's carries
        from xgboost_tpu.ops.pallas import histogram as ph

        monkeypatch.setattr(ph, "build_hist_pallas", functools.partial(
            ph.build_hist_pallas, interpret=True))
    seen = _check_scopes(_round_program_text(hist_method, batched))
    assert want <= seen, want - seen


@pytest.mark.parametrize("hist_method", ["auto", "fused"])
def test_lossguide_programs_open_known_stages_only(hist_method):
    """The lossguide programs are not round programs of their own (the
    host replays the pops); ``stage`` refusing an unknown name at trace
    time is what holds their scopes to STAGES."""
    bst = _train(2, grow_policy="lossguide", max_leaves=6, max_depth=0,
                 hist_method=hist_method)
    assert bst.num_boosted_rounds() == 2


@pytest.mark.parametrize("kernel", ["build_hist_int8", "build_hist",
                                    "fused_advance_coarse", "advance_leaf"])
def test_pallas_kernels_are_named_and_scoped(kernel):
    """Interpret mode (no Mosaic on CPU): the kernel's name and its
    ``xtpu.kernel.<name>`` scope are on the traced program, with the
    quantise / fold stages around it."""
    from xgboost_tpu.ops.pallas import histogram as ph

    rng = np.random.RandomState(0)
    n, F, B, N = 256, 4, 32, 2
    bins_t = jnp.asarray(rng.randint(0, B, (F, n)), jnp.uint8)
    gpair = jnp.asarray(rng.randn(n, 2), jnp.float32)
    pos = jnp.asarray(rng.randint(0, N, n), jnp.int32)
    if kernel == "fused_advance_coarse":
        split = (jnp.zeros(1, jnp.int32), jnp.full(1, 7, jnp.int32),
                 jnp.zeros(1, bool), jnp.ones(1, bool))
        fn = jax.jit(lambda b, g, p: ph.fused_advance_coarse_pallas(
            b, g, p * 0, *split, lo_prev=0, n_prev=1, lo=1, n_level=2,
            missing_bin=B - 1, interpret=True))
        want = {"quantise", "fold"}
    elif kernel == "advance_leaf":
        # through the epilogue's dispatcher: the kernel sits under the
        # scope the walk had, so what lies around it stays partition time
        from xgboost_tpu.ops.histogram import advance_leaf

        tree = (jnp.zeros(7, jnp.int32), jnp.full(7, 7, jnp.int32),
                jnp.zeros(7, bool), jnp.zeros(7, bool).at[1:3].set(True))
        prev = {"kind": "walk", "lo": 1, "n_level": 2, "arrs": tree}
        fn = jax.jit(lambda b, g, p: advance_leaf(
            b.T, p + 1, prev, jnp.arange(7, dtype=jnp.float32), B - 1,
            bins_t=b, interpret=True)[:2])
        want = {"advance"}
    else:
        precision = "int8x2" if kernel == "build_hist_int8" else "f32"
        fn = jax.jit(lambda b, g, p: ph.build_hist_pallas(
            b, g, p, N, B, precision=precision, interpret=True))
        want = {"fold"} | ({"quantise"} if precision == "int8x2" else set())
    text = fn.lower(bins_t, gpair, pos).as_text(debug_info=True)
    scopes = set(SCOPE.findall(text))
    assert {"xtpu." + w for w in want} | {"xtpu.kernel." + kernel} <= scopes
    assert not {s for s in scopes if s[len("xtpu."):] not in tr.STAGES}
    assert _pallas_names(jax.make_jaxpr(fn)(bins_t, gpair, pos).jaxpr) == \
        [kernel]


RETIRED = {"sort", "count_sort", "permute", "kernel.scan_hist"}


def test_no_surviving_schedule_opens_a_retired_stage(monkeypatch):
    """``STAGES`` keeps four names for the benchmark's recorded PR 27
    trace alone: the grow program traced under every accepted
    ``hist_method``, as a TPU traces it (its kernels in the trace) and
    as the CPU does, opens none of them."""
    from xgboost_tpu.tree.grow import HIST_METHODS, _grow
    from xgboost_tpu.tree.param import TrainParam

    assert RETIRED <= set(tr.STAGES)
    F = 4
    for i, backend in enumerate(("cpu", "tpu")):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        # auto's row threshold, and a shape a backend: jit's trace cache
        # does not know the backend is patched
        n = (1 << 16) + 8 * i
        args = (jax.ShapeDtypeStruct((n, F), jnp.uint8),
                jax.ShapeDtypeStruct((n, 2), jnp.float32),
                jax.ShapeDtypeStruct((F,), jnp.int32),
                jax.ShapeDtypeStruct((F,), jnp.bool_), jax.random.key(0))
        for method in HIST_METHODS:
            # traced, never lowered: the CPU cannot compile Mosaic
            jax.make_jaxpr(lambda *a, m=method: _grow(
                *a, param=TrainParam(max_depth=8), max_nbins=256,
                hist_method=m, has_missing=False).delta)(*args)
    assert {"advance_hist", "kernel.fused_advance_coarse",
            "kernel.build_hist_int8", "kernel.advance_leaf"} \
        <= tr.opened_stages()
    assert not RETIRED & tr.opened_stages()


def _pallas_names(jaxpr) -> list:
    """``name=`` of every ``pallas_call`` in a jaxpr, nested ones too."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            inner = getattr(inner, "jaxpr", inner)
            if hasattr(inner, "eqns"):
                out.extend(_pallas_names(inner))
    return out


# -------------------------------------------------------- compile counters

def test_compile_counters_by_program():
    """1 for a round program trained twice at one shape, 2 after a second
    shape; the jitted helpers traced inside it (``_grow``, ``take``, ...)
    are not booked beside it, and its trace+lower seconds hold theirs."""
    def counts():
        return om.program_compile_counts()

    def delta(after, before, program, field):
        zero = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0,
                "trace_lower_s": 0.0}
        return (after.get(program, zero)[field]
                - before.get(program, zero)[field])

    prog = "_fused_multi_round_fn"
    c0 = counts()
    _train(4, max_bin=29)                # a shape no other test compiles
    c1 = counts()
    _train(4, max_bin=29)
    c2 = counts()
    assert delta(c1, c0, prog, "compiles") == 1
    assert delta(c2, c1, prog, "compiles") == 0
    assert delta(c1, c0, prog, "trace_lower_s") > 0
    assert delta(c1, c0, prog, "compile_s") > 0
    assert delta(c2, c1, prog, "trace_lower_s") == 0
    # nested traces: _grow is jitted and traced inside the round program;
    # only the outermost program is booked
    assert delta(c1, c0, "_grow", "trace_lower_s") == 0
    assert delta(c1, c0, "_grow", "compiles") == 0
    _train(4, max_bin=27)
    c3 = counts()
    assert delta(c3, c0, prog, "compiles") == 2
    assert delta(c3, c0, prog, "cache_hits") == 0
    # the same program again with jax's in-memory caches dropped: traced
    # anew and served by the persistent cache (conftest: every compile
    # lands there), which jax's own event counts as a compile too
    jax.clear_caches()
    _train(4, max_bin=27)
    c4 = counts()
    assert delta(c4, c3, prog, "compiles") == 1
    assert delta(c4, c3, prog, "cache_hits") == 1
    assert delta(c4, c3, prog, "trace_lower_s") > 0
    text = om.get_registry().render_prometheus()
    assert f'xtpu_program_compiles_total{{program="{prog}"}}' in text
    assert "# TYPE xtpu_program_trace_lower_seconds_total counter" in text


# ------------------------------------------- phases and the start-up report

def _phase_delta(fn):
    """``fn()``, and what it added to the two phase counter families:
    ({phase: self seconds}, {phase: count})."""
    def read():
        return om.phase_seconds(), om.phase_counts()
    s0, n0 = read()
    fn()
    s1, n1 = read()
    return ({k: v - s0.get(k, 0.0) for k, v in s1.items()
             if v != s0.get(k, 0.0)},
            {k: v - n0.get(k, 0) for k, v in n1.items() if v != n0.get(k, 0)})


@pytest.fixture
def unfrozen(monkeypatch):
    """The next ``train()`` is the process's first again: it freezes a new
    start-up report (the gauges are written over)."""
    monkeypatch.setattr(om, "_startup", None)


def test_nested_phases_book_self_time():
    walls = {}

    def run():
        t0 = time.perf_counter()
        with tr.phase("test/outer", "test", {"k": 1}):
            time.sleep(0.02)
            t1 = time.perf_counter()
            with tr.phase("test/inner"):
                time.sleep(0.03)
            walls["inner"] = time.perf_counter() - t1
            with tr.phase("test/inner"):
                pass
        walls["outer"] = time.perf_counter() - t0

    secs, counts = _phase_delta(run)
    assert counts == {"test/outer": 1, "test/inner": 2}
    assert 0.03 <= secs["test/inner"] <= walls["inner"]
    # the outer phase's self time: its duration less the two inside it
    assert 0.02 <= secs["test/outer"] <= walls["outer"] - secs["test/inner"]
    text = om.get_registry().render_prometheus()
    assert 'xtpu_phase_seconds_total{phase="test/outer"}' in text
    assert "# TYPE xtpu_phase_total counter" in text


def test_import_and_before_import_are_booked_once():
    secs, counts = _phase_delta(lambda: om.book_import(time.perf_counter()))
    assert not secs and not counts           # the package's own call stands
    booked = om.phase_seconds()
    assert booked["import"] > 0
    if om._process_age() is not None:        # left out where /proc is not
        assert 0 < booked["before_import"] < om._process_age()


def test_startup_report_parts_add_up_to_total(unfrozen):
    _train(4)
    report = om.startup_report()
    assert {"import", "caller", "unattributed", "round", "train/state",
            "ingest/sketch", "ingest/bin", "total"} <= set(report)
    assert not set(om.PHASE_CONTAINERS) & set(report)
    parts = sum(v for k, v in report.items() if k != "total")
    assert abs(parts - report["total"]) < 1e-3, (parts, report)
    assert all(v >= 0 for v in report.values()), report
    age = om._process_age()
    if age is not None:
        assert 0 <= age - report["total"] < 5.0   # frozen moments ago


def test_startup_gauges_are_written_once(unfrozen):
    assert om.startup_report() is None
    _train(4)
    first = om.startup_report()
    reg = om.get_registry()

    def gauges():
        return {name: reg.get("xtpu_startup_seconds", (("phase", name),))
                for name in first}

    assert gauges() == first
    _train(4, max_bin=31)                 # compiles: the counters move on
    assert om.startup_report() == first and gauges() == first
    assert om.phase_seconds()["round"] > first["round"]
    assert 'xtpu_startup_seconds{phase="total"}' in reg.render_prometheus()


def test_startup_line_at_verbosity_2(unfrozen, capsys):
    with xgb.config_context(verbosity=2):
        _train(2)
        _train(2)
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("start-up: ")]
    assert len(lines) == 1 and "total " in lines[0] and "round " in lines[0]


def test_startup_is_silent_at_the_default_verbosity(unfrozen, capsys):
    _train(2)
    assert "start-up" not in capsys.readouterr().out
    assert om.startup_report() is not None


def test_phase_on_a_worker_thread_is_not_booked():
    import threading

    tr.enable()
    try:
        def work():
            with tr.phase("test/worker", "test"):
                time.sleep(0.001)

        def run():
            t = threading.Thread(target=work)
            t.start()
            t.join()

        secs, counts = _phase_delta(run)
        names = [s.name for s in tr.tracer().spans()]
    finally:
        tr.disable()
    assert not secs and not counts
    assert "test/worker" in names            # a plain span it stays


def test_first_train_on_a_worker_thread_freezes_nothing(unfrozen):
    import threading

    t = threading.Thread(target=_train, args=(2,))
    t.start()
    t.join()
    assert om.startup_report() is None


def test_trace_lower_comes_off_the_round_it_ran_in():
    X, y = _data()
    dm = xgb.DMatrix(X, label=y)         # its constructor is no round's
    tr.enable()
    try:
        # a shape no other test compiles: traced, lowered and compiled
        # inside the first ``round``
        secs, counts = _phase_delta(lambda: xgb.train(
            {**PARAMS, "max_bin": 23}, dm, 4, verbose_eval=False))
        spans = tr.tracer().spans()
    finally:
        tr.disable()
    rounds = [s for s in spans if s.name == "round"]
    assert len(rounds) == 1 and counts["round"] == 1
    traced = [s for s in spans if s.name == "program/trace_lower"
              and s.args["program"] == "_fused_multi_round_fn"]
    assert len(traced) == 2                  # the trace, then the lowering
    for s in traced:
        assert rounds[0].t0 <= s.t0 and s.t1 <= rounds[0].t1 + 1e-3
    compiled = [s for s in spans if s.name == "program/compile"
                and s.args["program"] == "_fused_multi_round_fn"]
    assert [s.args["cache_hit"] for s in compiled] == [0]
    assert secs["program/trace_lower"] >= sum(s.dur for s in traced) - 1e-6
    # the round's self time is what is left of it
    inside = sum(v for k, v in secs.items()
                 if k not in ("round", "train/call"))
    assert secs["round"] > 0
    assert secs["round"] <= rounds[0].dur - secs["program/trace_lower"]
    assert abs(secs["round"] + inside - rounds[0].dur) < 5e-3, (secs,
                                                               rounds[0].dur)
    # and the program counters read the same seconds as before
    assert om.program_compile_counts()["_fused_multi_round_fn"][
        "trace_lower_s"] > 0


def _ingest_spans(build):
    tr.enable()
    try:
        secs, counts = _phase_delta(build)
        spans = [s for s in tr.tracer().spans()
                 if s.name.startswith("ingest")]
    finally:
        tr.disable()
    return secs, counts, spans


@pytest.mark.parametrize("path", ["memory", "iterator"])
def test_ingest_paths_open_their_phases(path):
    from xgboost_tpu.data import quantile
    from xgboost_tpu.testing import IteratorForTest

    X, y = _data(n=1200)

    def build():
        if path == "memory":
            xgb.DMatrix(X, label=y).binned(32)
        else:
            xgb.QuantileDMatrix(IteratorForTest(
                [X[:600], X[600:]], [y[:600], y[600:]]), max_bin=32)._binned

    secs, counts, spans = _ingest_spans(build)
    by_name = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s)
    sketch, = by_name["ingest/sketch"]
    assert sketch.args == {"max_bin": 32,
                           "sample_rows": quantile.SKETCH_SAMPLE_ROWS}
    binning, = by_name["ingest/bin"]
    assert binning.args == {"rows": 1200, "nan": 0, "dtype": "uint8",
                            "batches": 1 if path == "memory" else 2}
    upload, = by_name["ingest/upload"]
    assert upload.args == {"rows": 1200, "shards": 1}
    assert counts["ingest/sketch"] == counts["ingest/bin"] == 1
    assert counts["ingest/upload"] == 1
    if path == "memory":
        assert "ingest/next" not in by_name
        assert counts["ingest"] == 2         # the constructor, binned()
    else:
        # two passes: two batches and the end of the stream in each
        assert counts["ingest/next"] == len(by_name["ingest/next"]) == 6
        assert counts["ingest"] == 1
    # the container holds them all, and books only what they leave (an
    # iterator's bins go up when first asked for, outside any container)
    whole = sum(s.dur for s in by_name["ingest"]) + (
        upload.dur if path == "iterator" else 0.0)
    assert abs(sum(secs.values()) - whole) < 5e-3, (secs, whole)
    assert secs["ingest"] < whole


def test_native_build_is_a_phase(monkeypatch):
    from xgboost_tpu import native

    assert native.load() is not None
    built = []
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_cached_digest", lambda _path: None)
    monkeypatch.setattr(native, "_build",
                        lambda path, digest: built.append(path))
    secs, counts = _phase_delta(native.load)
    assert len(built) == 1 and counts == {"native/build": 1}
    secs, counts = _phase_delta(native.load)  # loaded: nothing to build
    assert not counts


def test_phase_site_costs_under_5us():
    tr.disable()
    for _ in range(2000):
        with tr.phase("test/cost", "test", {"iteration": 1}):
            pass
    n, best = 5000, float("inf")
    for _ in range(12):                  # the best of twelve: a shared host
        t0 = time.perf_counter()
        for i in range(n):
            with tr.phase("test/cost", "test", {"iteration": i}):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 5e-6, f"{best * 1e6:.2f} us a phase site"
