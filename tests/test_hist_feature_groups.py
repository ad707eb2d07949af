"""The histogram kernels' bodies do not grow with F.

A kernel body's per-feature steps are unrolled in Python, and tracing,
lowering and Mosaic's compile of a round program are linear in what is
unrolled: at F = 968 whole-F bodies made the round program's set-up 405 s
(``PERF.md`` section 6, PR 36). ``ops/pallas/histogram.py FEATURE_GROUP`` (G)
is the most features a body unrolls: ``build_hist_pallas`` caps its feature
block on the grid, ``fused_advance_coarse_pallas`` loops over even groups
inside the kernel. Here, in interpret mode on the CPU:

- the grouped kernels against the straight-line ones (G lifted past F: the
  bodies every kernel had before), bit for bit, at F = G - 1, G, G + 1 and
  2G + 3 around a small G and at 968 under the module's own, ``uint8`` and
  ``uint16`` with the missing slot set, with a split feature in the last
  group of the fused sweep;
- the bodies' jaxprs at F = 968 hold no more ``dot_general`` than at F = G;
- the gauge ``xtpu_hist_body_features`` reads what was unrolled.

Compiling the grouped kernels for the chip is ``tests/test_tpu_compile.py``'s.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from xgboost_tpu.obs import metrics as obs_metrics
from xgboost_tpu.ops.pallas import histogram as ph
from xgboost_tpu.ops.split import COARSE_B

G = ph.FEATURE_GROUP
MISSING = {np.uint8: 255, np.uint16: 256}


def fresh(fn, **static):
    """The wrapper's body under a jit of its own: a new function object, so
    nothing jax cached for another ``FEATURE_GROUP`` is served again."""
    return jax.jit(lambda *arrays: fn.__wrapped__(*arrays, **static))


def build_args(F, dtype, n=200, n_nodes=4, width=36, seed=0):
    rng = np.random.RandomState(seed + F)
    bins = rng.randint(0, width - 1, (F, n)).astype(dtype)
    bins[rng.rand(F, n) < 0.6] = width - 1          # the missing slot
    gpair = rng.randn(n, 2).astype(np.float32)
    gpair[:, 1] = np.abs(gpair[:, 1])
    rel = rng.randint(0, n_nodes + 1, n).astype(np.int32)
    return ((jnp.asarray(bins), jnp.asarray(gpair), jnp.asarray(rel)),
            dict(n_nodes=n_nodes, max_nbins=width))


def fused_args(F, dtype, n=200, n_prev=2, seed=0):
    missing = MISSING[dtype]
    rng = np.random.RandomState(seed + F)
    bins = rng.randint(0, missing, (F, n)).astype(dtype)
    bins[rng.rand(F, n) < 0.6] = missing
    gpair = rng.randn(n, 2).astype(np.float32)
    gpair[:, 1] = np.abs(gpair[:, 1])
    lo_prev = n_prev - 1
    positions = rng.randint(lo_prev, lo_prev + n_prev, n).astype(np.int32)
    # the splits read the last feature (in the last group, behind which the
    # padded rows lie) and the first
    feat = np.array([F - 1, 0], np.int32)
    thr = np.array([missing // 2, missing // 3], np.int32)
    dleft = np.array([True, False])
    args = tuple(jnp.asarray(a) for a in (
        bins, gpair, positions, feat, thr, dleft, np.array([True, True])))
    kw = dict(lo_prev=lo_prev, n_prev=n_prev, lo=2 * n_prev - 1,
              n_level=2 * n_prev, missing_bin=missing)
    return args, kw


def both(monkeypatch, group, fn, args, kw):
    """fn in groups of ``group`` features and with the group lifted past
    every width -> (grouped, straight)."""
    monkeypatch.setattr(ph, "FEATURE_GROUP", group)
    grouped = fresh(fn, interpret=True, **kw)(*args)
    monkeypatch.setattr(ph, "FEATURE_GROUP", 1 << 20)
    straight = fresh(fn, interpret=True, **kw)(*args)
    return grouped, straight


# (group, F, ids): every width around a small group in one-byte and two-byte
# ids (the code's paths are the same at any group size, and the interpreter
# compiles each unrolled feature for the CPU: the straight-line body of 968
# takes most of a minute), and the production line's 968 columns of two-byte
# ids under the group the module ships
CASES = [(8, F, dtype) for F in (7, 8, 9, 2 * 8 + 3)
         for dtype in (np.uint8, np.uint16)] + [(G, 968, np.uint16)]


@pytest.mark.parametrize("group,F,dtype", CASES)
def test_build_hist_int8_in_feature_blocks_is_bit_identical(monkeypatch,
                                                            group, F, dtype):
    args, kw = build_args(F, dtype)
    grouped, straight = both(monkeypatch, group, ph.build_hist_pallas, args,
                             kw)
    assert grouped.shape == (4, F, 36, 2)
    np.testing.assert_array_equal(np.asarray(grouped), np.asarray(straight))
    assert float(np.abs(np.asarray(grouped)[:, F - 1]).sum()) > 0


@pytest.mark.parametrize("group,F,dtype", CASES)
def test_fused_advance_coarse_in_feature_groups_is_bit_identical(
        monkeypatch, group, F, dtype):
    args, kw = fused_args(F, dtype)
    (pos_g, hist_g), (pos_s, hist_s) = both(
        monkeypatch, group, ph.fused_advance_coarse_pallas, args, kw)
    assert hist_g.shape == (kw["n_level"], F, COARSE_B, 2)
    np.testing.assert_array_equal(np.asarray(pos_g), np.asarray(pos_s))
    np.testing.assert_array_equal(np.asarray(hist_g), np.asarray(hist_s))
    # the rows moved below the split on the last feature, and that feature's
    # missing mass is in its coarse histogram's last slot
    assert (np.asarray(pos_g) >= kw["lo"]).all()
    assert float(np.abs(np.asarray(hist_g)[:, F - 1, COARSE_B - 1]).sum()) > 0


def boundary_args(F, dtype, n_prev, n=300):
    """A level boundary under ``n_prev`` splitting nodes: every previous
    node splits, the first on the last feature; rows above the previous
    level (stopped: on no node of either level) and a padded row tail."""
    missing = MISSING[dtype]
    rng = np.random.RandomState(F + n_prev)
    bins = rng.randint(0, missing, (F, n)).astype(dtype)
    bins[rng.rand(F, n) < 0.3] = missing
    gpair = rng.randn(n, 2).astype(np.float32)
    gpair[:, 1] = np.abs(gpair[:, 1])
    lo_prev = n_prev - 1
    positions = rng.randint(lo_prev, lo_prev + n_prev, n).astype(np.int32)
    if lo_prev:
        positions[rng.rand(n) < 0.1] = 0
    feat = np.r_[F - 1, rng.randint(0, F, n_prev - 1)].astype(np.int32)
    thr = rng.randint(0, missing, n_prev).astype(np.int32)
    args = tuple(jnp.asarray(a) for a in (
        bins, gpair, positions, feat, thr, rng.rand(n_prev) < 0.5,
        np.ones(n_prev, bool)))
    return args, dict(lo_prev=lo_prev, n_prev=n_prev, lo=2 * n_prev - 1,
                      n_level=2 * n_prev, missing_bin=missing,
                      block_rows=128)


# the boundary sweep from the first boundary to the widest its gate admits
# (64 previous nodes), at widths that are no multiple of a dot's 8 features
@pytest.mark.parametrize("dtype", [np.uint8, np.uint16])
@pytest.mark.parametrize("n_prev", [1, 4, 16, 64])
@pytest.mark.parametrize("F", [5, 28, 67])
def test_fused_sweep_stacked_dot_equals_the_dot_a_feature(monkeypatch, F,
                                                          n_prev, dtype):
    args, kw = boundary_args(F, dtype, n_prev)
    assert ph._dot_features(COARSE_B, 2 * n_prev) == 8
    pos_s, hist_s = fresh(ph.fused_advance_coarse_pallas, interpret=True,
                          **kw)(*args)
    monkeypatch.setattr(ph, "_dot_features", lambda B, N: 1)
    pos_f, hist_f = fresh(ph.fused_advance_coarse_pallas, interpret=True,
                          **kw)(*args)
    assert hist_s.shape == (2 * n_prev, F, COARSE_B, 2)
    np.testing.assert_array_equal(np.asarray(pos_s), np.asarray(pos_f))
    np.testing.assert_array_equal(np.asarray(hist_s), np.asarray(hist_f))
    assert float(np.abs(np.asarray(hist_s)[:, F - 1, COARSE_B - 1]).sum()) > 0


def test_fused_sweep_in_feature_groups_holds_whole_and_short_dots(
        monkeypatch):
    """A group of the in-kernel loop that is no multiple of a dot's features
    ends in a short dot, and holds an even number of features so that its
    rows of the accumulator start a sublane tile: 21 features are 2 groups
    of 12 (8 + 4 a group), the last three of them padding."""
    monkeypatch.setattr(ph, "FEATURE_GROUP", 11)
    args, kw = boundary_args(21, np.uint16, 2)
    assert ph._feature_groups(21) == (2, 12)
    pos_s, hist_s = fresh(ph.fused_advance_coarse_pallas, interpret=True,
                          **kw)(*args)
    monkeypatch.setattr(ph, "_dot_features", lambda B, N: 1)
    pos_f, hist_f = fresh(ph.fused_advance_coarse_pallas, interpret=True,
                          **kw)(*args)
    np.testing.assert_array_equal(np.asarray(pos_s), np.asarray(pos_f))
    np.testing.assert_array_equal(np.asarray(hist_s), np.asarray(hist_f))


def test_bench_tool_times_the_candidate_dot_forms(tmp_path):
    """``tools/bench_hist_groups.py --forms`` rehearsed: the shipped kernels
    and every candidate form (``tools/hist_dot_forms.py``) against the dot a
    feature, bit for bit, at a width that ends in a short dot."""
    import json

    from tools import bench_hist_groups

    out = tmp_path / "forms.json"
    candidates = ["stacked:dense:8", "stacked:pad:128", "held:pad:128",
                  "held:dense:32"]
    assert bench_hist_groups.main([
        "--rehearse", "--forms", ",".join(candidates), "--shapes", "11",
        "--rows", "300", "--nodes", "2", "--widths", "20,36", "--reps", "1",
        "--out", str(out)]) == 0
    rows = json.loads(out.read_text())
    compared = [r for r in rows if "equal" in r]
    assert all(r["equal"] for r in compared)
    assert ({(r["kernel"], r["form"]) for r in compared}
            == {("fused_advance_coarse", "shipped"),
                ("build_hist_int8", "shipped")}
            | {("hist_form", c) for c in candidates})
    assert {r["dot_features"] for r in compared
            if r["form"] == "shipped"} == {ph.DOT_FEATURES}


def test_packed_u4_in_feature_blocks_is_bit_identical(monkeypatch):
    """The u4 page transport past G features: whole-byte blocks on the grid,
    the nibble rows addressed inside each."""
    monkeypatch.setattr(ph, "FEATURE_GROUP", 64)
    F, n = 151, 200
    rng = np.random.RandomState(4)
    bins = rng.randint(0, 16, (F, n)).astype(np.uint8)
    even = np.concatenate([bins, np.zeros((1, n), np.uint8)])
    packed = even[0::2] | even[1::2] << 4                # [ceil(F/2), n]
    (_, gpair, rel), kw = build_args(F, np.uint8, n=n, width=16)
    got = fresh(ph.build_hist_pallas, packed_u4=F, interpret=True, **kw)(
        jnp.asarray(packed), gpair, rel)
    want = fresh(ph.build_hist_pallas, interpret=True, **kw)(
        jnp.asarray(bins), gpair, rel)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def count_dots(jaxpr) -> int:
    """``dot_general`` equations in a jaxpr and everything it calls: one a
    feature a body unrolls (a loop's body counted once, as it is traced)."""
    total = 0
    for eqn in jaxpr.eqns:
        total += eqn.primitive.name == "dot_general"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    total += count_dots(sub)
    return total


def kernel_dots(fn, args, kw) -> int:
    jaxpr = jax.make_jaxpr(fresh(fn, **kw))(*args).jaxpr
    assert "pallas_call" in str(jaxpr)
    return count_dots(jaxpr)


@pytest.mark.parametrize("kernel", ["build_hist_int8", "build_hist",
                                    "fused_advance_coarse"])
def test_a_kernel_body_holds_no_more_dots_at_968_than_at_G(kernel):
    def dots(F):
        if kernel == "fused_advance_coarse":
            return kernel_dots(ph.fused_advance_coarse_pallas,
                               *fused_args(F, np.uint16))
        args, kw = build_args(F, np.uint16)
        return kernel_dots(
            ph.build_hist_pallas, args, dict(
                kw, precision="int8x2" if kernel == "build_hist_int8"
                else "f32"))

    at_g, wide = dots(G), dots(968)
    assert 0 < wide <= at_g
    if kernel != "build_hist":         # the f32 body stages 8 features a dot
        # a dot contracts eight features' one-hots (_dot_features) at the
        # refine width of build_args and at the coarse width alike
        assert at_g == -(-G // ph.DOT_FEATURES)


def test_gauge_reads_what_the_bodies_unroll():
    registry = obs_metrics.get_registry()

    def traced(F):
        registry.set_gauge("xtpu_hist_body_features", 0)
        kernel_dots(ph.fused_advance_coarse_pallas, *fused_args(F, np.uint16))
        fused = obs_metrics.hist_body_features()
        kernel_dots(ph.build_hist_pallas, *build_args(F, np.uint16))
        return fused, obs_metrics.hist_body_features()

    assert traced(G - 3) == (G - 3, G - 3)
    assert traced(G) == (G, G)
    fused, both_kernels = traced(968)
    assert fused == ph._feature_groups(968)[1] <= G
    assert both_kernels <= G
    # the largest seen stays: a narrower kernel traced later does not lower it
    kernel_dots(ph.fused_advance_coarse_pallas, *fused_args(5, np.uint16))
    assert obs_metrics.hist_body_features() == both_kernels


@pytest.mark.parametrize("F,cap,step,block", [
    (28, 256, 8, 28),          # whole F: no padding, one block
    (220, 256, 8, 220),
    (968, 256, 8, 88),         # 11 blocks and no padding, not 4 of 248
    (968, 128, 8, 88),
    (136, 128, 8, 72),         # 2 blocks of 72: not 128 + 8, not 17 of 8
    (1000, 256, 8, 200),
    (300, 128, 64, 64),        # u4: whole tiles of bytes
])
def test_feature_block_pads_least_in_blocks_of_a_quarter_cap_or_more(
        F, cap, step, block):
    assert ph._feature_block(F, cap, step) == block


def test_assemble_two_level_by_selects_equals_the_gather(monkeypatch):
    """Past ``SELECT_TAKE_FEATURES`` columns the synthetic histogram takes
    its slots by a select-and-sum (the gather's compile time for the chip
    grows with nodes x features): one hit a slot, so every entry is the
    gathered one bit for bit, signed zeros and the missing slot included."""
    from xgboost_tpu.ops import split

    N, F = 8, 24
    rng = np.random.RandomState(36)
    hist_c = rng.randn(N, F, split.COARSE_B, 2).astype(np.float32)
    hist_r = rng.randn(N, F, split.WINDOW, 2).astype(np.float32)
    hist_c[0, 0, :4] = -0.0
    window = rng.randint(0, 15, (N, F)).astype(np.int32)
    n_real = rng.randint(1, 257, F).astype(np.int32)

    def assemble():
        fn = jax.jit(lambda c, r, w, n: split.assemble_two_level(
            c, r, w, n, True))
        return [np.asarray(a) for a in fn(hist_c, hist_r, window, n_real)]

    gathered = assemble()
    monkeypatch.setattr(split, "SELECT_TAKE_FEATURES", F - 1)
    selected = assemble()
    assert gathered[0].shape == (N, F, split.SYN_B + 1, 2)
    for a, b in zip(gathered, selected):
        np.testing.assert_array_equal(a.view(np.int32), b.view(np.int32))


def kernel_body(fn, args, kw):
    """The jaxpr of the one ``pallas_call`` body that ``fn`` traces (a
    ``window`` in ``kw`` is passed as an array)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["jaxpr"])
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (tuple, list))
                            else (value,)):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub)

    if "window" in kw:
        kw = dict(kw)
        window = kw.pop("window")
        traced = jax.jit(lambda *a: fn.__wrapped__(*a[:-1], window=a[-1],
                                                   **kw))
        args = args + (window,)
    else:
        traced = fresh(fn, **kw)
    walk(jax.make_jaxpr(traced)(*args).jaxpr)
    assert len(found) == 1
    return found[0]


def packed_hist_traces(body) -> int:
    """Distinct traces of ``_packed_hist`` the body binds: a jit binds the
    same jaxpr for every call of one signature."""
    return len({id(eqn.params["jaxpr"]) for eqn in body.eqns
                if eqn.primitive.name == "pjit"
                and eqn.params["name"] == "_packed_hist"})


@pytest.mark.parametrize("N", [1, 32])
@pytest.mark.parametrize("F", [56, 224])
def test_refine_window_adds_a_fixed_number_of_equations(F, N):
    """The refine build's in-VMEM remap (``_remap_window``) is one step on
    the whole ``[F_blk, R]`` block: its body holds the same number of
    equations more than the body without the window table at every F and
    N, and traces ``_packed_hist`` no more often. A remap a feature (or a
    feature and node) would grow with both: the tracing and lowering of a
    round program is linear in what a body unrolls."""
    from xgboost_tpu.ops.split import WINDOW

    args, kw = build_args(F, np.uint8, n_nodes=N, width=WINDOW + 4)
    window = jnp.asarray(
        np.random.RandomState(F).randint(0, 15, (N, F)).astype(np.int32))
    plain = kernel_body(ph.build_hist_pallas, args, kw)
    refine = kernel_body(ph.build_hist_pallas, args,
                         dict(kw, window=window, missing_bin=WINDOW + 3))
    assert len(refine.eqns) - len(plain.eqns) == REMAP_EQUATIONS
    assert packed_hist_traces(refine) == packed_hist_traces(plain) <= 2


# the equations ``_remap_window`` adds to a kernel body, whatever F and N
REMAP_EQUATIONS = 15
