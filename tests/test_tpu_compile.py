"""The row-split mesh grow program compiles for the chip, with no chip.

The TPU's compiler is installed here and compiles for a described v5e
2x2 (``/opt/skills/guides/on-chip-measurement`` section 2). CPU runs
never promote ``auto`` and never hold a Mosaic kernel, so this is the one
tier-1 check of what a four-chip ``xgb.train(mesh=...)`` traces since
``auto`` is the fused sweep: ``shard_map`` keeps ``check_vma`` on there,
and ``pallas_call`` refuses an ``out_shape`` that does not say over which
mesh axes it varies (``ops/pallas/histogram.py _out_struct``). A compile
that passes is not a chip run. All such tests stay in this one file: the
process that describes the topology holds the TPU library's lock."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from xgboost_tpu.context import DATA_AXIS
from xgboost_tpu.obs.metrics import (grow_epilogue_counts,
                                     grow_schedule_counts, hist_dot_counts)
from xgboost_tpu.tree.grow import AUTO_COARSE_MIN_ROWS, TreeGrower
from xgboost_tpu.tree.param import TrainParam
from xgboost_tpu.tree.programs import _NumericCuts

MAX_NBINS = 256


@pytest.fixture(scope="module")
def mesh():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable cannot be read back from the cache without a chip
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield Mesh(np.array(topo.devices).reshape(4), (DATA_AXIS,))
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("method,schedule,depth,kernels,epilogue,FEATURES", [
    # advance+coarse and refine, a level
    ("auto", "fused", 3, 2 * 3, "dense", 28),
    # the published depth: a last level of 128 nodes, past DENSE_LEVEL_MAX,
    # takes the advance_leaf kernel below it
    ("auto", "fused", 8, 2 * 8 + 1, "kernel", 28),
    # 256 and 512 nodes, the widest levels advance_leaf's gate admits; the
    # levels past 128 nodes build their histograms in XLA
    ("auto", "fused", 9, 2 * 8 + 1, "kernel", 28),
    ("auto", "fused", 10, 2 * 8 + 1, "kernel", 28),
    # the click-log job's width (benchmark cell criteo-ctr.mesh-train): 67
    # features, no multiple of the sublane's 8, at the published depth
    ("auto", "fused", 8, 2 * 8 + 1, "kernel", 67),
    # the ranking job's width and depth (istella-letor.train): 55 stacked
    # dots of four features a coarse body, a (feature block, row block)
    # grid for the refine build, every level inside DENSE_LEVEL_MAX
    ("auto", "fused", 6, 2 * 6, "dense", 220),
])
def test_row_split_grow_program_compiles_for_v5e(monkeypatch, mesh, method,
                                                 schedule, depth, kernels,
                                                 epilogue, FEATURES):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = 4 * AUTO_COARSE_MIN_ROWS      # each shard at auto's threshold
    grower = TreeGrower(TrainParam(max_depth=depth), MAX_NBINS,
                        _NumericCuts(FEATURES), hist_method=method,
                        mesh=mesh, has_missing=False)

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    before = grow_schedule_counts().get(schedule, 0)
    before_epilogue = grow_epilogue_counts().get(epilogue, 0)
    dots = hist_dot_counts()
    compiled = grower.sharded_program().lower(
        arg((rows, FEATURES), jnp.uint8, P(DATA_AXIS, None)),
        arg((rows, 2), jnp.float32, P(DATA_AXIS, None)),
        arg((FEATURES,), jnp.int32, P()), arg((FEATURES,), jnp.bool_, P()),
        arg((2,), jnp.uint32, P()),
        None, None, None).compile()     # monotone, constraint sets, cat
    assert grow_schedule_counts().get(schedule, 0) == before + 1
    assert grow_epilogue_counts().get(epilogue, 0) == before_epilogue + 1
    # every histogram kernel of the two-level search (20 and 36 slots)
    # contracts a group of features a dot, none a feature
    assert hist_dot_counts().get("feature", 0) == dots.get("feature", 0)
    if (depth, FEATURES) in ((3, 28), (8, 67), (6, 220)):   # new shapes
        assert hist_dot_counts()["stacked"] > dots.get("stacked", 0)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= kernels
    assert ("xtpu.kernel.advance_leaf" in text) == (epilogue == "kernel")
    assert "all-reduce" in text          # the histogram psum
    # the collectives carry their mesh.* scopes into the compiled program
    for scope in ("mesh.hist_psum", "mesh.root_psum", "mesh.scale_pmax"):
        assert scope in text


# the held-out rows of the two configurations, and the deepest tree the
# walk's gate admits
@pytest.mark.parametrize("rows,features,bins_dtype,depth,kernels", [
    (500_000, 28, jnp.uint8, 8, 1),      # HIGGS: a last level of 128 nodes
    (3_129_004, 220, jnp.uint8, 6, 1),   # the Istella test split: last level
    (500_000, 28, jnp.uint16, 10, 3),    # 128, 256 and 512 nodes
])
def test_heap_walk_program_compiles_for_v5e(mesh, rows, features, bins_dtype,
                                            depth, kernels):
    """The eval walk from the device heap (``boosting/gbtree.py
    _heap_margin_delta``) for one described chip: Mosaic takes the
    ``advance_leaf`` kernel at the last level's width, and no op of the
    program gathers over the rows."""
    from jax.sharding import SingleDeviceSharding

    from xgboost_tpu.boosting.gbtree import _heap_margin_delta

    one = SingleDeviceSharding(mesh.devices.flat[0])
    nodes = 2 ** (depth + 1) - 1

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    heap = {"split_feature": arg((nodes,), jnp.int32),
            "split_bin": arg((nodes,), jnp.int32),
            "default_left": arg((nodes,), jnp.bool_),
            "is_leaf": arg((nodes,), jnp.bool_),
            "leaf_value": arg((nodes,), jnp.float32)}
    text = _heap_margin_delta.lower(
        (heap,), arg((rows, features), bins_dtype), missing_bin=256,
        max_depth=depth).compile().as_text()
    assert text.count("tpu_custom_call") >= kernels
    assert "xtpu.margin" in text and "xtpu.kernel.advance_leaf" in text
    row_gathers = [line for line in text.splitlines()
                   if " gather(" in line and str(rows) in line]
    assert not row_gathers


# the production-line job's width (benchmark cell bosch-line.sparse-train):
# 968 columns of two-byte ids with the missing slot at 256
@pytest.mark.parametrize("kernel,nodes,width", [
    # the widest boundary fused_advance_coarse's gate admits at this width
    # (F * n_level <= 52,428): the whole-F tile, its int32 copy, the coarse
    # accumulator and the group loop's operands, past the default 16 MiB of
    # scoped VMEM (the wrapper states its own limit)
    ("fused_advance_coarse", 32, 20),
    ("fused_advance_coarse", 2, 20),
    # the root's coarse build and the widest level's refine build, in
    # feature blocks on the grid
    ("build_hist_int8", 1, 20),
    ("build_hist_int8", 128, 36),
    # the last level's advance below 128 nodes
    ("advance_leaf", 128, 0),
])
def test_wide_missing_kernels_compile_for_v5e(mesh, kernel, nodes, width):
    """Mosaic takes ``_grow``'s kernels at F = 968 with ``uint16`` bins, in
    feature groups: no VMEM refusal at the widest tiles the default path
    hands them, and no body longer than ``FEATURE_GROUP`` features."""
    from jax.sharding import SingleDeviceSharding

    from xgboost_tpu.obs.metrics import get_registry, hist_body_features
    from xgboost_tpu.ops.pallas import histogram as ph

    one = SingleDeviceSharding(mesh.devices.flat[0])
    n, F = 4096, 968

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    get_registry().set_gauge("xtpu_hist_body_features", 0)
    dots = hist_dot_counts()
    if kernel == "fused_advance_coarse":
        prev = nodes // 2
        fn = jax.jit(lambda b, g, p, f, t, d, c: ph.fused_advance_coarse_pallas(
            b, g, p, f, t, d, c, lo_prev=prev - 1, n_prev=prev, lo=nodes - 1,
            n_level=nodes, missing_bin=256))
        args = (arg((F, n), jnp.uint16), arg((n, 2), jnp.float32),
                arg((n,), jnp.int32), arg((prev,), jnp.int32),
                arg((prev,), jnp.int32), arg((prev,), jnp.bool_),
                arg((prev,), jnp.bool_))
    elif kernel == "build_hist_int8":
        fn = jax.jit(lambda b, g, p: ph.build_hist_pallas(b, g, p, nodes,
                                                          width))
        args = (arg((F, n), jnp.uint8), arg((n, 2), jnp.float32),
                arg((n,), jnp.int32))
    else:
        fn = jax.jit(lambda b, p, f, t, d, c, v: ph.advance_leaf_pallas(
            b, p, f, t, d, c, v, n_prev=nodes, missing_bin=256))
        args = (arg((F, n), jnp.uint16), arg((n,), jnp.int32),
                arg((nodes,), jnp.int32), arg((nodes,), jnp.int32),
                arg((nodes,), jnp.bool_), arg((nodes,), jnp.bool_),
                arg((4 * nodes - 1,), jnp.float32))
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and f"xtpu.kernel.{kernel}" in text
    if kernel != "advance_leaf":
        assert 0 < hist_body_features() <= ph.FEATURE_GROUP
        assert hist_dot_counts() == {**dots,
                                     "stacked": dots.get("stacked", 0) + 1}


# the PASCAL epsilon job's width (benchmark cell pascal-epsilon.wide-train):
# 2,000 dense columns of one-byte ids. The refine build at 128 nodes and 36
# slots (200 features a block) and the last level's advance over the whole-F
# tile need more than the default scoped VMEM (20.44 and 24.75 MiB), and
# state their own limit
@pytest.mark.parametrize("kernel,nodes,width", [
    ("build_hist_int8", 128, 36),
    ("advance_leaf", 128, 0),
    ("advance_leaf", 256, 0),
])
def test_wide_dense_kernels_compile_for_v5e(mesh, kernel, nodes, width):
    from jax.sharding import SingleDeviceSharding

    from xgboost_tpu.obs.metrics import get_registry, kernel_vmem_bytes
    from xgboost_tpu.ops.pallas import histogram as ph

    one = SingleDeviceSharding(mesh.devices.flat[0])
    n, F = 4096, 2000

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    get_registry().set_gauge("xtpu_kernel_vmem_bytes", 0,
                             (("kernel", kernel),))
    if kernel == "build_hist_int8":
        fn = jax.jit(lambda b, g, p: ph.build_hist_pallas(b, g, p, nodes,
                                                          width))
        args = (arg((F, n), jnp.uint8), arg((n, 2), jnp.float32),
                arg((n,), jnp.int32))
    else:
        fn = jax.jit(lambda b, p, f, t, d, c, v: ph.advance_leaf_pallas(
            b, p, f, t, d, c, v, n_prev=nodes, missing_bin=256))
        args = (arg((F, n), jnp.uint8), arg((n,), jnp.int32),
                arg((nodes,), jnp.int32), arg((nodes,), jnp.int32),
                arg((nodes,), jnp.bool_), arg((nodes,), jnp.bool_),
                arg((4 * nodes - 1,), jnp.float32))
    text = fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and f"xtpu.kernel.{kernel}" in text
    assert kernel_vmem_bytes()[kernel] > ph.SCOPED_VMEM


def test_wide_dense_grow_program_compiles_for_v5e(monkeypatch, mesh):
    """The one-device grow program at the epsilon job's shape (400,000 x
    2,000 one-byte ids, depth 8) for one described chip: the boundary sweep
    on the kernel into 2-16 nodes and on its XLA body into 32, 64 and 128
    (past the coarse accumulator's 8 MiB gate), ``advance_leaf`` below the
    last level, and every wide kernel under the limit it states."""
    from jax.sharding import SingleDeviceSharding

    from xgboost_tpu.obs.metrics import (fused_boundary_counts,
                                         hist_body_features,
                                         kernel_vmem_bytes)
    from xgboost_tpu.ops.pallas import histogram as ph
    from xgboost_tpu.tree.grow import _grow

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(mesh.devices.flat[0])
    rows, F = 400_000, 2000

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    boundaries = fused_boundary_counts()
    before_epilogue = grow_epilogue_counts().get("kernel", 0)
    text = _grow.lower(
        arg((rows, F), jnp.uint8), arg((rows, 2), jnp.float32),
        arg((F,), jnp.int32), arg((F,), jnp.bool_), arg((2,), jnp.uint32),
        param=TrainParam(max_depth=8), max_nbins=MAX_NBINS,
        has_missing=False).compile().as_text()
    assert fused_boundary_counts() == {
        "kernel": boundaries.get("kernel", 0) + 4,
        "xla": boundaries.get("xla", 0) + 3}
    assert grow_epilogue_counts().get("kernel", 0) == before_epilogue + 1
    # the root's coarse build, 8 refine builds, 4 sweeps, the 3 XLA
    # boundaries' coarse builds and advance_leaf
    assert text.count('custom_call_target="tpu_custom_call"') >= 2 * 8 + 1
    assert "xtpu.kernel.advance_leaf" in text
    assert 0 < hist_body_features() <= ph.FEATURE_GROUP
    vmem = kernel_vmem_bytes()
    for kernel in ("build_hist_int8", "fused_advance_coarse", "advance_leaf"):
        assert vmem[kernel] > ph.SCOPED_VMEM, vmem


# the numeric twin of the airline shape needs 889,671,680 B of temporaries
# for this program; with three categorical columns it may take half again
CAT_TEMPS_MAX = 1_334_507_520


def test_categorical_grow_program_compiles_for_v5e(monkeypatch, mesh):
    """The one-device grow program at the airline on-time job's shape
    (20,000,000 x 13 two-byte ids, Origin and Dest as 351 categories and
    UniqueCarrier as 30, 352 slots with the missing one, depth 8) for one
    described chip: ``auto`` resolves to ``fused``, the categorical
    histograms ride the refine sweep, the bitmask advance runs in the
    boundary sweeps and in ``advance_leaf``, the temporaries stay within
    half again the numeric twin's, and every kernel compiles under the
    VMEM limit it states."""
    from jax.sharding import SingleDeviceSharding

    from xgboost_tpu.obs.metrics import (fused_boundary_counts,
                                         kernel_vmem_bytes)
    from xgboost_tpu.ops.split import CatInfo
    from xgboost_tpu.tree.grow import _grow

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(mesh.devices.flat[0])
    rows, F, cols = 20_000_000, 13, (6, 9, 10)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    cat = CatInfo(is_cat=arg((F,), jnp.bool_), is_onehot=arg((F,), jnp.bool_),
                  cols=cols, numeric_bins=256)
    before = grow_schedule_counts().get("fused", 0)
    boundaries = fused_boundary_counts().get("kernel", 0)
    before_epilogue = grow_epilogue_counts().get("kernel", 0)
    compiled = _grow.lower(
        arg((rows, F), jnp.uint16), arg((rows, 2), jnp.float32),
        arg((F,), jnp.int32), arg((F,), jnp.bool_), arg((2,), jnp.uint32),
        None, None, cat, param=TrainParam(max_depth=8), max_nbins=352,
        has_missing=True).compile()
    assert grow_schedule_counts().get("fused", 0) == before + 1
    assert fused_boundary_counts().get("kernel", 0) == boundaries + 7
    assert grow_epilogue_counts().get("kernel", 0) == before_epilogue + 1
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps <= CAT_TEMPS_MAX, temps
    text = compiled.as_text()
    # the root's coarse build, 8 refine builds, 7 sweeps and advance_leaf
    assert text.count('custom_call_target="tpu_custom_call"') >= 2 * 8 + 1
    vmem = kernel_vmem_bytes()
    for kernel in ("build_hist_int8", "fused_advance_coarse", "advance_leaf"):
        assert vmem[kernel] <= 64 * 2 ** 20, vmem


# the ranking job's layout (istella-letor.train: 7,325,625 rows in 23,219
# groups of at most 1,024, 23,552 padded, truncation 32), a ragged one: a
# longest group of no whole number of tiles, groups of no whole step, and
# one whose longest group of 32,768 rows the kernels move as pieces of
# 4,096 slots (as one window a group a step would hold 17 MB of VMEM)
@pytest.mark.parametrize("rows,groups,L,kcap,chunk", [
    (7_325_625, 23_552, 1024, 32, 512),
    (100_003, 336, 1000, 0, 16),
    (300_001, 64, 32_768, 32, 16),
])
def test_ranking_gradient_compiles_for_v5e(monkeypatch, mesh, rows, groups,
                                           L, kcap, chunk):
    """The ranking gradient for one described chip: Mosaic takes the two
    layout kernels (``ops/pallas/rank_layout.py``), under the gradient's
    ``rank.layout`` and ``rank.reduce`` scopes, and no op gathers over the
    rows."""
    import functools

    from jax.sharding import SingleDeviceSharding

    from xgboost_tpu.objective import ranking as rk

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(mesh.devices.flat[0])

    def arg(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    text = jax.jit(functools.partial(
        rk._lambda_grad_device, kcap=kcap, L=L, exp_gain=True,
        objective="ndcg", chunk=chunk)).lower(
            arg((rows,)), arg((groups, L)),
            rk._Tiles(*[arg((groups,), jnp.int32)] * 3),
            arg((rows,))).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3
    assert sum("rank.layout" in c for c in calls) == 1
    assert sum("rank.reduce" in c for c in calls) == 2
    row_gathers = [line for line in text.splitlines()
                   if " gather(" in line and str(rows) in line]
    assert not row_gathers


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs it calls."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


# the ranking job's width and depth and the HIGGS job's: few rows (tracing
# does not depend on them), no multiple of a row block
@pytest.mark.parametrize("features,depth", [(220, 6), (28, 8)])
def test_refine_builds_remap_in_the_kernel_for_v5e(monkeypatch, mesh,
                                                   features, depth):
    """The one-device grow program's refine builds read the raw tile for
    one described chip: a kernel remap a refined level and none in XLA
    (``xtpu_refine_remap_total``), the ``[F, n]`` tile padded to the row
    blocks once in the program, and the refine kernel under the scoped
    VMEM ``_int8_vmem_bytes`` states for it."""
    from jax.sharding import SingleDeviceSharding

    from xgboost_tpu.obs.metrics import (get_registry, kernel_vmem_bytes,
                                         refine_remap_counts)
    from xgboost_tpu.ops.pallas import histogram as ph
    from xgboost_tpu.ops.split import WINDOW
    from xgboost_tpu.tree.grow import _grow

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    one = SingleDeviceSharding(mesh.devices.flat[0])
    rows = AUTO_COARSE_MIN_ROWS + 5 + features   # a shape traced afresh
    R, n_pad = ph.row_blocks(rows)

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    get_registry().set_gauge("xtpu_kernel_vmem_bytes", 0,
                             (("kernel", "build_hist_int8"),))
    before = refine_remap_counts()
    traced = _grow.trace(
        arg((rows, features), jnp.uint8), arg((rows, 2), jnp.float32),
        arg((features,), jnp.int32), arg((features,), jnp.bool_),
        arg((2,), jnp.uint32), param=TrainParam(max_depth=depth),
        max_nbins=MAX_NBINS, has_missing=False)
    after = refine_remap_counts()
    assert after.get("kernel", 0) == before.get("kernel", 0) + depth
    assert after.get("xla", 0) == before.get("xla", 0)
    tile_pads = [eqn for eqn in _eqns(traced.jaxpr.jaxpr)
                 if eqn.primitive.name == "pad"
                 and eqn.outvars[0].aval.shape == (features, n_pad)]
    assert len(tile_pads) == 1
    text = traced.lower().compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') >= 2 * depth

    def stated(B, N, refine):
        # what build_hist_pallas states for a build of N nodes and B slots
        cap = min(max((8 * 2 ** 20) // (B * 2 * N * 4), 8), ph.FEATURE_GROUP)
        F_blk = ph._feature_block(features, cap)
        vmem = ph._int8_vmem_bytes(F_blk, ph._round_up(features, F_blk), B,
                                   N, R, F_blk, 1, True, refine)
        return ph.SCOPED_VMEM if vmem <= ph.SCOPED_VMEM else (
            vmem + ph.SCOPED_VMEM)

    # the root's coarse build and every level's refine build
    assert kernel_vmem_bytes()["build_hist_int8"] == max(
        [stated(20, 1, False)]
        + [stated(WINDOW + 4, 2 ** d, True) for d in range(depth)])
