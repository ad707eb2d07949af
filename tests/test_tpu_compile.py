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
