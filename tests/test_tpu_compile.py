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
from xgboost_tpu.obs.metrics import grow_epilogue_counts, grow_schedule_counts
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
    compiled = grower.sharded_program().lower(
        arg((rows, FEATURES), jnp.uint8, P(DATA_AXIS, None)),
        arg((rows, 2), jnp.float32, P(DATA_AXIS, None)),
        arg((FEATURES,), jnp.int32, P()), arg((FEATURES,), jnp.bool_, P()),
        arg((2,), jnp.uint32, P()),
        None, None, None).compile()     # monotone, constraint sets, cat
    assert grow_schedule_counts().get(schedule, 0) == before + 1
    assert grow_epilogue_counts().get(epilogue, 0) == before_epilogue + 1
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
