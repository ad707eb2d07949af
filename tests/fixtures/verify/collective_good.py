"""Good twin: collective-symmetry — collectives only over the contracted
data axis, and both cond branches issue the identical collective
sequence (the zero-contribution reduction idiom)."""

import jax
import jax.numpy as jnp
import numpy as np

from tools.xtpuverify.contracts import ProgramContract
from xgboost_tpu.programs import ProgramSpec, RoundPlan, _abstract

CONTRACT = ProgramContract("fx.collective", dispatch_budget=1,
                           mesh_axes=("data",))

P = jax.sharding.PartitionSpec


def symmetric_body(x):
    # every branch psums exactly once over the data axis: the false
    # branch reduces a zero contribution instead of skipping the
    # collective
    return jax.lax.cond(x[0] > 0,
                        lambda v: jax.lax.psum(v, "data"),
                        lambda v: jax.lax.psum(v * 0.0, "data"), x)


def plan():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
    fn = jax.jit(jax.shard_map(symmetric_body, mesh=mesh,
                               in_specs=P("data"), out_specs=P(),
                               check_vma=False))
    return RoundPlan(handle="fx.collective", unit="tree", dispatches=[
        ProgramSpec(name="sym", fn=fn,
                    args=(_abstract((8,), "float32"),),
                    src=symmetric_body),
    ])
