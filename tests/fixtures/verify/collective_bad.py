"""Bad twin: collective-symmetry — a psum over an axis the contract does
not declare, and a cond whose branches issue different collective
sequences (the SPMD deadlock shape)."""

import jax
import jax.numpy as jnp
import numpy as np

from tools.xtpuverify.contracts import ProgramContract
from xgboost_tpu.programs import ProgramSpec, RoundPlan, _abstract

CONTRACT = ProgramContract("fx.collective", dispatch_budget=2,
                           mesh_axes=("data",))

P = jax.sharding.PartitionSpec


def _mesh(axis):
    return jax.sharding.Mesh(np.array(jax.devices()[:2]), (axis,))


def stray_axis_body(x):  # VERIFY[collective-symmetry]
    # "model" drifted from the contracted data mesh
    return jax.lax.psum(x, "model")


def asymmetric_cond_body(x):  # VERIFY[collective-symmetry]
    # only the true branch psums: shards deadlock if the predicate
    # ever diverges across them
    return jax.lax.cond(x[0] > 0,
                        lambda v: jax.lax.psum(v, "data"),
                        lambda v: v * 2.0, x)


def plan():
    stray = jax.jit(jax.shard_map(stray_axis_body, mesh=_mesh("model"),
                                  in_specs=P("model"), out_specs=P(),
                                  check_vma=False))
    asym = jax.jit(jax.shard_map(asymmetric_cond_body, mesh=_mesh("data"),
                                 in_specs=P("data"), out_specs=P("data"),
                                 check_vma=False))
    return RoundPlan(handle="fx.collective", unit="tree", dispatches=[
        ProgramSpec(name="stray", fn=stray,
                    args=(_abstract((8,), "float32"),),
                    src=stray_axis_body),
        ProgramSpec(name="asym", fn=asym,
                    args=(_abstract((8,), "float32"),),
                    src=asymmetric_cond_body),
    ])
