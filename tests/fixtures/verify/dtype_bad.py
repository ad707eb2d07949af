"""Bad twin: dtype-discipline — bf16 values reach an accumulate
primitive (scatter-add, the histogram-build shape). Note ``jnp.sum`` would NOT
trip this: jax upcasts reductions to an f32 accumulator itself — the
hazard is manual accumulation."""

import jax
import jax.numpy as jnp

from tools.xtpuverify.contracts import ProgramContract
from xgboost_tpu.programs import ProgramSpec, RoundPlan, _abstract

CONTRACT = ProgramContract("fx.dtype", dispatch_budget=1)


@jax.jit  # VERIFY[dtype-discipline]
def bf16_hist(bins, vals):
    # every .add lands on a bf16 bucket: mantissa loss per row
    hist = jnp.zeros((64,), jnp.bfloat16)
    return hist.at[bins].add(vals.astype(jnp.bfloat16))


def plan():
    return RoundPlan(handle="fx.dtype", unit="pass", dispatches=[
        ProgramSpec(name="bf16hist", fn=bf16_hist,
                    args=(_abstract((512,), "int32"),
                          _abstract((512,), "float32"))),
    ])
