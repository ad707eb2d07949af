"""Good twin: dtype-discipline — bf16 STORAGE is fine; the values are
upcast to f32 before any accumulation (the fixed form of dtype_bad)."""

import jax
import jax.numpy as jnp

from tools.xtpuverify.contracts import ProgramContract
from xgboost_tpu.programs import ProgramSpec, RoundPlan, _abstract

CONTRACT = ProgramContract("fx.dtype", dispatch_budget=1)


@jax.jit
def f32_accumulate(gpair_bf16):
    # bf16 in HBM, f32 in the accumulator
    return jnp.sum(gpair_bf16.astype(jnp.float32), axis=0)


def plan():
    return RoundPlan(handle="fx.dtype", unit="pass", dispatches=[
        ProgramSpec(name="f32sum", fn=f32_accumulate,
                    args=(_abstract((512, 2), "bfloat16"),)),
    ])
