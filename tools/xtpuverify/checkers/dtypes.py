"""dtype-discipline: no f64/c128 anywhere in a traced program, and bf16
never reaches an accumulate primitive.

A bf16 value arriving at add/scatter-add/reduce_sum is an unreviewed
precision loss — exactly the class of bug that shows up as a 1e-2 AUC
wobble three PRs later. No contract is exempt.
"""

from __future__ import annotations

from typing import Iterator

from ..engine import CheckContext, Finding, iter_eqns

# Primitives that accumulate: feeding bf16 into these loses mantissa on
# every step. Movement/conversion prims (reshape, convert_element_type,
# broadcast) are fine — bf16 storage is allowed, bf16 *summation* is not.
ACCUM_PRIMS = {
    "add", "add_any", "scatter-add", "reduce_sum", "dot_general",
    "cumsum", "cumlogsumexp",
}

WIDE_DTYPES = {"float64", "complex128"}


def _avals(eqn):
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            yield aval


def check_dtypes(ctx: CheckContext) -> Iterator[Finding]:
    for tp in ctx.programs:
        seen = set()
        for eqn in iter_eqns(tp.jaxpr):
            prim = eqn.primitive.name
            for aval in _avals(eqn):
                name = aval.dtype.name
                if name in WIDE_DTYPES and ("wide", name) not in seen:
                    seen.add(("wide", name))
                    yield ctx.finding(
                        "dtype-discipline",
                        f"{name} value in the program (first at `{prim}`)"
                        " — an x64 leak into a compiled hot path",
                        detail=f"{name} in program",
                        spec=tp.spec,
                        hint="pin the input dtype or cast at the program "
                             "boundary; jax x64 mode must not reach "
                             "compiled tiers")
                if (name == "bfloat16"
                        and prim in ACCUM_PRIMS
                        and ("bf16", prim) not in seen):
                    seen.add(("bf16", prim))
                    yield ctx.finding(
                        "dtype-discipline",
                        f"bf16 reaches accumulate primitive `{prim}`",
                        detail=f"bf16 at {prim}",
                        spec=tp.spec,
                        hint="accumulate in f32 (upcast before the sum)")
