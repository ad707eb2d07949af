"""The program-contract table — contracts are DATA, not code.

One :class:`ProgramContract` per execution tier, checked by
``tools/xtpuverify/engine.py`` against the traced plan the library
exports for that tier (``xgboost_tpu/programs.py``). The ROADMAP item-4
schedule IR is expected to emit entries in this format per generated
driver (:func:`contract_from_dict` is the hook), so a generated schedule
ships with its own verification row instead of hand-written tests.

Fields:

- ``dispatch_budget``: max distinct compiled programs per steady
  scheduling unit (the plan's ``unit``: round / tree / level / batch).
  The canonical entry: resident rounds are exactly
  [fused_round, margin_bad_rows] — budget 2.
- ``uploads_per_level``: paged tiers only — host->device page transfers
  per steady level (0: the all-cached page-major path re-reads HBM).
- ``max_carry_kb``: byte bound on any single loop carry AT THE HANDLE'S
  TRACE SHAPES (a structural-blowup tripwire, e.g. a whole histogram
  stack riding in a fori_loop carry — not a production HBM estimate).
- ``mesh_axes``: axis names collectives may reference; empty means the
  tier's programs must contain NO collectives.
- ``donated``: the tier declares buffer donation and the verifier must
  see it materialize as input-output aliasing in the lowering.
- ``max_const_bytes``: largest literal that may be baked into the traced
  jaxprs (bigger = recompile hazard + duplicated HBM on every variant).
"""

from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Optional, Tuple


@dataclass(frozen=True)
class ProgramContract:
    handle: str
    dispatch_budget: int
    max_carry_kb: float = 1024.0
    mesh_axes: Tuple[str, ...] = ()
    donated: bool = False
    uploads_per_level: Optional[int] = None
    max_const_bytes: int = 1 << 16


def contract_from_dict(d: dict) -> ProgramContract:
    """Build a contract from plain data (the schedule-IR emission hook).
    Unknown keys are rejected so a typo cannot silently weaken a check."""
    known = {f.name for f in fields(ProgramContract)}
    extra = set(d) - known
    if extra:
        raise ValueError(f"unknown ProgramContract fields: {sorted(extra)}")
    d = dict(d)
    if "mesh_axes" in d:
        d["mesh_axes"] = tuple(d["mesh_axes"])
    return ProgramContract(**d)


CONTRACTS: Tuple[ProgramContract, ...] = (
    # resident boosting rounds: the round programs' budget of 2
    # dispatches, margin donated into the round program
    ProgramContract("resident.fused", dispatch_budget=2, donated=True),
    # xtpuinsight-armed rounds: telemetry + in-carry eval must ride the
    # round program as extra OUTPUTS — the budget stays the unarmed 2,
    # so an extra telemetry dispatch is a gate failure, not a regression
    ProgramContract("resident.fused.insight", dispatch_budget=2,
                    donated=True),
    # paged page-major fast path: one program per level boundary, zero
    # steady-state page re-uploads, positions+state donated through it
    ProgramContract("paged.level_full", dispatch_budget=1, donated=True,
                    uploads_per_level=0),
    # mesh twins: one sharded program per tree; collectives only over
    # the data axis
    ProgramContract("mesh.row", dispatch_budget=1, mesh_axes=("data",)),
    ProgramContract("mesh.col", dispatch_budget=1, mesh_axes=("data",)),
    # serve walk: one program per batch, no collectives
    ProgramContract("serve.walk", dispatch_budget=1),
    # packed-forest twins (PR 15): the whole forest in ONE walk
    # program, and the device TreeSHAP scan behind /contribs
    ProgramContract("serve.walk_packed", dispatch_budget=1),
    ProgramContract("serve.shap", dispatch_budget=1),
)
