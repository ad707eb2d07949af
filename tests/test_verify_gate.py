"""Tier-1 gate: the repo's traced hot-path programs satisfy their
contracts, modulo the reviewed baseline.

The enforcement half of tools/xtpuverify (docs/static_analysis.md),
mirroring tests/test_lint_gate.py:

- zero NEW findings — every contract violation either gets fixed or a
  baseline entry with a written justification;
- every baseline entry is justified, zero STALE entries;
- zero SKIPPED handles — under the test harness (8 virtual CPU devices,
  conftest.py) every contracted tier, including the mesh twins, must
  actually trace; a silent skip would hollow the gate out.

Traces abstractly on CPU — no device execution; the whole contract
table verifies in a few seconds.
"""

import os

from tools.xtpuverify import DEFAULT_BASELINE, load_baseline, verify_repo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_RESULT = None


def _result():
    global _RESULT
    if _RESULT is None:
        _RESULT = verify_repo(REPO)
    return _RESULT


def test_no_skipped_handles():
    skipped = _result().skipped
    assert not skipped, (
        "program handles that could not trace in this process: "
        + ", ".join(f"{s.handle} ({s.reason})" for s in skipped))


def test_repo_has_no_new_findings():
    result = _result()
    report = "\n".join(f.render() for f in result.new)
    assert result.ok, (
        f"{len(result.new)} new xtpuverify finding(s) — fix them or add "
        f"a justified baseline entry (python -m tools.xtpuverify "
        f"--write-baseline):\n{report}")


def test_every_baseline_entry_is_justified():
    bl = load_baseline(DEFAULT_BASELINE)
    unjustified = [e for e in bl.entries if not e.justification.strip()]
    assert not unjustified, (
        "baseline entries without a written justification: "
        + ", ".join(f"{e.path}:{e.line} [{e.checker}]"
                    for e in unjustified))


def test_no_stale_baseline_entries():
    result = _result()
    assert not result.stale, (
        "baseline entries whose finding no longer exists (delete them): "
        + ", ".join(f"{e.fingerprint} {e.path}:{e.line} [{e.checker}]"
                    for e in result.stale))


def test_round_dispatch_contract_is_pinned():
    """The round driver's bet in contract form: the resident tiers stay
    at budget 2 (fused_round + margin_bad_rows) and the paged tier at zero
    steady page uploads. Loosening these is an explicit, reviewable
    diff."""
    from tools.xtpuverify.contracts import CONTRACTS

    by_handle = {c.handle: c for c in CONTRACTS}
    for tier in ("resident.fused", "resident.fused.insight"):
        assert by_handle[tier].dispatch_budget == 2
        assert by_handle[tier].donated
    assert by_handle["paged.level_full"].uploads_per_level == 0
    assert by_handle["mesh.row"].dispatch_budget == 1
