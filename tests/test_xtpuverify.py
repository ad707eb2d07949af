"""xtpuverify unit tests: fixture twins, mutation checks, pragmas, CLI.

The fixtures under tests/fixtures/verify/ are bad/good twins per
checker: each module exports ``CONTRACT`` and ``plan()``, bad twins
carry a ``VERIFY[<slug>]`` marker on the line findings anchor at (the
program's decorator/def line), and expectations derive from the markers
so fixture and expectation cannot drift. Good twins verify clean.

The mutation tests are the PR-11 regression contract in static form:
the verifier must flag a resident round whose declared plan grows past
two dispatches, and a paged plan whose declared uploads_per_level rises
above zero — even on hosts where the runtime dispatch-count tests are
skipped.
"""

import glob
import importlib.util
import json
import os
import re
import subprocess
import sys

import pytest

from tools.xtpuverify import verify_pairs, verify_repo
from tools.xtpuverify.contracts import (CONTRACTS, ProgramContract,
                                        contract_from_dict)
from tools.xtpuverify.engine import _PragmaFile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "fixtures", "verify")
_MARKER = re.compile(r"#\s*VERIFY\[([a-z-]+)\]")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"verify_fixture_{name}", os.path.join(FIXTURES, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fixture_findings(name):
    mod = _load(name)
    findings, skipped = verify_pairs([(mod.CONTRACT, mod.plan())],
                                     root=REPO)
    assert not skipped
    return findings


def _markers(name):
    expected = set()
    with open(os.path.join(FIXTURES, f"{name}.py"), encoding="utf-8") as fh:
        for lineno, line in enumerate(fh, 1):
            m = _MARKER.search(line)
            if m:
                expected.add((lineno, m.group(1)))
    return expected


def _twins(suffix):
    names = [os.path.basename(p)[:-3] for p in
             glob.glob(os.path.join(FIXTURES, f"*_{suffix}.py"))]
    assert names, f"no *_{suffix}.py fixtures found"
    return sorted(names)


@pytest.mark.parametrize("name", _twins("bad"))
def test_bad_twin_flags_exactly_marked_lines(name):
    expected = _markers(name)
    assert expected, f"{name} has no VERIFY markers — not a bad twin"
    got = {(f.line, f.checker) for f in _fixture_findings(name)}
    assert got == expected, (
        f"{name}: missed={sorted(expected - got)} "
        f"unexpected={sorted(got - expected)}")


@pytest.mark.parametrize("name", _twins("good"))
def test_good_twin_is_clean(name):
    assert _markers(name) == set()
    findings = _fixture_findings(name)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_every_checker_has_a_twin_pair():
    from tools.xtpuverify.checkers import CHECKERS
    covered = set()
    for name in _twins("bad"):
        covered.update(slug for _, slug in _markers(name))
    assert covered == set(CHECKERS), (
        f"checkers without a bad-twin fixture: {set(CHECKERS) - covered}")


# ---------------------------------------------------- PR-11 mutation checks

def _contract(handle):
    return next(c for c in CONTRACTS if c.handle == handle)


def test_resident_fused_plan_is_contract_clean():
    from xgboost_tpu.programs import build_plan
    findings, skipped = verify_pairs(
        [(_contract("resident.fused"), build_plan("resident.fused"))],
        root=REPO)
    assert not skipped
    assert findings == [], "\n".join(f.render() for f in findings)


def test_round_budget_catches_a_third_dispatch():
    """A refactor that adds a stray third per-round program must fail the
    dispatch-budget contract statically, even where the runtime
    dispatch-count test is skipped."""
    import jax

    from xgboost_tpu.programs import ProgramSpec, _abstract, build_plan

    plan = build_plan("resident.fused")
    stray = jax.jit(lambda m: m * 0.5)
    plan.dispatches.append(ProgramSpec(
        name="stray_update", fn=stray,
        args=(_abstract((512, 1), "float32"),)))
    findings, _ = verify_pairs([(_contract("resident.fused"), plan)],
                               root=REPO)
    budget = [f for f in findings if f.checker == "dispatch-budget"]
    assert budget and "3 dispatches" in budget[0].message


def test_insight_plan_is_contract_clean():
    """The armed round (telemetry + in-carry eval as extra outputs) fits
    the UNARMED budget — the xtpuinsight zero-extra-dispatch claim in
    static form."""
    from xgboost_tpu.programs import build_plan
    findings, skipped = verify_pairs(
        [(_contract("resident.fused.insight"),
          build_plan("resident.fused.insight"))], root=REPO)
    assert not skipped
    assert findings == [], "\n".join(f.render() for f in findings)


def test_insight_budget_catches_a_telemetry_dispatch():
    """Moving the armed round's telemetry into its own per-round program
    must fail the ``resident.*.insight`` contract statically (the ISSUE-14
    mutation: telemetry may only ride the round as extra outputs)."""
    import jax
    import jax.numpy as jnp

    from xgboost_tpu.programs import ProgramSpec, _abstract, build_plan

    plan = build_plan("resident.fused.insight")
    telem = jax.jit(lambda m: jnp.stack([jnp.min(m), jnp.max(m)]))
    plan.dispatches.append(ProgramSpec(
        name="stray_telemetry", fn=telem,
        args=(_abstract((512, 1), "float32"),)))
    findings, _ = verify_pairs(
        [(_contract("resident.fused.insight"), plan)], root=REPO)
    budget = [f for f in findings if f.checker == "dispatch-budget"]
    assert budget and "3 dispatches" in budget[0].message


def test_paged_uploads_contract_catches_regression():
    """Flipping the paged plan's declared uploads_per_level to 1 (a pager
    refactor re-introducing per-level page uploads) must fail."""
    from xgboost_tpu.programs import build_plan

    plan = build_plan("paged.level_full")
    assert plan.meta["uploads_per_level"] == 0
    plan.meta["uploads_per_level"] = 1
    findings, _ = verify_pairs([(_contract("paged.level_full"), plan)],
                               root=REPO)
    assert any(f.checker == "dispatch-budget"
               and "uploads_per_level" in f.message for f in findings)


def test_donation_contract_catches_dropped_declaration():
    """Deleting donate_argnums from a donated tier's program is a
    one-line diff nothing else catches before an OOM: a donated=True
    contract over a plan with no declared donation must fail."""
    import jax

    from xgboost_tpu.programs import ProgramSpec, RoundPlan, _abstract

    m = _abstract((512, 1), "float32")
    fn = jax.jit(lambda margin, delta: margin + delta)   # donation dropped
    plan = RoundPlan(handle="fx.undonated", unit="round", dispatches=[
        ProgramSpec(name="round", fn=fn, args=(m, m))])
    contract = ProgramContract("fx.undonated", dispatch_budget=1,
                               donated=True)
    findings, _ = verify_pairs([(contract, plan)], root=REPO)
    assert any(f.checker == "donation-ineffective"
               and "no dispatch" in f.message for f in findings)


# ------------------------------------------------------------ trace failure

def test_broken_avals_surface_as_trace_failure():
    import jax

    from xgboost_tpu.programs import (ProgramSpec, RoundPlan, _abstract)

    fn = jax.jit(lambda x, y: x @ y)
    plan = RoundPlan(handle="fx.broken", unit="pass", dispatches=[
        ProgramSpec(name="mm", fn=fn,
                    args=(_abstract((4, 8), "float32"),
                          _abstract((4, 8), "float32")))])  # shape clash
    findings, _ = verify_pairs(
        [(ProgramContract("fx.broken", dispatch_budget=1), plan)],
        root=REPO)
    assert [f.checker for f in findings] == ["trace-failure"]


# ----------------------------------------------------------------- pragmas

def test_pragma_suppresses_on_line_and_line_above(tmp_path):
    src = ("def f():\n"
           "    pass  # xtpuverify: disable=carry-stability\n"
           "# xtpuverify: disable=dtype-discipline,constant-bloat\n"
           "def g():\n"
           "    pass\n")
    (tmp_path / "m.py").write_text(src)
    pf = _PragmaFile(str(tmp_path), "m.py")
    assert pf.suppressed(2, "carry-stability")
    assert not pf.suppressed(2, "dtype-discipline")
    assert pf.suppressed(4, "dtype-discipline")      # line above the def
    assert pf.suppressed(4, "constant-bloat")
    assert not pf.suppressed(4, "carry-stability")
    assert not pf.suppressed(1, "carry-stability")


def test_pragma_all_wildcard(tmp_path):
    (tmp_path / "m.py").write_text(
        "def f():  # xtpuverify: disable=all\n    pass\n")
    pf = _PragmaFile(str(tmp_path), "m.py")
    assert pf.suppressed(1, "dispatch-budget")
    assert pf.suppressed(1, "constant-bloat")


# ---------------------------------------------------------------- contracts

def test_contract_from_dict_roundtrip():
    c = contract_from_dict({"handle": "x", "dispatch_budget": 2,
                            "mesh_axes": ["data"], "donated": True})
    assert c == ProgramContract("x", dispatch_budget=2,
                                mesh_axes=("data",), donated=True)
    with pytest.raises(ValueError, match="unknown"):
        contract_from_dict({"handle": "x", "dispatch_budget": 1,
                            "dispatch_bugdet": 3})


def test_contract_table_covers_every_registered_handle():
    from xgboost_tpu.programs import program_names
    assert sorted(c.handle for c in CONTRACTS) == program_names()


# ------------------------------------------------------------- select filter

def test_select_runs_only_named_checkers():
    mod = _load("dispatch_bad")
    findings, _ = verify_pairs([(mod.CONTRACT, mod.plan())], root=REPO,
                               select=("carry-stability",))
    assert findings == []


# ---------------------------------------------------------------------- CLI

def _run_cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "tools.xtpuverify", *args],
        cwd=REPO, capture_output=True, text=True, timeout=300)


def test_cli_list_checkers_and_contracts():
    proc = _run_cli("--list-checkers")
    assert proc.returncode == 0
    assert set(proc.stdout.split()) == {
        "dispatch-budget", "carry-stability", "dtype-discipline",
        "donation-ineffective", "collective-symmetry", "constant-bloat"}
    proc = _run_cli("--list-contracts")
    assert proc.returncode == 0
    assert "resident.fused: dispatch_budget=2 donated" in proc.stdout


def test_cli_single_handle_json():
    proc = _run_cli("--json", "serve.walk")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["counts"] == {"new": 0, "suppressed": 0, "stale": 0,
                                "skipped": 0}
