"""xtpuverify core: trace program handles, walk jaxprs, emit findings.

Where ``tools.xtpulint`` reasons about *source* (ast, no imports),
xtpuverify reasons about *programs*: it imports the library, builds each
registered :class:`~xgboost_tpu.programs.RoundPlan`, traces every
dispatch with ``jax.ShapeDtypeStruct`` avals (``.trace()`` — abstract
evaluation only, no device execution, no real data) and hands the traced
artifacts to the checkers in ``tools/xtpuverify/checkers``. That makes
properties checkable that no source lint can see: the number of compiled
programs a steady round actually dispatches, the shape/dtype/size of
every loop carry, which primitives a bf16 value reaches after jax's own
promotion, whether declared donation survives to input-output aliasing
in the lowered StableHLO, and the collective sequence on each side of a
``lax.cond``.

Findings use the SAME fingerprint recipe as xtpulint
(sha1-prefix of checker|path|symbol|normalized-text) so both tools share
``tools/analysis_baseline.py``. For a verify finding the fingerprinted
text is a *semantic descriptor* of the violation (e.g.
``carry[3] float64 in scan``) rather than a source line: the finding is
about the traced program, and should survive unrelated edits to the file
that defines it. Path/line anchor at the program's def site (via
``ProgramSpec.source``) — that is also where an inline
``# xtpuverify: disable=<slug>`` pragma suppresses it.

Tracing must stay CI-cheap: everything runs under ``JAX_PLATFORMS=cpu``
(the ``__main__`` sets it before jax loads) and lowering — the only
expensive step — happens lazily, only for programs whose contract needs
the StableHLO text (donation).
"""

from __future__ import annotations

import hashlib
import os
from dataclasses import dataclass, field
from typing import (Any, Dict, Iterable, Iterator, List, Optional, Sequence,
                    Tuple)

SUPPRESS_TOKEN = "xtpuverify: disable="


# ------------------------------------------------------------------ findings

@dataclass
class Finding:
    checker: str          # slug, e.g. "dispatch-budget"
    path: str             # repo-relative posix path of the program's def
    line: int             # def line (anchors pragmas; informational)
    symbol: str           # "<handle>/<program>" or "<handle>"
    message: str
    hint: str = ""
    line_text: str = ""   # semantic descriptor — the fingerprinted text
    occurrence: int = 0   # disambiguates identical descriptors

    @property
    def fingerprint(self) -> str:
        norm = "".join(self.line_text.split())
        key = f"{self.checker}|{self.path}|{self.symbol}|{norm}"
        if self.occurrence:
            key += f"#{self.occurrence}"
        return hashlib.sha1(key.encode()).hexdigest()[:12]

    def to_dict(self) -> Dict[str, object]:
        return {
            "checker": self.checker, "path": self.path, "line": self.line,
            "symbol": self.symbol, "message": self.message,
            "hint": self.hint, "fingerprint": self.fingerprint,
        }

    def render(self) -> str:
        out = (f"{self.path}:{self.line}: [{self.checker}] "
               f"({self.symbol}) {self.message}")
        if self.hint:
            out += f"\n    hint: {self.hint}"
        return out


def finalize_findings(findings: List[Finding]) -> List[Finding]:
    findings.sort(key=lambda f: (f.path, f.line, f.checker, f.message))
    seen: Dict[Tuple[str, str, str, str], int] = {}
    for f in findings:
        key = (f.checker, f.path, f.symbol, "".join(f.line_text.split()))
        f.occurrence = seen.get(key, 0)
        seen[key] = f.occurrence + 1
    return findings


# --------------------------------------------------------------- jaxpr utils
#
# Sub-jaxprs hide in eqn.params values as ClosedJaxpr, bare Jaxpr, or
# tuples/lists of either (scan: "jaxpr", while: "cond_jaxpr"/"body_jaxpr",
# cond: "branches", pjit: "jaxpr", custom_*: "call_jaxpr"/"fun_jaxpr").

def _sub_jaxprs(value) -> Iterator[Any]:
    from jax.extend.core import ClosedJaxpr, Jaxpr

    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def iter_eqns(jaxpr) -> Iterator[Any]:
    """Every eqn in a (Closed)Jaxpr, recursing into sub-jaxprs."""
    from jax.extend.core import ClosedJaxpr

    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                for inner in iter_eqns(sub):
                    yield inner


def iter_closed_jaxprs(closed) -> Iterator[Any]:
    """Every ClosedJaxpr in the tree (top level + nested) — the consts of
    inner pjit closures live on these, not on the top-level jaxpr."""
    from jax.extend.core import ClosedJaxpr

    yield closed
    for eqn in iter_eqns(closed):
        for v in eqn.params.values():
            if isinstance(v, ClosedJaxpr):
                yield v
            elif isinstance(v, (tuple, list)):
                for x in v:
                    if isinstance(x, ClosedJaxpr):
                        yield x


def scan_carry_avals(eqn) -> List[Any]:
    """Carry avals of a ``scan`` eqn (fori_loop lowers to scan when the
    trip count is static, so this covers the level loops too)."""
    n_consts = eqn.params["num_consts"]
    n_carry = eqn.params["num_carry"]
    return [v.aval for v in eqn.invars[n_consts:n_consts + n_carry]]


def while_carry_avals(eqn) -> List[Any]:
    n_consts = eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
    return [v.aval for v in eqn.invars[n_consts:]]


def aval_nbytes(aval) -> int:
    import numpy as np

    size = 1
    for d in getattr(aval, "shape", ()):
        size *= int(d)
    return size * np.dtype(aval.dtype).itemsize


def short_aval(aval) -> str:
    shape = ",".join(str(d) for d in getattr(aval, "shape", ()))
    weak = "~" if getattr(aval, "weak_type", False) else ""
    return f"{weak}{aval.dtype.name}[{shape}]"


# ----------------------------------------------------------- traced programs

class TraceFailure(Exception):
    def __init__(self, spec, cause: BaseException) -> None:
        super().__init__(f"{spec.name}: {type(cause).__name__}: {cause}")
        self.spec = spec
        self.cause = cause


class TracedProgram:
    """One plan dispatch, traced once; lowering deferred until a checker
    asks for the StableHLO text."""

    def __init__(self, spec) -> None:
        self.spec = spec
        try:
            self.traced = spec.fn.trace(*spec.args, **(spec.kwargs or {}))
        except Exception as e:          # noqa: BLE001 - reported as finding
            raise TraceFailure(spec, e) from e
        self._lowered_text: Optional[str] = None

    @property
    def jaxpr(self):
        return self.traced.jaxpr

    @property
    def lowered_text(self) -> str:
        if self._lowered_text is None:
            self._lowered_text = self.traced.lower().as_text()
        return self._lowered_text


# ------------------------------------------------------------- check context

@dataclass
class CheckContext:
    contract: Any                      # ProgramContract
    plan: Any                          # RoundPlan
    programs: List[TracedProgram]
    root: str

    def finding(self, checker: str, message: str, *, detail: str,
                spec=None, hint: str = "") -> Finding:
        """``detail`` is the stable fingerprint text — keep it a compact
        signature of the violation, free of incidental counters."""
        if spec is None:
            spec = self.plan.dispatches[0]
            symbol = self.plan.handle
        else:
            symbol = f"{self.plan.handle}/{spec.name}"
        path, line = spec.source
        return Finding(checker=checker, path=path, line=line, symbol=symbol,
                       message=message, hint=hint, line_text=detail)


# ------------------------------------------------------------------- running

@dataclass
class VerifyConfig:
    root: str
    select: Optional[Tuple[str, ...]] = None
    handles: Optional[Tuple[str, ...]] = None   # contract handles to verify
    contracts: Optional[Tuple[Any, ...]] = None  # override contract table


@dataclass
class SkippedHandle:
    handle: str
    reason: str


class _PragmaFile:
    def __init__(self, root: str, relpath: str) -> None:
        self.lines: List[str] = []
        full = os.path.join(root, relpath)
        if os.path.isfile(full):
            try:
                with open(full, "r", encoding="utf-8") as fh:
                    self.lines = fh.read().splitlines()
            except OSError:
                pass

    def suppressed(self, lineno: int, checker: str) -> bool:
        for ln in (lineno, lineno - 1):
            if not (1 <= ln <= len(self.lines)):
                continue
            text = self.lines[ln - 1]
            if SUPPRESS_TOKEN in text:
                ids = text.split(SUPPRESS_TOKEN, 1)[1].split()[0]
                names = {s.strip() for s in ids.split(",")}
                if checker in names or "all" in names:
                    return True
        return False


def run_contracts(config: VerifyConfig
                  ) -> Tuple[List[Finding], List[SkippedHandle]]:
    """Build, trace and check every contracted handle. Returns finalized
    findings plus the handles that could not run in this process
    (ProgramUnavailable — e.g. mesh twins on a single device)."""
    from xgboost_tpu.programs import ProgramUnavailable, build_plan

    from .checkers import CHECKERS
    from .contracts import CONTRACTS

    contracts = config.contracts if config.contracts is not None \
        else CONTRACTS
    findings: List[Finding] = []
    skipped: List[SkippedHandle] = []
    pragma_cache: Dict[str, _PragmaFile] = {}

    def is_suppressed(f: Finding) -> bool:
        pf = pragma_cache.get(f.path)
        if pf is None:
            pf = pragma_cache[f.path] = _PragmaFile(config.root, f.path)
        return pf.suppressed(f.line, f.checker)

    for contract in contracts:
        if config.handles and contract.handle not in config.handles:
            continue
        try:
            plan = build_plan(contract.handle)
        except ProgramUnavailable as e:
            skipped.append(SkippedHandle(contract.handle, str(e)))
            continue
        programs: List[TracedProgram] = []
        failed = False
        for spec in plan.dispatches:
            try:
                programs.append(TracedProgram(spec))
            except TraceFailure as e:
                path, line = spec.source
                findings.append(Finding(
                    checker="trace-failure", path=path, line=line,
                    symbol=f"{plan.handle}/{spec.name}",
                    message=f"program failed to trace abstractly: {e}",
                    hint="every declared dispatch must trace with "
                         "ShapeDtypeStruct avals; fix the handle's avals "
                         "or the program",
                    line_text=f"trace failure {spec.name}"))
                failed = True
        if failed:
            continue
        ctx = CheckContext(contract=contract, plan=plan,
                           programs=programs, root=config.root)
        for slug, fn in CHECKERS.items():
            if config.select and slug not in config.select:
                continue
            for f in fn(ctx):
                if not is_suppressed(f):
                    findings.append(f)
    return finalize_findings(findings), skipped


def verify_pairs(pairs, root: str,
                 select: Optional[Tuple[str, ...]] = None
                 ) -> Tuple[List[Finding], List[SkippedHandle]]:
    """Check explicit (contract, plan) pairs — the fixture-twin tests'
    entry point; no registry, no baseline."""
    from .checkers import CHECKERS

    findings: List[Finding] = []
    skipped: List[SkippedHandle] = []
    for contract, plan in pairs:
        programs = []
        failed = False
        for spec in plan.dispatches:
            try:
                programs.append(TracedProgram(spec))
            except TraceFailure as e:
                path, line = spec.source
                findings.append(Finding(
                    checker="trace-failure", path=path, line=line,
                    symbol=f"{plan.handle}/{spec.name}",
                    message=str(e), line_text=f"trace failure {spec.name}"))
                failed = True
        if failed:
            continue
        ctx = CheckContext(contract=contract, plan=plan,
                           programs=programs, root=root)
        for slug, fn in CHECKERS.items():
            if select and slug not in select:
                continue
            findings.extend(fn(ctx))
    return finalize_findings(findings), skipped
