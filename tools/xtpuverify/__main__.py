"""CLI: ``python -m tools.xtpuverify [--json] [--baseline FILE] ...``

Exit codes: 0 = clean (no findings outside the baseline), 1 = new
findings, 2 = usage/internal error. See docs/static_analysis.md.

Tracing is forced onto CPU with 8 virtual devices BEFORE jax loads, so
the verifier is deterministic and CI-cheap on any host (the mesh twins
need >= 2 devices; everything runs abstractly, nothing executes).
"""

from __future__ import annotations

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()

import argparse  # noqa: E402
import json      # noqa: E402
import sys       # noqa: E402
import time      # noqa: E402
from typing import List  # noqa: E402

from . import (DEFAULT_BASELINE, format_baseline, load_baseline,  # noqa: E402
               suppression_of, verify_repo)
from .checkers import CHECKERS   # noqa: E402
from .contracts import CONTRACTS  # noqa: E402


def _repo_root() -> str:
    # tools/xtpuverify/__main__.py -> repo root two levels up
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.xtpuverify",
        description="jaxpr-level program-contract verifier for "
                    "xgboost_tpu (dispatch-budget, carry-stability, "
                    "dtype-discipline, donation-ineffective, "
                    "collective-symmetry, constant-bloat).")
    ap.add_argument("handles", nargs="*",
                    help="contract handles to verify (default: all; "
                         "see --list-contracts)")
    ap.add_argument("--root", default=_repo_root(),
                    help="repository root (default: autodetected)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable report on stdout")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline file (default: "
                         "tools/xtpuverify/baseline.toml)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding, ignoring the baseline")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write skeleton suppressions for all CURRENT "
                         "findings to --baseline (justifications for new "
                         "entries are left empty and MUST be filled in "
                         "by hand — the gate rejects empty ones)")
    ap.add_argument("--select", default=None,
                    help="comma-separated checker slugs to run")
    ap.add_argument("--list-checkers", action="store_true")
    ap.add_argument("--list-contracts", action="store_true")
    args = ap.parse_args(argv)

    if args.list_checkers:
        for slug in CHECKERS:
            print(slug)
        return 0
    if args.list_contracts:
        for c in CONTRACTS:
            print(f"{c.handle}: dispatch_budget={c.dispatch_budget}"
                  + (f" uploads_per_level<={c.uploads_per_level}"
                     if c.uploads_per_level is not None else "")
                  + (f" mesh_axes={list(c.mesh_axes)}" if c.mesh_axes
                     else "")
                  + (" donated" if c.donated else ""))
        return 0

    select = tuple(s.strip() for s in args.select.split(",")) \
        if args.select else None
    handles = tuple(args.handles) if args.handles else None

    baseline_path = None if args.no_baseline else args.baseline
    t0 = time.perf_counter()
    result = verify_repo(args.root, baseline_path=baseline_path,
                         select=select, handles=handles)
    elapsed = time.perf_counter() - t0

    if args.write_baseline:
        existing = load_baseline(args.baseline).by_fingerprint()
        entries = []
        for f in result.all_findings:
            old = existing.get(f.fingerprint)
            entries.append(suppression_of(
                f, old.justification if old else ""))
        with open(args.baseline, "w", encoding="utf-8") as fh:
            fh.write(format_baseline(entries))
        empty = sum(1 for e in entries if not e.justification)
        print(f"wrote {len(entries)} suppressions to {args.baseline} "
              f"({empty} need justifications)")
        return 0

    if args.json:
        print(json.dumps({
            "new": [f.to_dict() for f in result.new],
            "suppressed": [f.to_dict() for f in result.suppressed],
            "stale_baseline": [e.fingerprint for e in result.stale],
            "skipped": [{"handle": s.handle, "reason": s.reason}
                        for s in result.skipped],
            "counts": {
                "new": len(result.new),
                "suppressed": len(result.suppressed),
                "stale": len(result.stale),
                "skipped": len(result.skipped),
            },
            "elapsed_s": round(elapsed, 3),
        }, indent=2))
        return 0 if result.ok else 1

    for f in result.new:
        print(f.render())
    if result.stale:
        print(f"note: {len(result.stale)} stale baseline entr"
              f"{'y' if len(result.stale) == 1 else 'ies'} (fixed "
              "findings still suppressed) — run --write-baseline and "
              "review:")
        for e in result.stale:
            print(f"  {e.fingerprint}  {e.path}:{e.line} [{e.checker}]")
    for s in result.skipped:
        print(f"note: skipped {s.handle}: {s.reason}")
    print(f"xtpuverify: {len(result.new)} new, "
          f"{len(result.suppressed)} baselined, "
          f"{len(result.stale)} stale baseline entries, "
          f"{len(result.skipped)} skipped handles "
          f"({elapsed:.1f}s)")
    return 0 if result.ok else 1


if __name__ == "__main__":
    sys.exit(main())
