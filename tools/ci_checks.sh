#!/usr/bin/env bash
# The repo's CI entry point: static analysis first (fast, catches the
# jax/TPU failure modes before any test runs), then the tier-1 suite.
#
#   bash tools/ci_checks.sh            # everything
#   bash tools/ci_checks.sh --lint     # xtpulint only (sub-second-ish)
#
# xtpulint gates at zero NEW findings against tools/xtpulint/baseline.toml
# and xtpuverify gates the traced program contracts against
# tools/xtpuverify/baseline.toml (docs/static_analysis.md); the same gates
# also run inside the suite as tests/test_lint_gate.py /
# tests/test_verify_gate.py, so CI setups that only run pytest still
# enforce them — this script just fails faster and prints findings with
# hints.

set -o pipefail
cd "$(dirname "$0")/.."

echo "== xtpulint =="
python -m tools.xtpulint || exit $?

echo "== xtpuverify (program contracts, abstract trace on CPU) =="
python -m tools.xtpuverify || exit $?

[ "$1" = "--lint" ] && exit 0

echo "== validate_obs (traced-vs-untraced byte equality + exposition lint) =="
JAX_PLATFORMS=cpu python tools/validate_obs.py || exit $?

echo "== validate_fleet (kill-one-replica, atomic fan-out, ring churn) =="
JAX_PLATFORMS=cpu VALIDATE_FLEET_REQS="${VALIDATE_FLEET_REQS:-60}" \
    python tools/validate_fleet.py || exit $?

echo "== tier-1 tests =="
timeout -k 10 870 env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly
