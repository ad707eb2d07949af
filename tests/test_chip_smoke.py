"""chip_smoke.py on a CPU backend: refused without ``--dry-run-cpu``, a
passing and loudly labelled rehearsal with it (the chip run itself happens
through the chip tool, never here)."""

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # one CPU device, like a one-chip machine: the mesh phase must SAY skip
    env["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "",
        env.get("XLA_FLAGS", ""))
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)


def test_refuses_a_cpu_backend():
    r = _run()
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())


def test_dry_run_cpu_passes_and_says_so():
    r = _run("--dry-run-cpu")
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    report, verdict = map(json.loads, r.stdout.strip().splitlines()[-2:])
    # the verdict line: the chip contract's keys, plus the dry-run mark
    assert set(verdict) == {"ok", "device", "dry_run"}
    assert verdict["ok"] is True and verdict["dry_run"] is True
    assert set(verdict["device"]) == {"platform", "kind", "count"}
    assert verdict["device"]["platform"] == "cpu"
    assert isinstance(verdict["device"]["count"], int)
    assert report["dry_run"] is True
    assert report["device"] == verdict["device"]
    assert report["kernels"]["compiled"] is False
    assert report["mesh"] == "skipped: 1 device(s)"
    assert report["serve"]["recompiles_after_warmup"] == 0
    assert report["fallback_records"] == 0
