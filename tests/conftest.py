"""Test configuration: force the CPU backend with 8 virtual devices so the
multi-chip sharding paths run as a mesh without TPU hardware (SURVEY.md §4 test
plan; same trick as the reference's InMemoryCommunicator multi-worker tests)."""

import os
import tempfile

# Compile cache. A JAX_COMPILATION_CACHE_DIR set from outside is used as it
# is (jax reads the variable itself; xgboost_tpu then sets no directory).
# Unset, every run gets a throwaway directory: jax 0.9's LRUCache.put writes
# entries non-atomically, so a run killed mid-write (the tier-1 command is
# killed at its time limit) leaves a truncated entry that crashes every
# later process reading it, and a shared default would also put CPU-test
# entries into <repo>/.jax_cache, the directory a chip run uses. The
# throwaway directory still gives intra-run reuse: the module fixture below
# drops the in-memory executable caches, and dask/multiprocess children
# inherit the variable.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
        prefix="xtpu_test_jax_cache_")
# threshold 0: EVERY compile lands in the disk cache. The module fixture
# below drops the in-memory executable caches at each module boundary
# (segfault workaround), so cross-module reuse of shared-shape programs
# happens through the disk cache — at jax's default threshold the many
# sub-second programs recompiled once per module, which dominated the cold
# suite time.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

# Must run before jax is imported: the CPU backend with 8 virtual devices.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
# Backend optimization level 0 for TEST compiles: the cold suite is
# XLA:CPU compile-bound across genuinely diverse shapes (no small set of
# tests dominates), and dropping the backend optimization level cuts the
# cold wall-clock ~26% (measured on test_basic: 206 -> 151 s). Parity
# tests compare two paths compiled under the SAME flags, so every
# bit-exactness contract is unaffected; numeric tolerances vs host
# oracles are unchanged. Opt out with XTPU_TEST_XLA_OPT=1 to compile at
# the production level.
if os.environ.get("XTPU_TEST_XLA_OPT") != "1" \
        and "xla_backend_optimization_level" not in flags:
    flags = (flags + " --xla_backend_optimization_level=0").strip()
os.environ["XLA_FLAGS"] = flags

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.RandomState(1994)


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Full-suite runs accumulate hundreds of compiled XLA:CPU programs in
    one process; past a point, fresh compiles started segfaulting inside
    backend_compile_and_load nondeterministically (jax 0.9, 8-device
    virtual CPU) — the same tests pass in a short session. Dropping the
    executable caches at each module boundary keeps the process small and
    has survived full single-shot runs where the unbounded process did
    not. Costs per-module recompiles of shared helpers (~seconds)."""
    yield
    try:
        import jax

        jax.clear_caches()
    except Exception:  # pragma: no cover
        pass


def make_regression(n=500, f=10, rng=None, missing_frac=0.0):
    rng = rng or np.random.RandomState(0)
    X = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f).astype(np.float32)
    y = X @ w + 0.1 * rng.randn(n).astype(np.float32)
    if missing_frac > 0:
        mask = rng.rand(n, f) < missing_frac
        X = X.copy()
        X[mask] = np.nan
    return X, y


def make_classification(n=500, f=10, rng=None, n_classes=2):
    rng = rng or np.random.RandomState(0)
    X = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f, n_classes).astype(np.float32)
    logits = X @ w
    y = logits.argmax(axis=1).astype(np.float32)
    return X, y
