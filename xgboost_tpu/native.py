"""Native (C++) runtime components, loaded via ctypes.

The reference keeps its CPU hot paths in C++ (TreeSHAP in
``src/predictor/cpu_treeshap.cc``, data parsing in dmlc-core); this module is
the equivalent runtime layer for the TPU framework: a small shared library
compiled from ``native/*.cc`` on first use (g++ is part of the toolchain;
there is no separate wheel build step) and cached next to the sources.

All device compute stays in JAX/Pallas — only host-side, latency-bound,
pointer-chasing work (SHAP path algebra, text parsing, CLI serving) lives
here.
"""

from __future__ import annotations

import contextlib
import ctypes
import hashlib
import os
import platform
import shutil
import subprocess
import threading
from typing import Optional

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_LIB_NAME = "libxgboost_tpu_native.so"
_BASE_FLAGS = ["-O3", "-std=c++17", "-shared", "-fPIC"]
# -march=native unlocks the AVX-512 binning sweep in sketch.cc; fall back
# progressively for toolchains/CPUs that reject it or lack libgomp
_FLAG_LADDER = (["-march=native", "-fopenmp"], ["-fopenmp"],
                ["-march=native"], [])
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_no_toolchain = False


def _sources():
    return sorted(
        os.path.join(_NATIVE_DIR, f)
        for f in os.listdir(_NATIVE_DIR) if f.endswith((".cc", ".h")))


def _host_cpu() -> str:
    """What ``-march=native`` resolves against: the machine type plus the
    first processor's model and feature flags."""
    lines = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            seen = set()
            for line in f:
                key = line.split(":", 1)[0].strip()
                if key in ("model name", "flags", "Features") \
                        and key not in seen:
                    seen.add(key)
                    lines.append(line.strip())
    except OSError:
        lines.append(platform.processor())
    return "\n".join(lines)


def _digest() -> str:
    """Key of the cached library: the bytes of ``native/*.cc|*.h``, the
    compiler flags and the host CPU. A library built from other sources or
    on another CPU (a copied checkout) never matches."""
    h = hashlib.sha256()
    for src in _sources():
        h.update(os.path.basename(src).encode())
        with open(src, "rb") as f:
            h.update(f.read())
    h.update(repr((_BASE_FLAGS, _FLAG_LADDER)).encode())
    h.update(_host_cpu().encode())
    return h.hexdigest()


def _build(lib_path: str, digest: str) -> None:
    # Build to a unique temp path and rename atomically so concurrent
    # processes never dlopen a half-written library. The digest sidecar is
    # removed first and written last: a reader that finds it matching finds
    # the library it describes.
    srcs = [s for s in _sources() if s.endswith(".cc")]
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    base = ["g++"] + _BASE_FLAGS + ["-o", tmp] + srcs
    for extra in _FLAG_LADDER:
        try:
            subprocess.run(base + extra, check=True, capture_output=True)
            break
        except subprocess.CalledProcessError as e:
            if not extra:
                raise RuntimeError(
                    "building native/*.cc failed:\n"
                    + e.stderr.decode(errors="replace")) from e
    side = lib_path + ".digest"
    with contextlib.suppress(FileNotFoundError):
        os.remove(side)
    os.replace(tmp, lib_path)
    with open(tmp, "w") as f:
        f.write(digest)
    os.replace(tmp, side)


def _cached_digest(lib_path: str) -> Optional[str]:
    try:
        with open(lib_path + ".digest") as f:
            return f.read().strip()
    except OSError:
        return None


def load() -> Optional[ctypes.CDLL]:
    """Return the native library, (re)building it unless the cached one was
    built from these sources with these flags on this CPU (``_digest``).
    None only when the host has no ``g++`` (callers then take the
    pure-Python paths); a build that fails where ``g++`` exists raises."""
    global _lib, _no_toolchain
    if _lib is not None or _no_toolchain:
        return _lib
    with _lock:
        if _lib is not None or _no_toolchain:
            return _lib
        lib_path = os.path.join(_NATIVE_DIR, _LIB_NAME)
        digest = _digest()
        if not os.path.exists(lib_path) \
                or _cached_digest(lib_path) != digest:
            if shutil.which("g++") is None:
                _no_toolchain = True
                return None
            from .obs import trace as obs_trace

            with obs_trace.phase("native/build", "ingest"):
                _build(lib_path, digest)
        _lib = ctypes.CDLL(lib_path)
    return _lib
