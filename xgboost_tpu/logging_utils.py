"""Logging + phase timing.

Mirrors the reference's console logger (``include/xgboost/logging.h:41``).
The ``common::Monitor`` analogue now lives in
:mod:`xgboost_tpu.obs.monitor` (this module used to carry a duplicate
copy); it is re-exported here for compatibility. On TPU the analogue of
NVTX ranges is ``jax.profiler.TraceAnnotation``, which
:mod:`xgboost_tpu.obs.trace` spans open.
"""

from __future__ import annotations

import logging
from typing import Callable, Optional

from .obs.monitor import Monitor  # noqa: F401  (compat re-export)

logger = logging.getLogger("xgboost_tpu")
if not logger.handlers:
    _h = logging.StreamHandler()
    _h.setFormatter(logging.Formatter("[%(asctime)s] %(message)s", "%H:%M:%S"))
    logger.addHandler(_h)
    logger.setLevel(logging.INFO)

_VERBOSITY_TO_LEVEL = {0: logging.ERROR, 1: logging.WARNING, 2: logging.INFO,
                       3: logging.DEBUG}

# Registerable sink, like XGBRegisterLogCallback routing C++ logs into Python
# (reference c_api.h:93).
_log_callback: Optional[Callable[[str], None]] = None


def set_log_callback(cb: Optional[Callable[[str], None]]) -> None:
    global _log_callback
    _log_callback = cb


def console(msg: str) -> None:
    if _log_callback is not None:
        _log_callback(msg)
    else:
        print(msg, flush=True)


def set_verbosity(verbosity: int) -> None:
    logger.setLevel(_VERBOSITY_TO_LEVEL.get(int(verbosity), logging.DEBUG))
