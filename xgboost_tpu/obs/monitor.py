"""The one ``Monitor`` (reference ``common::Monitor``,
``src/common/timer.h:16,46``): per-label wall-clock accumulators whose
table prints at verbosity >= 3, like the reference's ``--verbosity=3``
per-class timing tables.

This unifies the two historical copies (``utils/timer.py`` and
``logging_utils.py`` both grew one; both re-export from here now) and
fixes their documented lie: on TPU the device work is asynchronous, so
a plain ``start``/``stop`` bracket measures **host-side dispatch**, not
device time. Opt in to device-true tables with ``sync=True`` and hand
each section a sentinel to block on::

    mon = Monitor("Booster", sync=True)
    with mon.section("BoostOneIter") as sec:
        out = fused_step(...)
        sec.sync_on(out)        # stop() blocks until out is device-ready

With ``sync=False`` (the default) the sentinel is ignored and the
bracket stays free — the historical behavior, fine for host-side phases
and for spotting dispatch stalls. Sections are the verbosity-3 table's
alone: the trace timeline names the same intervals ``round/gradient`` and
``round/general`` (:mod:`~xgboost_tpu.obs.trace`).
"""

from __future__ import annotations

import time
from typing import Dict, Optional


class Timer:
    __slots__ = ("elapsed", "count", "_start")

    def __init__(self) -> None:
        self.elapsed = 0.0
        self.count = 0
        self._start = 0.0

    def start(self) -> None:
        self._start = time.perf_counter()

    def stop(self) -> None:
        self.elapsed += time.perf_counter() - self._start
        self.count += 1


def _block(x) -> None:
    import jax

    jax.block_until_ready(x)


class Monitor:
    """Label -> Timer map with a context-manager shorthand."""

    def __init__(self, name: str = "", sync: bool = False) -> None:
        self.name = name
        self.sync = sync
        self.timers: Dict[str, Timer] = {}

    # ------------------------------------------------------------- brackets
    def start(self, label: str) -> None:
        self.timers.setdefault(label, Timer()).start()

    def stop(self, label: str, sync_on=None) -> None:
        if self.sync and sync_on is not None:
            _block(sync_on)
        self.timers[label].stop()

    class _Section:
        __slots__ = ("mon", "label", "_sentinel")

        def __init__(self, mon: "Monitor", label: str) -> None:
            self.mon = mon
            self.label = label
            self._sentinel = None

        def sync_on(self, x) -> None:
            """Under ``Monitor(sync=True)``, block on ``x`` before the
            section's clock stops; a no-op otherwise."""
            self._sentinel = x

        def __enter__(self) -> "Monitor._Section":
            self.mon.start(self.label)
            return self

        def __exit__(self, *exc):
            self.mon.stop(self.label, sync_on=self._sentinel)
            self._sentinel = None
            return False

    def section(self, label: str) -> "_Section":
        return Monitor._Section(self, label)

    # historical logging_utils.Monitor API
    def timed(self, label: str) -> "_Section":
        return self.section(label)

    # ----------------------------------------------- logging_utils compat
    @property
    def totals(self) -> Dict[str, float]:
        return {k: t.elapsed for k, t in self.timers.items()}

    @property
    def counts(self) -> Dict[str, int]:
        return {k: t.count for k, t in self.timers.items()}

    # ------------------------------------------------------------ reporting
    def report(self) -> str:
        lines = [f"======== Monitor ({self.name}) ========"]
        for label, t in sorted(self.timers.items()):
            lines.append(f"{label}: {t.elapsed * 1e3:.3f}ms, "
                         f"{t.count} calls @ "
                         f"{t.elapsed / max(t.count, 1) * 1e6:.1f}us")
        return "\n".join(lines)

    def maybe_print(self, verbosity: Optional[int] = None) -> None:
        """Print the table when verbosity >= 3 (reference prints from the
        Monitor destructor under the same condition). ``verbosity=None``
        reads the global config."""
        if verbosity is None:
            from ..config import get_config

            verbosity = get_config().get("verbosity", 1)
        if verbosity >= 3 and self.timers:
            from ..logging_utils import console

            console(self.report())


def annotate(label: str):
    """Named range on the device timeline (the reference's NVTX ranges,
    ``src/common/timer.h:52`` under ``USE_NVTX``): shows up in
    ``jax.profiler`` traces. Usable as a context manager."""
    import jax

    return jax.profiler.TraceAnnotation(label)


class profile:
    """Capture a device profile around a block (reference: nvprof/NVTX
    workflow): ``with profile("/tmp/trace"): bst = train(...)`` writes a
    TensorBoard-loadable trace of every XLA kernel."""

    def __init__(self, log_dir: str) -> None:
        self.log_dir = log_dir

    def __enter__(self):
        import jax

        jax.profiler.start_trace(self.log_dir)
        return self

    def __exit__(self, *exc):
        import jax

        jax.profiler.stop_trace()
        return False
