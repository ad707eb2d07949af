"""xtpuobs — the unified observability subsystem (docs/observability.md).

Five instruments, one taxonomy:

- :mod:`~xgboost_tpu.obs.trace` — program spans as profiler annotations
  and ``xtpu.<stage>`` scopes inside the compiled programs (the switch
  is the ``jax.profiler`` session); ``XTPU_TRACE=1`` also records the
  spans into a ring exported as Chrome/Perfetto JSON or jsonl. A
  ``phase()`` is a span whose self time is also booked when nothing
  listens: the start-up report (``metrics.startup_report()``).
- :mod:`~xgboost_tpu.obs.metrics` — the process-wide
  :class:`MetricsRegistry` every counting subsystem registers into;
  rendered as Prometheus text exposition on serve's ``GET /metrics``.
- :mod:`~xgboost_tpu.obs.monitor` — the per-label wall-clock
  :class:`Monitor` (the single copy; ``utils/timer.py`` and
  ``logging_utils.py`` re-export it), with the opt-in ``sync=True``
  mode that makes verbosity>=3 tables measure device work.
- :mod:`~xgboost_tpu.obs.flight` — the distributed flight recorder:
  ``(rank, world)``-tagged rings, clock-aligned multi-rank timeline
  merging, the shared overlap kernel, and the crash black box
  (``python -m xgboost_tpu.obs postmortem <bundle>`` renders a dump).
- :mod:`~xgboost_tpu.obs.memory` — stage-boundary HBM watermarks
  (``device.memory_stats()`` with explicit CPU bookings) behind
  ``XTPU_FLIGHT_MEM=1``.
- :mod:`~xgboost_tpu.obs.insight` — learning-health telemetry: per-round
  training scalars and eval metrics computed *inside* the round programs
  (``XTPU_INSIGHT=1`` / ``XTPU_INSIGHT_EVAL=1``), the
  :class:`TrainingLog`, and the model inspector / diff backing
  ``tools/model_report.py`` and the pipeline's gate-rejection reports.

``tools/trace_analyze.py`` computes overlap/straggler reports from
exported rings; ``benchmark/lib/program_trace.py`` reads the spans and
scopes out of a profiler trace.
"""

from . import flight, insight, memory, metrics, trace
from .flight import BlackBox, FlightRecorder, StragglerWarning
from .insight import TrainingLog
from .metrics import Family, HistogramData, MetricsRegistry, Sample, \
    get_registry
from .monitor import Monitor, Timer, annotate, profile
from .trace import Span, Tracer, span

__all__ = [
    "trace", "metrics", "flight", "memory", "insight",
    "Span", "Tracer", "span", "TrainingLog",
    "FlightRecorder", "BlackBox", "StragglerWarning",
    "MetricsRegistry", "Family", "Sample", "HistogramData", "get_registry",
    "Monitor", "Timer", "annotate", "profile",
]
