"""The program's own account of set-up: ``xgboost_tpu.obs.metrics
.startup_report()``, the gauges ``xtpu_startup_seconds{phase}`` that the
process's first ``train()`` froze when it returned (self seconds by phase on
the program's clock; ``caller`` is this harness's own code, above all the
data generator). The ``setup_*`` per-layer metrics read it; nothing here
reads a driver's ``facts``. A program without the report (the parent of the
PR that brought it) gives None, and the line leaves the metric out."""

from __future__ import annotations


def report():
    """``{phase: seconds}``, or None where the program has no report."""
    try:
        from xgboost_tpu.obs.metrics import startup_report
    except ImportError:
        return None
    return startup_report()


def seconds(*phases):
    """The phases' seconds added up; a phase the run never opened reads 0.
    None where the program has no report."""
    rep = report()
    if rep is None:
        return None
    return float(sum(rep.get(p, 0.0) for p in phases))


def _compile_counts():
    """The program's ``{program: {compiles, cache_hits, ...}}``, or None
    where it does not count them."""
    try:
        from xgboost_tpu.obs.metrics import program_compile_counts
    except ImportError:
        return None
    return program_compile_counts()


def compiled_programs():
    """Programs this process compiled where a persistent-cache entry would
    have served it: compiles jax then wrote to the cache
    (``xtpu_program_cache_misses_total``, all programs so far). The small
    programs under jax's 1 s threshold are compiled in every process, never
    written and not counted. None where the program does not count them."""
    counts = _compile_counts()
    if counts is None or any("cache_misses" not in c
                             for c in counts.values()):
        return None
    return float(sum(c["cache_misses"] for c in counts.values()))


def line(rep, round_programs=()):
    """One line of a run's whole set-up, largest part first, with the
    compile path's seconds (which ``compile_s`` and
    ``round_program_trace_lower_s`` read, less the small programs' share)
    and ``native/build`` among them."""
    parts = sorted(((k, v) for k, v in rep.items() if k != "total"),
                   key=lambda kv: -kv[1])
    text = f"total {rep.get('total', 0.0):.2f}: " + ", ".join(
        f"{k} {v:.2f}" for k, v in parts)
    counts = _compile_counts()
    if counts is None:
        return text

    def total(field, programs=counts.values()):
        return sum(c.get(field, 0) for c in programs)

    mine = [c for name, c in counts.items()
            if any(p in name for p in round_programs)]
    return text + (
        f"; of the compile path the round programs trace+lower "
        f"{total('trace_lower_s', mine):.2f}, compile or load "
        f"{total('compile_s', mine):.2f}; programs compiled "
        f"{int(total('compiles'))}, served by the cache "
        f"{int(total('cache_hits'))}, written to it "
        f"{int(total('cache_misses'))}")
