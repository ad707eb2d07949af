"""Seconds the process spent tracing and lowering the round programs (the
mix's ``round_programs``), from the program's own counter
``xtpu_program_trace_lower_seconds_total{program}``: booked to the
outermost program, so nested traces are not counted twice. Nothing compiles
inside a window, so this is set-up's. None where the program has no such
counter."""


def read(facts):
    import sys
    programs = facts.get('round_programs')
    if not programs:
        return None
    try:
        from xgboost_tpu.obs.metrics import program_compile_counts
    except ImportError:
        return None
    counts = program_compile_counts()
    top = sorted(counts.items(), key=lambda kv: -kv[1]["trace_lower_s"])[:8]
    print("[bench] trace+lower seconds by program: " + ", ".join(
        f"{name} {c['trace_lower_s']:.2f} (compiles {int(c['compiles'])}, "
        f"cache hits {int(c.get('cache_hits', 0))}, {c['compile_s']:.2f} s)"
        for name, c in top),
        file=sys.stderr, flush=True)
    return sum(c["trace_lower_s"] for name, c in counts.items()
               if any(p in name for p in programs))
