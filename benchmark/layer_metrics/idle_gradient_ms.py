"""Device idle ms a round while the host was under ``round/gradient``: the
objective's gradient dispatch on the general path (for ranking, the layout's
content key and the launch). Prints what the spans say the content key cost
(``layout_key_ms``, the last call's reading on each span) beside it."""


def read(facts):
    import sys
    from lib import program_trace as pt
    from lib.rank_trace import gradient_span_stats
    value = pt.idle_ms_under(facts, r"round/gradient")
    planes = pt.last_trace() if value is not None else None
    keys = [float(st["layout_key_ms"]) for st in gradient_span_stats(
        planes or []) if "layout_key_ms" in st]
    if keys:
        print(f"[bench] round/gradient spans: {len(keys)} with "
              f"layout_key_ms, mean {sum(keys) / len(keys):.3f}, max "
              f"{max(keys):.3f} ms of host time a round",
              file=sys.stderr, flush=True)
    return value
