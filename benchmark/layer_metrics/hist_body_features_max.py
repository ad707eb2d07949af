"""The most features any traced histogram kernel body unrolls, from the
program's gauge ``xtpu_hist_body_features`` (``ops/pallas/histogram.py``: a
feature block of ``build_hist_pallas``, a group of
``fused_advance_coarse_pallas``). Tracing, lowering and Mosaic's compile of a
round program are linear in it: whole-F bodies at F = 968 made set-up 405 s
(PERF.md section 6, PR 36). None where the program has no such gauge or
traced no kernel."""


def read(facts):
    value = (facts.get('sparse') or {}).get('body_features')
    return float(value) if value else None
