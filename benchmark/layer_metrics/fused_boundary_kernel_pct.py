"""Level boundaries of the traced grow programs that took the Mosaic sweep
(``ops/histogram.py fused_advance_coarse``: advance + coarse histogram from
one read of the bin tile) over all their boundaries, in percent, from the
program's counter ``xtpu_fused_boundary_total{body="kernel"|"xla"}``. None
where the program has no such counter or traced no boundary."""


def read(facts):
    boundary = (facts.get('sparse') or {}).get('boundary') or {}
    total = sum(boundary.values())
    if not total:
        return None
    return 100.0 * boundary.get('kernel', 0) / total
