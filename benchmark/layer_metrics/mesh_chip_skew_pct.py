"""Largest less smallest busy time over the cell's device planes in the
traced interval, over their mean (``lib/mesh_trace.py``): how far the
slowest chip holds the others at each collective."""


def read(facts):
    from lib import mesh_trace
    return mesh_trace.chip_skew_pct(facts)
