"""Device ms a round under the partition stages of the round programs: the
row decision (``xtpu.advance``), the counting sort (``xtpu.count_sort``),
the per-level leaf delta (``xtpu.delta``) and what is left directly under
``xtpu.sort`` (``lib/program_trace.py`` GROUPS). 0.0 where no op carries
them."""


def read(facts):
    from lib.program_trace import stage_group_ms
    return stage_group_ms(facts, "partition")
