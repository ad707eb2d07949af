"""Device ms a round in the split search: ``xtpu.window`` + ``xtpu.refine``
+ ``xtpu.eval`` + ``xtpu.exchange``."""


def read(facts):
    from lib.program_trace import stage_group_ms
    return stage_group_ms(facts, "split")
