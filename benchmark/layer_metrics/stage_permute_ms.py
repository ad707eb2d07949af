"""Device ms a round under ``xtpu.permute``: every gather of bins, gradient
pairs and positions through the row permutation."""


def read(facts):
    from lib.program_trace import stage_group_ms
    return stage_group_ms(facts, "permute")
