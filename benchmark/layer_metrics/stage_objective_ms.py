"""Device ms a round outside the tree's levels: ``xtpu.gradient`` +
``xtpu.leaf`` + ``xtpu.margin`` and what is left directly under
``xtpu.grow`` (loop glue, tree bookkeeping)."""


def read(facts):
    from lib.program_trace import stage_group_ms
    return stage_group_ms(facts, "objective")
