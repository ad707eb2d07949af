"""Device ms a round under ``rank.layout`` and ``rank.reduce``: the rows
scattered into the padded ``[G, L]`` buffers, and the padded sums gathered
back to rows."""


def read(facts):
    from lib.rank_trace import part_ms
    return part_ms(facts, ("layout", "reduce"))
