"""Device ms a round under ``rank.order``: the per-group argsorts that give
each row its rank, the label sort for the ideal DCG, gains and discounts."""


def read(facts):
    from lib.rank_trace import part_ms
    return part_ms(facts, ("order",))
