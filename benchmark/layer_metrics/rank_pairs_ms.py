"""Device ms a round under ``rank.pairs``: the ``[C, L, L]`` pair block
(masks, |delta NDCG|, the RankNet lambda and hessian, the two row sums) and
the chunk loop's own glue."""


def read(facts):
    from lib.rank_trace import part_ms
    return part_ms(facts, ("pairs",))
