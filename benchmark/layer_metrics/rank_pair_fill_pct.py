"""Pairs kept over pair slots computed, in percent, from the program's own
counters over the window (``xtpu_rank_pairs_kept_total`` /
``xtpu_rank_pair_slots_total``): the pair sweep's share of useful work."""


def read(facts):
    rank = facts.get('rank')
    if not rank or not rank.get('pair_slots'):
        return None
    return 100.0 * rank['pairs_kept'] / rank['pair_slots']
