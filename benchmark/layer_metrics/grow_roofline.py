"""Least HBM time for one round's work (``lib/work.py``, from the
configuration's shapes, over the table's HBM peak) over the round programs'
device time per round. HBM-bound work, a lower bound on bytes."""


def read(facts):
    from lib.trace_reduce import matching_seconds
    from lib import peaks, work
    trace = facts.get('trace')
    if not trace or not trace['rounds']:
        return None
    sec = matching_seconds(trace['programs'],
                           facts['round_programs'])
    if sec <= 0:
        return None
    least = work.round_least_seconds(
        facts['config'], peaks.peak(facts['device_kind'], 'hbm_bytes_per_s'))
    return 100.0 * least / (sec / trace['rounds'])
