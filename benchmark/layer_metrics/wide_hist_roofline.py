"""The histogram kernels' share of their roofline on a dense matrix: the
least HBM time of a round's histogram streams (``lib/work.py``'s dense count,
one byte for every (row, feature) and the gradient pair of each row, a
level, over the table's HBM peak) over the self time a round of the
``tpu_custom_call`` ops (the Mosaic kernels: ``pallas_share_pct`` reads the
same ops). HBM-bound work, a lower bound on bytes. None where no trace was
taken or no kernel ran."""


def read(facts):
    from lib import peaks, work
    trace = facts.get('trace')
    if not trace or not trace['rounds']:
        return None
    mark = 'custom_call_target="tpu_custom_call"'
    kernels = sum(s for name, s in trace['op_self'].items() if mark in name)
    if kernels <= 0:
        return None
    least = work.round_least_seconds(
        facts['config'], peaks.peak(facts['device_kind'], 'hbm_bytes_per_s'))
    return 100.0 * least / (kernels / trace['rounds'])
