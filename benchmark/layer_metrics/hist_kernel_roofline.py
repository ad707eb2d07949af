"""The histogram kernels' share of their roofline: the least HBM time of a
round's histogram streams (``lib/sparse_work.py``: one byte for each PRESENT
value and the gradient pair of each row, a level, over the table's HBM peak)
over the self time a round of the ``tpu_custom_call`` ops (the Mosaic
kernels: ``pallas_share_pct`` reads the same ops). HBM-bound work, a lower
bound on bytes that no kernel's choice of layout moves. None where the
configuration states no ``missing_share`` or no kernel ran."""


def read(facts):
    from lib import peaks, sparse_work
    trace = facts.get('trace')
    if not trace or not trace['rounds'] \
            or 'missing_share' not in facts['config']:
        return None
    mark = 'custom_call_target="tpu_custom_call"'
    kernels = sum(s for name, s in trace['op_self'].items() if mark in name)
    if kernels <= 0:
        return None
    least = sparse_work.round_least_seconds(
        facts['config'], peaks.peak(facts['device_kind'], 'hbm_bytes_per_s'))
    return 100.0 * least / (kernels / trace['rounds'])
