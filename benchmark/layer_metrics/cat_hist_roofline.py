"""The categorical histograms' share of their HBM roofline: the least HBM
time of a round's categorical-column reads (``lib/cat_work.py``: each row's
categorical ids and its gradient pair, a level, over the table's HBM peak)
over the device time a round of the histogram stage (``stage_hist_ms``'
group: the kernels, their quantisation and fold). The categorical columns
ride the refine build's sweep, with no kernel of their own, so the share
reads that stage against the count, whatever implements the build. None
where no trace was taken, the configuration states no categorical columns,
or no histogram op ran."""


def read(facts):
    from lib import cat_work, peaks
    from lib.program_trace import stage_group_ms
    if not facts.get('trace') or 'categorical' not in facts['config']:
        return None
    ms = stage_group_ms(facts, 'hist')
    if not ms:
        return None
    least = cat_work.round_least_seconds(
        facts['config'], peaks.peak(facts['device_kind'], 'hbm_bytes_per_s'))
    return 100.0 * least / (ms / 1e3)
