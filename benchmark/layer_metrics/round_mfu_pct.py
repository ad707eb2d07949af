"""The whole round's share of the chip's HBM peak: least HBM time for one
round's work over (traced interval / rounds completed in it), host gaps and
eval included. Reads no program or kernel name. A tree booster has no FLOP
count worth the name; this is a bandwidth share under the name the driver
looks a whole-step share up by."""


def read(facts):
    from lib import peaks, work
    trace = facts.get('trace')
    if not trace or not trace['rounds']:
        return None
    least = work.round_least_seconds(
        facts['config'], peaks.peak(facts['device_kind'], 'hbm_bytes_per_s'))
    return 100.0 * least / (trace['window_s'] / trace['rounds'])
