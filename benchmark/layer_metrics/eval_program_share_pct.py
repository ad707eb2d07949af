"""Device time of every program that is not a round program over the device's
busy time, in the traced interval."""


def read(facts):
    from lib.trace_reduce import matching_seconds
    trace = facts.get('trace')
    if not trace:
        return None
    other = sum(trace['programs'].values()) - matching_seconds(
        trace['programs'], facts['round_programs'])
    return 100.0 * other / trace['busy_s'] if other > 0 else None
