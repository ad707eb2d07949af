"""Device time of the round programs' executions in the traced interval over
the rounds traced. Which programs are round programs is the mix's
``round_programs``."""


def read(facts):
    from lib.trace_reduce import matching_seconds
    trace = facts.get('trace')
    if not trace or not trace['rounds']:
        return None
    sec = matching_seconds(trace['programs'],
                           facts['round_programs'])
    return 1e3 * sec / trace['rounds'] if sec > 0 else None
