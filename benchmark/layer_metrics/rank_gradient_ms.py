"""Device ms a round of the ranking gradient program (the mix's
``gradient_program``): its executions' device time in the traced interval
over the rounds traced."""


def read(facts):
    from lib.trace_reduce import matching_seconds
    trace, prog = facts.get('trace'), facts.get('gradient_program')
    if not trace or not trace['rounds'] or not prog:
        return None
    sec = matching_seconds(trace['programs'], [prog])
    return 1e3 * sec / trace['rounds'] if sec > 0 else None
