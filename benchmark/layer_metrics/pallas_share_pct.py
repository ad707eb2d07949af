"""Share of the round programs' device time spent in Mosaic (Pallas) kernels:
the self time of the ops whose name carries
``custom_call_target="tpu_custom_call"``, which a TPU trace marks apart from
XLA fusions, over the round programs' device time. A share of time and not
of a peak: a schedule that runs no Mosaic kernel reads 0."""


MARK = 'custom_call_target="tpu_custom_call"'


def read(facts):
    from lib.trace_reduce import matching_seconds
    trace = facts.get('trace')
    if not trace:
        return None
    kernels = sum(s for name, s in trace['op_self'].items() if MARK in name)
    rounds = matching_seconds(trace['programs'],
                           facts['round_programs'])
    if rounds <= 0:
        return None
    return 100.0 * kernels / rounds
