"""Device idle ms a round while the host was in the round driver: under
``round``, ``round/batch``, ``round/fused``, ``round/guard``,
``round/flush``, ``round/callbacks``, ``round/general`` or ``train/*``
(innermost span wins; ``round/eval*`` is ``idle_eval_ms``'s)."""


def read(facts):
    from lib.program_trace import idle_ms_under
    return idle_ms_under(facts, r"round(/(?!eval).*)?|train/.*")
