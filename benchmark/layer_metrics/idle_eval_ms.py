"""Device idle ms a round while the host was under ``round/eval`` or
``round/eval/pull``: the eval dispatches' launch gaps and the pull of the
metric partials."""


def read(facts):
    from lib.program_trace import idle_ms_under
    return idle_ms_under(facts, r"round/eval(/.*)?")
