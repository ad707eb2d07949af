"""Share of the round programs' op self time under no ``xtpu.`` scope. The
five ``stage_*_ms`` add up to ``round_program_ms`` less this share."""


def read(facts):
    from lib.program_trace import stage_group_seconds
    sec = stage_group_seconds(facts)
    if sec is None:
        return None
    total = sum(sec.values())
    return 100.0 * sec[""] / total if total > 0 else 0.0
