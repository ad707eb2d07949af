"""Share of the device's idle time in the traced interval under no program
span: 100 when there is idle time and no span, 0 when there is no idle
time."""


def read(facts):
    from lib.program_trace import idle_seconds
    idle = idle_seconds(facts)
    if idle is None:
        return None
    total = sum(idle.values())
    return 100.0 * idle.get("", 0.0) / total if total > 0 else 0.0
