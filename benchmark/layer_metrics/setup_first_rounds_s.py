"""Seconds of the first call's ``round`` spans themselves: dispatch and the
wait for the device, with tracing, compiling and state set-up taken off. Phase
``round`` of the program's start-up report (``lib/startup.py``); None where the
program has none."""


def read(facts):
    from lib import startup
    return startup.seconds("round")
