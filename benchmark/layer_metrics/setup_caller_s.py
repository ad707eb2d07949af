"""Seconds the thread that imported the program spent under none of its
phases between the end of the import and the end of the first ``train()``:
the caller's own code, here the benchmark's data generator and driver. Entry
``caller`` of the program's start-up report (``lib/startup.py``); None where
the program has none."""


def read(facts):
    from lib import startup
    return startup.seconds("caller")
