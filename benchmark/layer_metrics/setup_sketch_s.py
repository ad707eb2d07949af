"""Seconds in the quantile sketch: ``sketch_matrix`` on the in-memory path,
the iterator's first pass (less its waits for data) on the other. Phase
``ingest/sketch`` of the program's start-up report (``lib/startup.py``); None
where the program has none."""


def read(facts):
    from lib import startup
    return startup.seconds("ingest/sketch")
