"""Seconds ``DataIter.collect()`` waited in the caller's ``next()``, both
passes, retries included: the data source's time, not the program's. Phase
``ingest/next`` of the program's start-up report (``lib/startup.py``); None
where the program has none."""


def read(facts):
    from lib import startup
    return startup.seconds("ingest/next")
