"""Seconds binning the matrix and putting it on the device. Phases
``ingest/bin`` + ``ingest/upload`` of the program's start-up report
(``lib/startup.py``): in memory past 2M rows the uploads run on a thread of
their own and the wait for them counts under ``ingest/bin``; by iterator the
second pass less its waits for data, then ``place_binned``. None where the
program has none."""


def read(facts):
    from lib import startup
    return startup.seconds("ingest/bin", "ingest/upload")
