"""Seconds the first call spent making its state before a round could be
dispatched: booster and objective set-up, the training cache entry (or the
sharded state under a mesh), labels and margin onto the device, and the
ranking layout. Phases ``train/state`` + ``rank/layout`` of the program's
start-up report (``lib/startup.py``); None where the program has none."""


def read(facts):
    from lib import startup
    return startup.seconds("train/state", "rank/layout")
