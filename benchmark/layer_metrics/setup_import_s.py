"""Seconds ``import xgboost_tpu`` took, first to last line of its
``__init__.py``, less what was booked to another phase while it ran. Phase
``import`` of the program's start-up report (``lib/startup.py``); None where
the program has none."""


def read(facts):
    from lib import startup
    return startup.seconds("import")
