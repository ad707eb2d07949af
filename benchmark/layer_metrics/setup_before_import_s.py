"""Seconds from the start of the process to the first line of
``xgboost_tpu/__init__.py``: the interpreter, the caller's own imports, and
``import jax`` with the backend's start where the caller does them first, as
``run.py`` does. Phase ``before_import`` of the program's start-up report
(``lib/startup.py``); None where the program has none, or could not read the
process's start time."""


def read(facts):
    from lib import startup
    rep = startup.report()
    return None if rep is None else rep.get("before_import")
