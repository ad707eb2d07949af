"""Share of the start-up report's ``total`` under no phase of its own: the
self time of the containers ``ingest`` and ``train/call``
(``100 x unattributed / total``). Prints the run's whole report to stderr as
one line, with the compile path's seconds and ``native/build``
(``lib/startup.py``). None where the program has no report."""


def read(facts):
    import sys
    from lib import startup
    rep = startup.report()
    if rep is None or not rep.get("total"):
        return None
    print("[bench] start-up report: " + startup.line(
        rep, facts.get("round_programs") or ()), file=sys.stderr, flush=True)
    return 100.0 * rep.get("unattributed", 0.0) / rep["total"]
