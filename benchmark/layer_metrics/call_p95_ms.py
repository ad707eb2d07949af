"""Host clock per ``xgb.train`` continuation call in the window: the 95th
percentile, or the maximum while a window holds fewer than 20 calls (then no
sample lies beyond a 95th percentile)."""


def read(facts):
    calls = sorted(facts.get('call_s') or [])
    if not calls:
        return None
    if len(calls) < 20:
        return 1e3 * calls[-1]
    return 1e3 * calls[min(len(calls) - 1, int(0.95 * len(calls)))]
