"""The collectives' share of their roofline: the least interconnect time
for the bytes the program's counter says a round exchanged
(``lib/mesh_work.py``: 2 (P - 1) / P x bytes over the chip's published
interconnect bandwidth) over ``mesh_allreduce_ms``. Latency-bound exchanges
read far under 100."""


def read(facts):
    from lib import mesh_trace, mesh_work
    ms = mesh_trace.allreduce_ms(facts)
    if not ms:
        return None
    return 100.0 * mesh_work.round_least_ici_seconds(facts) / (ms / 1e3)
