"""Host clock around ``DMatrix(...)`` + ``binned(max_bin)`` + the first element
pulled back (and the eval set's ``DMatrix`` where the mix has one)."""


def read(facts):
    return facts.get('ingest_s')
