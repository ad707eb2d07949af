"""Device self ms a traced round of the round programs' ops under a
``mesh.<what>`` scope (the row-split collectives: ``hist_psum``,
``root_psum``, ``scale_pmax``), on the first device plane
(``lib/mesh_trace.py``)."""


def read(facts):
    from lib import mesh_trace
    return mesh_trace.allreduce_ms(facts)
