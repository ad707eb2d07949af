"""Programs this process compiled where a persistent-cache entry would have
served: compiles that jax then wrote to the cache, all programs, up to the
moment of reading (``xtpu_program_cache_misses_total``: ``lib/startup.py``).
0 on a cache-served run, which is what tells a compiling run's ``setup_s`` from
a served one's. (``xtpu_program_compiles_total`` less
``xtpu_program_cache_hits_total`` does not: the few dozen small programs under
jax's 1 s threshold are compiled in every process and never cached.) None where
the program lacks the counter."""


def read(facts):
    from lib import startup
    return startup.compiled_programs()
