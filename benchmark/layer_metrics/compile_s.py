"""Backend-compile seconds that jax's own monitoring events summed during
set-up: compilation on a cold cache, loading from the cache on a warm one."""


def read(facts):
    return facts.get('compile_s')
