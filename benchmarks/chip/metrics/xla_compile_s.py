"""XLA compile seconds in set-up, from JAX's compile events; a persistent
cache hit counts its retrieval time."""


def read(r):
    return r.xla_compile_s
