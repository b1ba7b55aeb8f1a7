"""Device / compile: programs compiled, or loaded from the persistent
compilation cache, after set-up ended (JAX's backend-compile events)."""


def read(r):
    return float(r.compiles)
