"""JIT: programs built inside the measured window -- XLA backend
compiles plus loads from the persistent compile cache, counted by a
``jax.monitoring`` listener the harness registers."""


def read(run):
    return run.compiles
