"""Jit entry: compiles and persistent-cache loads JAX reported inside the
window (``jax.monitoring`` events); the warm set should leave none."""


def read(rec):
    return rec.compiles_in_window
