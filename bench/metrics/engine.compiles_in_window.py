"""Programs compiled, or fetched from the persistent compilation cache,
inside the window (``jax.monitoring`` events). Every program the window
runs is compiled in set-up, so this should read 0."""


def read(v):
    return float(v.compiles)
