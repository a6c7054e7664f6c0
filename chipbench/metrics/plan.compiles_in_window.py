"""Backend compiles inside the window, from ``jax.monitoring``."""


def read(run):
    return run.compiles_in_window
