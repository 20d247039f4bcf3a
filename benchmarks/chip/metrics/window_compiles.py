"""Backend compilations inside the window (jax.monitoring events)."""


def read(run):
    return run.window_compiles
