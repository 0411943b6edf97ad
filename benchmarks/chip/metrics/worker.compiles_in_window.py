"""XLA compilations (persistent-cache loads included) inside the window, from
JAX's monitoring events.  The warm-up is built so that this reads 0."""


def read(run):
    return run.compiles_in_window
