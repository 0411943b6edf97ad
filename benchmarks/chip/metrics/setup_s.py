"""Process start to window open (host clock): interpreter and JAX start-up,
weights, the warm-up with its compilations or cache loads, and building the
measured engine."""


def read(run):
    return run.setup_s
