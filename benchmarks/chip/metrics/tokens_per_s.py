"""Output tokens first emitted in the window / the window's length (host
clock; the window holds whole passes, so its length is at least the run's
seconds).  A token regenerated after a rollback is not new output."""


def read(run):
    return run.tokens_out() / run.window_s
