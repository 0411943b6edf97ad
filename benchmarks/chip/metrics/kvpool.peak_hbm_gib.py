"""The device's peak bytes in use (``memory_stats()['peak_bytes_in_use']``)
read after the window, in GiB: weights, per-pass dense caches and, once the
pool moves to HBM, the pool."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 2**30
