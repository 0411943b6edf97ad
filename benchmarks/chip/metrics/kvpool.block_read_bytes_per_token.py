"""Bytes read off the device's KV pages (the program's
``kvpool.block_read_bytes`` counter, its increase over the window) / output
tokens in the window (B/token).  In these cells every read is replication's:
each decode step reads the block it wrote, each completed prompt all of its
blocks, on every stage.  The counters reach the run only with ``--trace 1``;
None without them."""


def read(run):
    if run.counters is None or "kvpool.block_read_bytes" not in run.counters:
        return None
    n = run.tokens_out()
    return run.counters["kvpool.block_read_bytes"] / n if n else None
