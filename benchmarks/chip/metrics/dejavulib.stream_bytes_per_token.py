"""Bytes that DejaVuLib moved over every link kind of the engine in the
window (``transfer_summary()`` at its close minus at its open) / output
tokens in the window (B/token)."""


def read(run):
    n = run.tokens_out()
    return run.stream_bytes / n if n else None
