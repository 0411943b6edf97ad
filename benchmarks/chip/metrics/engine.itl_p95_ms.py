"""95th percentile of every gap between consecutive tokens of one request,
over all requests, with both tokens in the window (host clock, ms).

Per layer, with no bound: a window of ~100 gaps comes from ~27 passes of
up to 4 tokens, so one pass that the host stalls moves the tail by a
third (PERF.md, section 2)."""
from chipbench.window import percentile


def read(run):
    gaps = run.gaps()
    return percentile(gaps, 95) * 1e3 if gaps else None
