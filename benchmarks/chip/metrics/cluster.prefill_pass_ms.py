"""Mean host time of a prefill pass in the window (a chunk-set pass, or an
admission's first chunk): from its first engine.step fire to its first
sampler return, or to the next pass's first fire when no prefill in it
completes (ms)."""


def read(run):
    pf = run.passes("prefill")
    if not pf:
        return None
    return 1e3 * sum(p.t1 - p.t0 for p in pf) / len(pf)
