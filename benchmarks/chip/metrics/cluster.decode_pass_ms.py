"""Mean host time of a fused decode pass in the window: from its first
engine.step fire to its first sampler return (ms)."""


def read(run):
    dec = run.passes("decode")
    if not dec:
        return None
    return 1e3 * sum(p.t1 - p.t0 for p in dec) / len(dec)
