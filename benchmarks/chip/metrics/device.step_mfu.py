"""Model FLOPs of every pass that ran in the traced window (all prompt and
output tokens, `counters`), over (traced window x chips x the chip's bf16
peak), in %."""


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None or tr.window_s <= 0:
        return None
    flops = sum(p.flops for p in run.traced_passes())
    if flops <= 0:
        return None
    return 100.0 * flops / (tr.window_s * tr.devices
                            * run.peaks["bf16_flops_per_s"])
