"""Share of its roofline that the stage decode program reaches, in %: over
every ``jit_stage_decode_batch_paged`` execution in the traced window, the
least time the chip could take for the work each needs, over their device
time.  The least time of one execution is the larger of its FLOPs over the
chip's bf16 peak and its bytes over its HBM bandwidth, both from the
configuration's reference module (``stage_decode_cost``): the stage's layer
weights, the batch's embedding rows on the first stage, the final norm and
the head on the last, and K and V of each sequence's live context at the
stage's layers with the token written.  The live context, not the block
table's fixed width: a wider gather is waste that this share shows.

Each execution is matched to the decode pass it ran in, for the pass's
sequences and their contexts; a pass runs the program once per stage, in
stage order, and a pass with another count is left out.  None when no
execution matched."""

PROGRAM = "jit_stage_decode_batch_paged"


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    runs = sorted(tr.modules.get(PROGRAM, ()))
    n = len(run.stages)
    least = secs = 0.0
    for p in run.traced_passes():
        if p.kind != "decode":
            continue
        mine = [d for s, d in runs if p.t0 <= run.host_time(s) <= p.t1]
        if len(mine) != n:
            continue
        for i, ((lo, hi), dur) in enumerate(zip(run.stages, mine)):
            flops, nbytes = run.ref.stage_decode_cost(
                run.arch, lo, hi, p.keys, i == 0, i == n - 1)
            least += max(flops / run.peaks["bf16_flops_per_s"],
                         nbytes / run.peaks["hbm_bytes_per_s"])
            secs += dur
    if secs <= 0:
        return None
    return 100.0 * least / secs
