"""Share of its memory roofline that ``kv_pack_ragged`` reaches, in %: the
bytes its calls in the traced window must move (read and write of each
sequence's window, from the shapes, `counters.kv_pack_ragged_bytes`) over
the chip's HBM bandwidth, divided by the calls' device time.  Each call is
matched to the decode pass it ran in, for its batch; a fused decode pass
calls it twice (K and V) on every stage."""
from chipbench import counters


def read(run):
    tr = run.trace
    if tr is None or run.peaks is None:
        return None
    calls = [ev for name, evs in tr.modules.items()
             if name.startswith("jit_kv_pack_ragged") for ev in evs]
    if len(set(run.stage_layers)) != 1:
        return None                    # uneven stages: bytes per call unknown
    dec = [p for p in run.traced_passes() if p.kind == "decode"]
    nbytes = secs = 0.0
    for start, dur in calls:
        t = run.host_time(start)
        p = next((p for p in dec if p.t0 <= t <= p.t1), None)
        if p is None:
            continue
        nbytes += counters.kv_pack_ragged_bytes(
            run.sizes, run.stage_layers[0], p.batch, run.kv_pack_width)
        secs += dur
    if secs <= 0:
        return None
    return 100.0 * nbytes / run.peaks["hbm_bytes_per_s"] / secs
