"""DejaVuLib's KV replication per decode pass in the traced window, its own
time only (ms): the self time of the program's ``dejavulib.replicate``
spans, summed over stages, less the part of each span in which the device
ran a program other than replication's block read (``jit__read_blocks``).

The span opens with the pass's first read from the device
(``StageWorker.read_blocks``), which waits for the stage programs and the
page write-back that the pass queued before it; that wait is the stage
worker's device time, not replication's, so it is taken out.  What is left
is the host's work, the block read on the device and the copies between
device and host.  The spans reach the run only with ``--trace 1``, from the
tracer the harness installs while the profiler runs, and the device's
programs from the trace (mapped onto the host clock with
``run.host_time``); None without either."""
from chipbench import spans

OWN = ("jit__read_blocks",)


def read(run):
    if run.spans is None or run.trace is None:
        return None
    others = [(run.host_time(t), run.host_time(t + d))
              for name, execs in run.trace.modules.items() if name not in OWN
              for t, d in execs]
    dec = [p for p in run.traced_passes() if p.kind == "decode"]
    return spans.per_pass_ms(run.spans, dec, ["dejavulib.replicate"],
                             less=others)
