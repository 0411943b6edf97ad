"""The program's spans reduced to per-layer numbers (`chipbench.spans`):
self time per decode pass, byte sums, window totals and the unspanned
share of the device's idle time, on a synthetic tracer snapshot and
synthetic profiler planes; and the annotations leave the trace reduction's
outputs as they were."""
from types import SimpleNamespace as NS

import pytest

from chipbench import spans, tracefile
from chipbench.window import Pass

MS = 1_000_000                         # ns


def x(eid, name, ts_ms, dur_ms, parent=None, **args):
    e = {"eid": eid, "name": name, "ph": "X", "ts": int(ts_ms * MS),
         "dur": int(dur_ms * MS)}
    if parent is not None:
        e["parent"] = parent
    if args:
        e["args"] = args
    return e


def snapshot():
    """Two decode passes of two stages (1000-1100 and 1200-1300 ms) and a
    prefill pass (1400-1500 ms) in between them and the window's end."""
    evs = []
    eid = 0
    for p0, kind in ((1000, "fused_decode"), (1200, "fused_decode"),
                     (1400, "chunkset")):
        pas = eid
        evs.append(x(pas, "pass", p0, 100, kind=kind))
        eid += 1
        for wid, s0 in ((0, p0), (1, p0 + 40)):
            evs += [x(eid, "worker.gather", s0, 20, pas, wid=wid, n=2),
                    x(eid + 1, "worker.upload", s0 + 20, 5, pas, wid=wid,
                      bytes=1000),
                    x(eid + 2, "worker.stage", s0 + 25, 1, pas, wid=wid),
                    x(eid + 3, "worker.readback", s0 + 26, 10, pas,
                      wid=wid, bytes=10)]
            eid += 4
        evs.append(x(eid, "dejavulib.replicate", p0 + 80, 15, pas,
                     bytes=64))
        eid += 1
    evs.append({"eid": eid, "name": "sched.plan", "ph": "I",
                "ts": 1000 * MS})
    return {"schema": "repro.trace/v1", "capacity": 64,
            "tracks": {"serve": {"events": evs, "dropped": 0,
                                 "emitted": len(evs)}}}


def passes():
    return [Pass("decode", 1.0, 1.1), Pass("decode", 1.2, 1.3)]


def test_self_times_subtract_children():
    sp = spans.from_snapshot(snapshot())
    assert [s.name for s in sp[:3]] == ["pass", "worker.gather",
                                        "worker.upload"]
    pas = sp[0]
    # 100 ms minus 2 x (20 + 5 + 1 + 10) minus 15
    assert pas.self_s == pytest.approx(0.013)
    assert sp[1].self_s == pytest.approx(0.020)
    assert sp[1].args == {"wid": 0, "n": 2}
    assert all(s.name != "sched.plan" for s in sp)      # instants left out


def test_per_pass_means_sum_the_stages():
    sp = spans.from_snapshot(snapshot())
    dec = passes()
    assert spans.per_pass_ms(sp, dec, ["worker.gather"]) == \
        pytest.approx(40.0)
    assert spans.per_pass_ms(sp, dec, ["worker.upload",
                                       "worker.readback"]) == \
        pytest.approx(30.0)
    assert spans.per_pass_ms(sp, dec, ["dejavulib.replicate"]) == \
        pytest.approx(15.0)
    # the parts and the pass's own remainder make up the pass
    names = ["worker.gather", "worker.upload", "worker.stage",
             "worker.readback", "dejavulib.replicate", "pass"]
    assert spans.per_pass_ms(sp, dec, names) == pytest.approx(100.0)
    assert spans.per_pass_ms(sp, dec, ["kvpool.append"]) is None
    assert spans.per_pass_ms(sp, [], ["worker.gather"]) is None


def test_replicate_ms_leaves_out_the_device_time_of_other_programs():
    """`dejavulib.replicate_ms` reads the replicate spans' self time less
    the part in which the device ran a program other than the block read:
    the wait at the span's first read is the stage worker's time."""
    from chipbench import spec
    from chipbench.harness import Run
    from chipbench.tracefile import TraceSummary

    sp = spans.from_snapshot(snapshot())
    # the trace's clock is the host's minus 1 s; the first pass's span
    # (1080-1095 ms) waits 5 ms for the stage program and 2 ms for the
    # write-back, then reads its blocks (its own program, kept)
    modules = {"jit_stage_decode_batch_paged": [(0.070, 0.015)],
               "jit__write_window": [(0.085, 0.002)],
               "jit__read_blocks": [(0.087, 0.002), (0.281, 0.002)]}
    tr = TraceSummary(window=(0.0, 1.0), busy_s=0.1, devices=1,
                      modules=modules)
    run = Run(cell=None, ref=None, arch={}, rec=NS(passes=passes()),
              window_s=1.0, t_open=1.0, t_close=2.0, setup_s=1.0,
              clients=2, stages=[(0, 1), (1, 2)], trace=tr, trace_t0=1.0,
              trace_t1=2.0, spans=sp)
    read = spec.reader("dejavulib.replicate_ms")
    assert read(run) == pytest.approx((15.0 - 7.0 + 15.0) / 2)
    assert spans.overlap(1.080, 1.095, [(1.07, 1.085), (1.084, 1.087),
                                        (1.2, 1.3)]) == pytest.approx(0.007)
    run.trace = None
    assert read(run) is None
    run.trace, run.spans = tr, None
    assert read(run) is None


def test_byte_sums_and_window_totals():
    sp = spans.from_snapshot(snapshot())
    # the window holds both decode passes and the chunk-set pass
    assert spans.arg_sum(sp, "worker.upload", "bytes", 1.0, 1.5) == 6000
    assert spans.arg_sum(sp, "worker.upload", "bytes", 1.0, 1.3) == 4000
    assert spans.arg_sum(sp, "worker.stage", "bytes", 1.0, 1.5) is None
    tot = spans.totals(sp, 1.0, 1.5)
    assert tot["worker.gather"] == (pytest.approx(0.120), 6)
    assert tot["pass"][1] == 3
    line = spans.totals_line(sp, 1.0, 1.5)
    assert line.startswith("window spans (name:self ms/count): ")
    assert "worker.gather:120.0ms/6" in line
    assert spans.totals_line(sp, 5.0, 6.0).endswith("none")


def ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3)


def traced_planes(with_spans: bool):
    host = [ev("bench.trace_window", 100, 1000),
            ev("bench.decode_pass", 100, 900),
            ev("bench.between_passes", 1000, 100)]
    if with_spans:
        host += [ev("repro.pass", 100, 900),
                 ev("repro.worker.gather", 150, 300),
                 ev("repro.worker.upload", 450, 100),
                 ev("repro.worker.readback", 800, 150)]
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("%fusion.1 = bf16[8] fusion(x)",
                                      550, 250)]),
        NS(name="XLA Modules", events=[ev("jit_stage_decode_batch(1)", 550,
                                          250)])])
    return [NS(name="/host:CPU", lines=[NS(name="python3", events=host)]),
            dev]


def test_annotations_and_unspanned_idle_share():
    planes = traced_planes(True)
    ann = spans.annotations(planes)
    assert [a[2] for a in ann] == ["pass", "worker.gather", "worker.upload",
                                   "worker.readback"]
    assert ann[1][:2] == pytest.approx((150e-6, 450e-6))
    lo, hi = 100e-6, 1100e-6
    idle = tracefile.idle_intervals([(550e-6, 800e-6)], lo, hi)
    # idle 100-550 and 800-1100 (750 us); spans cover 150-550 and 800-950
    # (550 us); the pass frame does not count
    assert spans.unspanned_share(idle, ann) == pytest.approx(
        100.0 * 200 / 750)
    assert spans.unspanned_share(idle, []) == pytest.approx(100.0)
    assert spans.unspanned_share([], ann) is None


def test_span_annotations_leave_the_trace_reduction_unchanged():
    plain = tracefile.reduce_planes(traced_planes(False))
    spanned = tracefile.reduce_planes(traced_planes(True))
    assert spanned == plain
    assert spanned.op_seconds == pytest.approx(
        {"jit_stage_decode_batch/fusion": 250e-6})


def test_traced_run_keeps_the_program_spans_and_counters():
    """A CPU serve through the harness: with --trace 1 the run holds the
    spans of the traced part of the window and the telemetry counters'
    increase over the window; with --trace 0 neither, and no tracer stays
    installed after either."""
    import time

    from conftest import tiny_cell

    from chipbench import harness, spec
    from chipbench.tracefile import TraceSummary
    from repro.core import tracing

    def run(trace):
        cell = tiny_cell()
        cell.config["limits"] = {"widest_gap": 0.5, "logit_error": 0.1}
        out = harness.run_cell(cell, 21, 1.5, trace,
                               t_process=time.perf_counter(),
                               require_chip=False, log=lambda m: None)
        assert tracing.current() is None
        return out["_run"]

    traced = run(True)
    assert traced.trace_t0 is not None and traced.spans
    names = {s.name for s in traced.spans}
    assert {"dejavulib.replicate", "worker.stage"} <= names
    assert all(traced.trace_t0 <= s.t0 and s.t1 <= traced.trace_t1 + 1.0
               for s in traced.spans)
    dec = [p for p in traced.traced_passes() if p.kind == "decode"]
    assert dec and all(len(p.keys) == p.batch for p in dec)
    # the CPU leaves no device plane in the trace: with an empty one, no
    # other program's time comes out of the replicate spans
    replicate_ms = spec.reader("dejavulib.replicate_ms")
    assert replicate_ms(traced) is None
    traced.trace = TraceSummary(window=(0.0, 1.0), busy_s=0.0, devices=1)
    ms = replicate_ms(traced)
    assert ms > 0 and ms == spans.per_pass_ms(traced.spans, dec,
                                              ["dejavulib.replicate"])
    # blocks read off the pages to replicate them, in the window only
    assert traced.counters["kvpool.block_reads"] > 0
    assert all(v >= 0 for v in traced.counters.values())
    per_token = spec.reader("kvpool.block_read_bytes_per_token")(traced)
    assert per_token == traced.counters["kvpool.block_read_bytes"] \
        / traced.tokens_out() > 0

    plain = run(False)
    assert plain.spans is None and plain.counters is None
    assert replicate_ms(plain) is None
    assert spec.reader("kvpool.block_read_bytes_per_token")(plain) is None
