"""The trace reduction: busy-interval union, idle gaps attributed to the
host's annotated state, per-operation device time and program executions,
on synthetic planes and on a small trace recorded on a TPU v5e."""
import os
from types import SimpleNamespace as NS

import pytest

from conftest import HERE

from chipbench import tracefile

RECORDED = os.path.join(HERE, "data", "v5e_kv_pack.xplane.pb")


def ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3,
              stats=list(stats.items()))


def planes():
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.trace_window", 100, 1000),
        ev("bench.decode_pass", 100, 600),
        ev("bench.sampling", 700, 100),
        ev("bench.between_passes", 800, 300),
        ev("PjitFunction(f)", 150, 10)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            ev("%fusion.1 = bf16[8] fusion(x)", 50, 100),        # clipped
            ev("%while.7 = (s32[]) while(t)", 200, 100),         # container
            ev("%fusion.2 = bf16[8] fusion(y)", 200, 100),
            ev("%copy.3 = bf16[8] copy(z)", 250, 100),            # overlaps
            ev("%custom-call.4 = bf16[8] custom-call(w)", 900, 50),
        ]),
        NS(name="XLA Modules", events=[ev("jit_f(11)", 40, 360),
                                       ev("jit_kv_pack_ragged(3)", 900, 50)]),
    ])
    other = NS(name="/device:TPU:0 SparseCore 0", lines=[
        NS(name="XLA Ops", events=[ev("ignored", 100, 1000)])])
    return [host, dev, other]


def test_union_and_idle_intervals():
    assert tracefile.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert tracefile.union_length([]) == 0
    assert tracefile.idle_intervals([(1, 2), (1.5, 3)], 0, 4) == \
        [(0, 1), (3, 4)]


def test_reduction_of_synthetic_planes():
    s = tracefile.reduce_planes(planes())
    assert s.window == pytest.approx((100e-6, 1100e-6))
    # busy: [100,150] + [200,350] + [900,950] = 250 us
    assert s.busy_s == pytest.approx(250e-6) and s.devices == 1
    assert s.op_seconds == pytest.approx({
        "jit_f/fusion": 150e-6, "jit_f/copy": 100e-6,
        "jit_kv_pack_ragged/custom-call": 50e-6})
    # a program execution counts when it lies inside the traced window
    assert s.modules == {"jit_kv_pack_ragged": [
        pytest.approx((900e-6, 50e-6))]}
    # idle: 150-200 and 350-900 (decode pass / sampling), 950-1100 (between)
    names = dict((round(d * 1e6), n) for n, d in s.idle_gaps)
    assert names == {550: "decode_pass", 150: "between_passes",
                     50: "decode_pass"}


def test_no_window_or_no_device_gives_nothing():
    p = planes()
    assert tracefile.reduce_planes(p[1:]) is None          # no annotation
    assert tracefile.reduce_planes([p[0]]) is None         # no device


def test_recorded_v5e_trace():
    s = tracefile.reduce_file(RECORDED)
    assert s is not None and s.devices == 1
    assert 0 < s.busy_s < s.window_s
    calls = s.modules["jit_kv_pack_ragged"]
    assert len(calls) == 6
    assert all(d > 0 for _, d in calls)
    assert {n for n, _ in s.idle_gaps} <= {"decode_pass", "sampling",
                                           "between_passes", "prefill_pass",
                                           "unannotated"}
    assert any(k.startswith("jit_kv_pack_ragged") for k in s.op_seconds)
