"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, idle gaps attributed to what the host was
doing, per-operation device time, and the executions of named programs.

The benchmark opens a ``bench.trace_window`` TraceAnnotation over the
traced part of the window and, inside it, one annotation at a time naming
the host's state: ``bench.decode_pass``, ``bench.prefill_pass``,
``bench.sampling`` or ``bench.between_passes``.  Device operations are the
events of the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane; program
executions those of its ``XLA Modules`` line.  All times in a trace file
share one clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.trace_window"
# operations whose interval holds other operations' (counted once in busy
# time, left out of the per-operation totals)
_CONTAINERS = {"while", "conditional", "call"}
STATE_PREFIX = "bench."


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def union_length(intervals: Sequence[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    start = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            if end is not None:
                total += end - start
            start, end = a, b
        else:
            end = max(end, b)
    if end is not None:
        total += end - start
    return total


def idle_intervals(busy: Sequence[Tuple[float, float]], lo: float,
                   hi: float) -> List[Tuple[float, float]]:
    """The parts of [lo, hi] that no busy interval covers."""
    out, cur = [], lo
    for a, b in sorted(busy):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def _op_family(name: str) -> str:
    """An operation's kind from its event name, which on a TPU is the HLO
    instruction ('%fusion.12 = bf16[...] fusion(...)' -> 'fusion')."""
    m = re.match(r"%?([A-Za-z_][\w-]*?)(?:\.\d+)*(?:\s|=|$)", name)
    return m.group(1) if m else name[:40]


def _module_name(name: str) -> str:
    """'jit_kv_pack_ragged(1234)' -> 'jit_kv_pack_ragged'."""
    return re.sub(r"\(\d+\)$", "", name)


@dataclass
class TraceSummary:
    window: Tuple[float, float]                  # seconds, trace clock
    busy_s: float                                # mean over devices
    devices: int
    op_seconds: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    modules: Dict[str, List[Tuple[float, float]]] = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def _host_annotations(planes) -> Tuple[Optional[Tuple[float, float]],
                                       List[Tuple[float, float, str]]]:
    window, states = None, []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(STATE_PREFIX):
                    continue
                a = ev.start_ns * 1e-9
                b = a + ev.duration_ns * 1e-9
                if ev.name == WINDOW:
                    window = (a, b)
                else:
                    states.append((a, b, ev.name[len(STATE_PREFIX):]))
    return window, sorted(states)


def _state_at(states, t: float) -> str:
    for a, b, name in states:
        if a <= t <= b:
            return name
    return "unannotated"


def reduce_planes(planes, top: int = 10) -> Optional[TraceSummary]:
    """The summary of one trace's planes, or None when it holds no traced
    window or no device operation."""
    planes = list(planes)
    window, states = _host_annotations(planes)
    if window is None:
        return None
    lo, hi = window
    per_device, ops, modules = [], {}, {}
    first_busy = None
    for plane in planes:
        if not re.match(r"/device:TPU:\d+$", plane.name):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        runs = sorted((ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns)
                       * 1e-9, _module_name(ev.name))
                      for ev in lines.get("XLA Modules", ()))
        starts = [r[0] for r in runs]
        for a, b, name in runs:
            if lo <= a and b <= hi:
                modules.setdefault(name, []).append((a, b - a))
        busy = []
        for ev in lines.get("XLA Ops", ()):
            a = ev.start_ns * 1e-9
            b = a + ev.duration_ns * 1e-9
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            busy.append((a, b))
            fam = _op_family(ev.name)
            if fam in _CONTAINERS:
                continue
            i = bisect.bisect_right(starts, a) - 1
            mod = runs[i][2] if i >= 0 and a <= runs[i][1] else "?"
            key = f"{mod}/{fam}"
            ops[key] = ops.get(key, 0.0) + (b - a)
        if busy:
            per_device.append(union_length(busy))
            if first_busy is None:
                first_busy = busy
    if not per_device:
        return None
    gaps = sorted(((b - a, _state_at(states, (a + b) / 2))
                   for a, b in idle_intervals(first_busy, lo, hi)),
                  reverse=True)
    return TraceSummary(
        window=window, busy_s=sum(per_device) / len(per_device),
        devices=len(per_device),
        op_seconds=dict(sorted(ops.items(), key=lambda kv: -kv[1])[:top]),
        idle_gaps=[(name, secs) for secs, name in gaps[:top]],
        modules=modules)


def reduce_file(path: str, top: int = 10) -> Optional[TraceSummary]:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes, top)
