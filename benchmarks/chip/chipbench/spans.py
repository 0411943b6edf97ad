"""The program's own spans (``repro.core.tracing``, host clock) reduced to
per-layer numbers: self time per span name in each decode pass, bytes per
output token, and the share of the device's idle time that no span
explains.  Pure functions over a tracer snapshot (``repro.trace/v1``) and
the window's passes; the tests drive them with synthetic spans.

A tracer on the host clock stamps spans with ``time.perf_counter_ns``, the
clock of `window.Recorder` (``time.perf_counter``), so a span is joined to
the pass its start lies in with no conversion.  The profiler trace holds
the same spans as ``repro.<name>`` annotations on its own clock, which is
the one its device operations use.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from chipbench.tracefile import union_length

# spans that frame a pass rather than a layer of it
FRAMES = ("round", "pass")
ANNOTATION_PREFIX = "repro."


@dataclass
class Span:
    name: str
    t0: float                          # seconds, host clock
    t1: float
    self_s: float                      # t1 - t0 minus its children's
    args: Dict[str, object] = field(default_factory=dict)


def from_snapshot(snapshot: dict) -> List[Span]:
    """The complete spans of a tracer snapshot's serve track, in start
    order, each with its self time."""
    evs = [e for e in snapshot["tracks"].get("serve", {}).get("events", ())
           if e["ph"] == "X"]
    child_ns: Dict[int, int] = {}
    for e in evs:
        if "parent" in e:
            child_ns[e["parent"]] = child_ns.get(e["parent"], 0) + e["dur"]
    out = [Span(e["name"], e["ts"] * 1e-9, (e["ts"] + e["dur"]) * 1e-9,
                (e["dur"] - child_ns.get(e["eid"], 0)) * 1e-9,
                dict(e.get("args", {})))
           for e in evs]
    return sorted(out, key=lambda s: s.t0)


def _in(spans: Iterable[Span], t0: float, t1: float) -> List[Span]:
    return [s for s in spans if t0 <= s.t0 <= t1]


def per_pass_ms(spans: Sequence[Span], passes, names: Sequence[str],
                less: Sequence[Tuple[float, float]] = ()
                ) -> Optional[float]:
    """Mean over `passes` (objects with ``t0``/``t1``, host seconds) of the
    self time of the spans named `names` that start inside each, summed
    over stages (ms); None without passes or without any such span.  The
    time that each span's interval shares with the intervals `less` (host
    seconds) is taken out of it: meant for spans with no children, whose
    self time is their whole interval."""
    if not passes:
        return None
    total, seen = 0.0, False
    for p in passes:
        for s in _in(spans, p.t0, p.t1):
            if s.name in names:
                total += s.self_s - overlap(s.t0, s.t1, less)
                seen = True
    return 1e3 * total / len(passes) if seen else None


def overlap(t0: float, t1: float,
            intervals: Sequence[Tuple[float, float]]) -> float:
    """Length of [t0, t1] that the union of `intervals` covers."""
    return union_length([(max(a, t0), min(b, t1)) for a, b in intervals
                         if b > t0 and a < t1])


def arg_sum(spans: Sequence[Span], name: str, key: str, t0: float,
            t1: float) -> Optional[int]:
    """Sum of argument `key` over the spans named `name` that start in
    [t0, t1]; None when there is none."""
    vals = [int(s.args[key]) for s in _in(spans, t0, t1)
            if s.name == name and key in s.args]
    return sum(vals) if vals else None


def totals(spans: Sequence[Span], t0: float, t1: float
           ) -> Dict[str, Tuple[float, int]]:
    """{name: (self seconds, count)} of the spans that start in [t0, t1]."""
    out: Dict[str, Tuple[float, int]] = {}
    for s in _in(spans, t0, t1):
        secs, n = out.get(s.name, (0.0, 0))
        out[s.name] = (secs + s.self_s, n + 1)
    return out


def totals_line(spans: Sequence[Span], t0: float, t1: float) -> str:
    """One log line: each span name's self time and count in the window."""
    tot = totals(spans, t0, t1)
    body = " ".join(f"{name}:{secs * 1e3:.1f}ms/{n}"
                    for name, (secs, n) in sorted(tot.items()))
    return f"window spans (name:self ms/count): {body or 'none'}"


def annotations(planes) -> List[Tuple[float, float, str]]:
    """(start s, end s, span name) of every ``repro.`` annotation on the
    host planes of a profiler trace, on the trace's clock."""
    out = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(ANNOTATION_PREFIX):
                    a = ev.start_ns * 1e-9
                    out.append((a, a + ev.duration_ns * 1e-9,
                                ev.name[len(ANNOTATION_PREFIX):]))
    return sorted(out)


def unspanned_share(idle: Sequence[Tuple[float, float]],
                    ann: Sequence[Tuple[float, float, str]]
                    ) -> Optional[float]:
    """Share of the device's idle intervals that no annotation other than
    a frame (``round``, ``pass``) covers, in %; None without idle time."""
    idle_s = sum(b - a for a, b in idle)
    if idle_s <= 0:
        return None
    layers = [(a, b) for a, b, name in ann if name not in FRAMES]
    covered = sum(overlap(lo, hi, layers) for lo, hi in idle)
    return 100.0 * (1.0 - covered / idle_s)
