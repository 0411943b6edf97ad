"""Per-request causal tracing: a bounded flight recorder on one clock.

The telemetry registry (`repro.core.telemetry`) deliberately collapses
spans to path-keyed aggregates and never keeps individual events, so it
can answer "what is TTFT p99?" but not "why did request 17's TTFT hit
p99?".  This module is the complementary layer: it records *individual*
events and spans with causal parent links and request/sequence IDs, so a
single run can be replayed into a per-request critical-path breakdown
(`tools/trace_report.py`), exported to Perfetto (`repro.core.exporters`),
or split by layer beside a device trace.

**The clock.**  A `Tracer` reads one clock, chosen at construction, in
integer nanoseconds:

* ``host_ns`` (the default, ``time.perf_counter_ns``): what the program
  really spends.  It is the clock of `time.perf_counter`, which the
  on-chip benchmark's recorder reads, so spans line up with its passes
  with no conversion.  Every thread reads it: an event from the DejaVuLib
  streamer thread is stamped with the host time it fires.  A caller's
  ``dur_ns`` is a modeled duration, so on this clock it is kept as the
  argument ``model_ns`` and the event is an instant.
* ``modeled_ns``: the telemetry registry's ``clock_s``, which
  ``telemetry.advance(cm.*)`` moves — the CPU cost model's clock, for the
  callers that replay it (`benchmarks/failures.py` and its
  ``trace_report --assert`` gate).  It is read only on the serving
  thread.  Events fired from other threads never read it: they land on
  their own *track*, where each event's timestamp is the track's running
  cursor (the accumulated modeled duration of the events before it) —
  the streamer FIFO serializes its tasks, so per-track order and cursors
  are reproducible.

Guarantees:

1. **Determinism, on the modeled clock.**  Two identical runs produce
   byte-identical dumps; the dump keeps each track's own order and never
   merges across tracks.  On the host clock names, nesting, per-track
   order and arguments repeat; times do not.
2. **Near-free when disabled.**  Call sites use the module helpers
   (`event`, `span`, `active`), a single ``is None`` check when no
   tracer is installed — the same pattern as `telemetry` and
   `dejavulib.faults` (micro-benchmarked in
   ``benchmarks/streaming_breakdown.py``).  Call sites build costly
   arguments (byte sums, rid lists) only when `active()`.
3. **Bounded memory, no silent truncation.**  Each track is a
   fixed-capacity ring buffer that overwrites its oldest events
   (flight-recorder semantics); the snapshot reports explicit
   ``dropped`` and ``emitted`` counters per track so a truncated dump
   is always visibly truncated.
4. **One clock with the device trace.**  Every span also opens a
   ``jax.profiler.TraceAnnotation`` named ``repro.<span name>``: while
   the JAX profiler runs, each span lands on the host plane of its
   ``.xplane.pb`` on the device trace's own clock, whichever clock the
   tracer reads.

Cross-thread rules: span open/close happens on the owner (serving)
thread only — `Tracer.span` raises off-thread, mirroring the telemetry
thread-affinity guard.  `event()` is safe from any thread; non-owner
threads are routed to the ``streamer`` track automatically.

The snapshot is a versioned, JSON-stable schema (``repro.trace/v1``):

```json
{"schema": "repro.trace/v1",
 "capacity": 65536,
 "tracks": {"serve": {"events": [{"eid": 3, "name": "pass", "ph": "X",
                                  "ts": 120000, "dur": 80000,
                                  "parent": 2, "rid": 17, "seq": 4,
                                  "args": {"kind": "fused_decode"}}],
                      "dropped": 0, "emitted": 4}}}
```

``ph`` follows the Chrome trace-event phases the Perfetto exporter
emits: ``"X"`` complete span (``ts`` + ``dur``), ``"I"`` instant.
"""
from __future__ import annotations

import json
import threading
import time
from contextlib import contextmanager
from typing import Callable, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

from repro.core import telemetry

SCHEMA = "repro.trace/v1"

#: default per-track ring capacity (events); generous enough that the CI
#: workloads never drop, small enough to bound a runaway run's memory
DEFAULT_CAPACITY = 1 << 16

_NS = 1_000_000_000

#: the prefix of every span's profiler annotation
ANNOTATION_PREFIX = "repro."

#: the host clock (the default)
host_ns: Callable[[], int] = time.perf_counter_ns


def modeled_ns() -> int:
    """The cost model's clock: the installed telemetry registry's
    ``clock_s`` (0 without one).  Serving thread only."""
    t = telemetry.current()
    return 0 if t is None else int(round(t.clock_s * _NS))


#: the track serving-thread events land on by default
SERVE_TRACK = "serve"
#: the track non-owner-thread events are routed to automatically
STREAM_TRACK = "streamer"


class _Track:
    """One ring buffer: fixed capacity, oldest-overwritten, counted drops."""

    __slots__ = ("name", "capacity", "events", "head", "next_eid",
                 "dropped", "emitted", "cursor_ns")

    def __init__(self, name: str, capacity: int):
        self.name = name
        self.capacity = capacity
        self.events: List[dict] = []
        self.head = 0              # index of the OLDEST event once full
        self.next_eid = 0
        self.dropped = 0
        self.emitted = 0
        self.cursor_ns = 0         # running end-time for clock-less threads

    def append(self, ev: dict) -> None:
        self.emitted += 1
        if len(self.events) < self.capacity:
            self.events.append(ev)
            return
        self.events[self.head] = ev    # overwrite the oldest (flight recorder)
        self.head = (self.head + 1) % self.capacity
        self.dropped += 1

    def chronological(self) -> List[dict]:
        return self.events[self.head:] + self.events[:self.head]


class Tracer:
    """The flight recorder: per-track rings + causal span stack.

    One tracer == one run (or one aggregation window).  All mutation is
    lock-protected; the owner thread is bound at construction and
    re-bound by :func:`install`, exactly like the telemetry registry.
    `clock` is ``host_ns`` (default) or ``modeled_ns`` (module docstring).
    """

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 clock: Callable[[], int] = host_ns):
        self.capacity = capacity
        self.clock = clock
        self.modeled = clock is modeled_ns
        self._lock = threading.Lock()
        self._tracks: Dict[str, _Track] = {}
        self._tls = threading.local()
        self._owner = threading.get_ident()

    # -- internals -----------------------------------------------------
    def _track(self, name: str) -> _Track:
        tr = self._tracks.get(name)
        if tr is None:
            tr = self._tracks[name] = _Track(name, self.capacity)
        return tr

    def _stack(self) -> List[int]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    @staticmethod
    def _mkev(eid: int, name: str, ph: str, ts: int, dur: int,
              parent: Optional[int], rid: Optional[int],
              seq: Optional[int], args: dict) -> dict:
        ev = {"eid": eid, "name": name, "ph": ph, "ts": ts}
        if dur:
            ev["dur"] = dur
        if parent is not None:
            ev["parent"] = parent
        if rid is not None:
            ev["rid"] = int(rid)
        if seq is not None:
            ev["seq"] = int(seq)
        if args:
            # lists are copied: a caller may reuse the one it passed
            ev["args"] = {k: list(v) if isinstance(v, (list, tuple)) else v
                          for k, v in sorted(args.items())}
        return ev

    # -- recording -----------------------------------------------------
    def event(self, name: str, *, track: Optional[str] = None,
              ts_ns: Optional[int] = None, dur_ns: int = 0,
              rid: Optional[int] = None, seq: Optional[int] = None,
              **args: object) -> None:
        """Record one instant (or pre-timed) event.

        Thread routing: on the owner (serving) thread the event lands on
        `track` (default ``serve``) with the current span as causal
        parent; on any other thread on the ``streamer`` track (unless
        `track` is given).  `dur_ns` is the caller's modeled duration.
        On the host clock an untimed event is an instant at the clock's
        reading, with `dur_ns` kept as the argument ``model_ns``.  On the
        modeled clock the owner thread reads it; another thread never
        does: its event sits at the track's running cursor, which then
        advances by `dur_ns`.
        """
        on_owner = threading.get_ident() == self._owner
        if track is None:
            track = SERVE_TRACK if on_owner else STREAM_TRACK
        parent = None
        if on_owner:
            st = self._stack()
            if st:
                parent = st[-1]
        if ts_ns is None and not self.modeled:
            ts_ns = self.clock()
            if dur_ns:
                args["model_ns"] = int(dur_ns)
                dur_ns = 0
        with self._lock:
            tr = self._track(track)
            if ts_ns is None:                # the modeled clock
                if on_owner:
                    ts_ns = self.clock()
                else:
                    ts_ns = tr.cursor_ns
                    tr.cursor_ns += int(dur_ns)
            eid = tr.next_eid
            tr.next_eid += 1
            ph = "X" if dur_ns else "I"
            tr.append(self._mkev(eid, name, ph, int(ts_ns), int(dur_ns),
                                 parent, rid, seq, args))

    @contextmanager
    def span(self, name: str, *, rid: Optional[int] = None,
             seq: Optional[int] = None,
             **args: object) -> Iterator[Dict[str, object]]:
        """A complete ("X") event on the tracer's clock, recorded at
        close, inside a profiler annotation ``repro.<name>``.  Owner
        thread only; the eid is reserved at open so children recorded
        inside link to it.  Yields the span's arguments, which the body
        may add to (a byte count known only at the end)."""
        if threading.get_ident() != self._owner:
            raise RuntimeError(
                "Tracer.span: spans open/close on the owner (serving) "
                "thread only; other threads use event(ts/dur) instead")
        st = self._stack()
        parent = st[-1] if st else None
        with self._lock:
            tr = self._track(SERVE_TRACK)
            eid = tr.next_eid
            tr.next_eid += 1
        st.append(eid)
        with TraceAnnotation(ANNOTATION_PREFIX + name):
            t0 = self.clock()
            try:
                yield args
            finally:
                st.pop()
                dur = self.clock() - t0
                with self._lock:
                    tr.append(self._mkev(eid, name, "X", t0, dur, parent,
                                         rid, seq, args))

    # -- snapshot ------------------------------------------------------
    def snapshot(self) -> Dict[str, object]:
        """Stable, JSON-serialisable dump (schema ``repro.trace/v1``).
        Per-track event order is each track's own (deterministic) order;
        tracks are never merged, so cross-thread interleaving can't make
        two identical runs dump differently."""
        with self._lock:
            tracks = {}
            for name in sorted(self._tracks):
                tr = self._tracks[name]
                tracks[name] = {
                    "dropped": tr.dropped,
                    "emitted": tr.emitted,
                    "events": tr.chronological(),
                }
        return {"schema": SCHEMA, "capacity": self.capacity,
                "tracks": tracks}

    def to_json(self) -> str:
        return json.dumps(self.snapshot(), sort_keys=True,
                          separators=(",", ":"))


# -- module-global tracer (mirrors telemetry / dejavulib.faults) --------
_ACTIVE: Optional[Tracer] = None


def install(t: Tracer) -> Optional[Tracer]:
    """Install *t* as the process-wide tracer; returns the previous one.
    Re-binds the owner thread to the installing thread."""
    global _ACTIVE
    prev = _ACTIVE
    t._owner = threading.get_ident()
    _ACTIVE = t
    return prev


def uninstall(prev: Optional[Tracer] = None) -> None:
    global _ACTIVE
    _ACTIVE = prev


def current() -> Optional[Tracer]:
    return _ACTIVE


def active() -> bool:
    """One-attribute-read gate hot call sites check before building args."""
    return _ACTIVE is not None


# -- cheap helpers: one `is None` check when tracing is off -------------
def event(name: str, **kw: object) -> None:
    t = _ACTIVE
    if t is not None:
        t.event(name, **kw)


@contextmanager
def span(name: str, **kw: object) -> Iterator[Optional[Dict[str, object]]]:
    """`Tracer.span` on the installed tracer; yields its arguments, or
    None when no tracer is installed (nothing recorded or annotated)."""
    t = _ACTIVE
    if t is None:
        yield None
    else:
        with t.span(name, **kw) as args:
            yield args
