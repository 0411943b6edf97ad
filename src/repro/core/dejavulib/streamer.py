"""StreamEngine — background streaming thread with compute overlap.

The paper uses a dedicated CPU thread + CUDA streams so KV-cache streaming
overlaps with GPU compute (§4.1 opts 2–3).  Here a single worker thread
drains a FIFO of transfer closures while the main thread computes; the
modeled timeline tracks how much of the streaming time was hidden.

Overlap accounting (simulated-hardware time): each submitted task carries a
`model_seconds` estimate; `overlap_report()` compares total streamed time
against the compute intervals registered via `compute_span()` — the exposed
(non-hidden) streaming time is what DéjàVu's optimizations minimize.

Error handling: `wait()` on a task re-raises its error directly.  Errors of
fire-and-forget tasks nobody waits on are collected and re-raised (first
failure as ``__cause__`` of a :class:`~repro.core.dejavulib.faults.
StreamTaskError`) at the next `drain()` or `close()` barrier, so a failed
background replication or spill can never be silently dropped.

Fault injection: `submit` / the worker loop / `wait` / `drain` fire the
``stream.submit`` / ``stream.task`` / ``stream.wait`` / ``stream.drain``
points (see `repro.core.dejavulib.faults`).  An injected transient fault
(`task_error`, or an `ssd_write` raised from inside the closure) is retried
once by the worker thread — the paper's streaming layer retransmits on
recoverable I/O errors rather than declaring the node dead.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

from repro.core import telemetry
from repro.core import tracing
from repro.core.dejavulib import faults


@dataclass
class _Task:
    fn: Callable[[], object]
    model_seconds: float
    tag: str
    done: threading.Event = field(default_factory=threading.Event)
    result: object = None
    error: Optional[BaseException] = None


class StreamEngine:
    def __init__(self, name: str = "streamer"):
        self.name = name
        self._q: "queue.Queue[Optional[_Task]]" = queue.Queue()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name=f"dejavu-{name}")
        self._thread.start()
        self._stream_model_time = 0.0
        self._compute_model_time = 0.0
        self._lock = threading.Lock()
        self._errors: List[_Task] = []   # failed tasks nobody waited on yet
        self._closed = False
        self._submit_lock = threading.Lock()

    def _run(self):
        while True:
            task = self._q.get()
            if task is None:
                self._q.task_done()
                return
            extra_model = 0.0
            try:
                spec = faults.fire("stream.task", tag=task.tag)
                if spec is not None and spec.kind == "delay":
                    extra_model = spec.delay_s       # injected straggler
                task.result = task.fn()
            except faults.FaultInjected as e:
                if e.spec.kind in faults.RETRYABLE_KINDS:
                    telemetry.count("stream.retries")
                    try:                 # transient I/O fault: one retry
                        task.result = task.fn()
                    except BaseException as e2:
                        task.error = e2
                else:
                    task.error = e
            except BaseException as e:   # surfaced on wait()/drain()/close()
                task.error = e
            if task.error is not None:
                telemetry.count("stream.task_errors")
                with self._lock:
                    self._errors.append(task)
            with self._lock:
                self._stream_model_time += task.model_seconds + extra_model
            # integer-ns counters only from this thread: no spans, no clock
            telemetry.count("stream.tasks_done")
            telemetry.count_time("stream.model_ns",
                                 task.model_seconds + extra_model)
            if tracing.active():
                # non-owner thread: lands on the streamer track at its own
                # FIFO cursor (never reads the modeled clock)
                tracing.event("stream.task", tag=task.tag,
                              dur_ns=int(round(
                                  (task.model_seconds + extra_model) * 1e9)),
                              failed=task.error is not None)
            task.done.set()
            self._q.task_done()

    def submit(self, fn: Callable[[], object], *, model_seconds: float = 0.0,
               tag: str = "") -> _Task:
        spec = faults.fire("stream.submit", tag=tag)
        if spec is not None and spec.kind == "delay":
            model_seconds += spec.delay_s
        t = _Task(fn, model_seconds, tag)
        telemetry.count("stream.tasks_submitted")
        with self._submit_lock:
            if self._closed:
                raise RuntimeError(
                    f"stream engine {self.name!r} is closed; "
                    f"cannot submit {tag!r}")
            self._q.put(t)
        return t

    def wait(self, task: _Task, timeout: Optional[float] = None):
        faults.fire("stream.wait", tag=task.tag)
        if not task.done.wait(timeout):
            raise TimeoutError(f"stream task {task.tag!r} timed out")
        if task.error is not None:
            with self._lock:
                if task in self._errors:     # waited-on: caller handles it
                    self._errors.remove(task)
            raise task.error
        return task.result

    def drain(self, timeout: float = 60.0) -> None:
        """Block until the queue is empty (barrier); surface background
        errors of fire-and-forget tasks that failed since the last barrier."""
        faults.fire("stream.drain", tag=self.name)
        sentinel = self.submit(lambda: None, tag="drain")
        self.wait(sentinel, timeout)
        self._raise_background_errors()

    def quiesce(self, timeout: float = 60.0) -> None:
        """Wait until every task submitted so far has run.  Unlike `drain`
        it submits nothing, fires no fault point and raises nothing, so a
        telemetry snapshot taken after it holds every task's counters."""
        with self._q.all_tasks_done:
            self._q.all_tasks_done.wait_for(
                lambda: not self._q.unfinished_tasks, timeout)

    def _raise_background_errors(self) -> None:
        with self._lock:
            failed, self._errors = self._errors, []
        if failed:
            first = failed[0]
            raise StreamTaskError(
                f"{len(failed)} background stream task(s) failed on "
                f"{self.name!r}; first: {first.tag!r} "
                f"({type(first.error).__name__}: {first.error})"
            ) from first.error

    def compute_span(self, model_seconds: float) -> None:
        """Register compute time available to hide streaming behind."""
        with self._lock:
            self._compute_model_time += model_seconds

    def overlap_report(self) -> dict:
        with self._lock:
            hidden = min(self._stream_model_time, self._compute_model_time)
            exposed = self._stream_model_time - hidden
            return {"stream_s": self._stream_model_time,
                    "compute_s": self._compute_model_time,
                    "hidden_s": hidden, "exposed_s": exposed}

    def reset(self) -> None:
        with self._lock:
            self._stream_model_time = 0.0
            self._compute_model_time = 0.0

    def close(self) -> None:
        """Stop the worker thread.  Idempotent.  Raises if the thread fails
        to exit or if background tasks failed and were never surfaced."""
        with self._submit_lock:
            first_close = not self._closed
            self._closed = True
            if first_close:
                self._q.put(None)        # sentinel: drain queue, then exit
        self._thread.join(timeout=5)
        if self._thread.is_alive():
            raise RuntimeError(
                f"stream engine {self.name!r}: worker thread did not exit")
        self._raise_background_errors()


StreamTaskError = faults.StreamTaskError   # re-export at the raising site
