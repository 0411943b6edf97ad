"""One run of one cell: weights from the seed, a warm-up that replays the
run's own request stream, then the measured window over a fresh engine, the
comparison with the reference, and the result line's contents.

Both engines serve the stream from its start.  The window opens at the
first pass after the first requests of every client (or of the mix's
``fill`` clients) have emitted their first token, so the closed loop is
decoding, and closes at the first pass that starts `seconds` later: it
holds whole passes, and its length is measured, not assumed.  Filling the
loop is set-up.

The system under test is ``ServingEngine(cfg, model, params, n_workers,
paged=True, replication=True).run_continuous(requests, max_active=clients)``
with the engine's defaults (fused rounds, chunked prefill).  The benchmark
reaches it only through the request token lists, the sampler and the fault
injector (see `window.py`); it injects no fault.  A ``--trace 1`` run also
installs a ``repro.core.tracing.Tracer`` while the profiler runs, and reads
the ``repro.core.telemetry`` counters at the window's open and close; a
``--trace 0`` run does neither.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from chipbench import correct, counters, peaks, spans, spec, traffic, weights
from chipbench.compiles import CompileCounter
from chipbench.tracefile import TraceSummary, find_xplane, reduce_file
from chipbench.window import (Recorder, TimedTokens, WindowClosed,
                              attempted, gaps_in_window, send_times,
                              tokens_in_window)

# After its loop fills, the warm-up serves the run's stream for this
# multiple of the window (plus WARMUP_EXTRA_S), compile time excluded, so
# the window meets no shape that set-up has not compiled.
WARMUP_FACTOR = 1.15
WARMUP_EXTRA_S = 2.0
# A --trace 1 run profiles from TRACE_AT of the window for at least
# TRACE_MIN_S, ending at a pass boundary after one whole decode pass.
TRACE_AT = 0.4
TRACE_MIN_S = 4.0


@jax.jit
def _served_logit(logits, tok):
    return logits[0, tok[0]].astype(jax.numpy.float32)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def devices_for(chips: int, require_chip: bool = True):
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"needs a TPU; JAX found {devs[0].platform!r}")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips; JAX found "
                         f"{len(devs)}")
    return devs[:chips]


@dataclass
class Run:
    """What a metric reader gets (metrics/<name>.py: read(run))."""
    cell: spec.Cell
    ref: object                                # the reference module
    arch: dict                                 # the served ArchConfig fields
    rec: Recorder
    window_s: float                            # t_close - t_open
    t_open: float
    t_close: float
    setup_s: float
    clients: int
    stages: List[Tuple[int, int]]              # each stage's layers lo, hi
    compiles_in_window: int = 0
    stream_bytes: int = 0                      # DejaVuLib bytes in window
    peak_bytes: Optional[int] = None
    peaks: Optional[dict] = None
    trace: Optional[TraceSummary] = None
    trace_t0: Optional[float] = None           # host clock at trace window
    trace_t1: Optional[float] = None
    gc_pauses: List[tuple] = field(default_factory=list)  # (t0, t1, gen)
    # --trace 1 only: the program's spans while the profiler ran, and its
    # telemetry counters' increase over the window
    spans: Optional[List[spans.Span]] = None
    counters: Optional[Dict[str, int]] = None

    def tokens_out(self) -> int:
        return tokens_in_window(self.rec.first, self.t_open, self.t_close)

    def gaps(self) -> List[float]:
        return gaps_in_window(self.rec.first, self.t_open, self.t_close)

    def passes(self, kind: Optional[str] = None):
        return [p for p in self.rec.passes
                if p.t1 is not None and self.t_open <= p.t0 and p.t1 <= self.t_close
                and (kind is None or p.kind == kind)]

    def traced_passes(self):
        """Passes that began inside the traced window (all end inside it:
        it closes at a pass boundary)."""
        if self.trace_t0 is None:
            return []
        return [p for p in self.rec.passes
                if self.trace_t0 <= p.t0 < self.trace_t1]

    def host_time(self, trace_s: float) -> float:
        """A trace-clock time on the host clock."""
        return self.trace_t0 + (trace_s - self.trace.window[0])


def _stages(num_layers: int, n: int) -> List[Tuple[int, int]]:
    """Each stage's layer range, split as the cluster splits them."""
    return [(int(s[0]), int(s[-1]) + 1)
            for s in np.array_split(np.arange(num_layers), n)]


def _injector(rec: Recorder):
    """The fault injector the engine fires at, with no fault planned: it
    forwards the serving thread's ``engine.step`` to the recorder."""
    from repro.core.dejavulib import faults

    class Injector(faults.FaultInjector):
        def __init__(self):
            super().__init__(faults.FaultPlan([]))
            self.main = threading.get_ident()

        def fire(self, point, tag=""):
            if point == "engine.step" and threading.get_ident() == self.main:
                rec.on_step(tag)
            return super().fire(point, tag)

    return Injector()


class _Profiler:
    """Starts the profiler at a pass boundary TRACE_AT into the window and
    stops it at a pass boundary at least TRACE_MIN_S later, once a whole
    decode pass ran inside; annotates the host's state meanwhile, and keeps
    a tracer of the program's spans installed while it runs."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.t0 = self.t1 = None
        self.window_ann = self.state_ann = None
        self.tracer = self.prev_tracer = None

    def new_pass(self, rec: Recorder, kind: str) -> None:
        now = rec.clock()
        if self.t0 is None:
            if rec.t_open is not None and \
                    now >= rec.t_open + TRACE_AT * self.seconds:
                self._start(rec)
        elif self.t1 is None:
            done = any(p.t0 >= self.t0 and p.kind == "decode"
                       and p.t1 is not None for p in rec.passes)
            if done and now >= self.t0 + TRACE_MIN_S:
                self.stop(rec)

    def _start(self, rec: Recorder) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        from repro.core import tracing
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.window_ann = jax.profiler.TraceAnnotation("bench.trace_window")
        self.window_ann.__enter__()
        self.tracer = tracing.Tracer()
        self.prev_tracer = tracing.install(self.tracer)
        self.t0 = rec.clock()
        rec.annotate = self._annotate

    def _annotate(self, state: str) -> None:
        if self.state_ann is not None:
            self.state_ann.__exit__(None, None, None)
        self.state_ann = jax.profiler.TraceAnnotation(f"bench.{state}")
        self.state_ann.__enter__()

    def stop(self, rec: Recorder) -> None:
        if self.t0 is None or self.t1 is not None:
            return
        from repro.core import tracing
        rec.annotate = None
        if self.state_ann is not None:
            self.state_ann.__exit__(None, None, None)
            self.state_ann = None
        self.t1 = rec.clock()
        tracing.uninstall(self.prev_tracer)
        self.window_ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def summary(self) -> Optional[TraceSummary]:
        path = find_xplane(self.dir)
        return reduce_file(path) if path else None

    def program_spans(self) -> Optional[List[spans.Span]]:
        if self.tracer is None:
            return None
        return spans.from_snapshot(self.tracer.snapshot())

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


class Served:
    """What one run builds once for both engines: model, weights, stream."""

    def __init__(self, cell: spec.Cell, seed: int, devices):
        from repro.configs.base import ArchConfig
        from repro.models import build_model

        self.cell, self.seed, self.devices = cell, seed, devices
        cfgfile, mix = cell.config, cell.traffic
        self.arch = dict(cfgfile["arch"])
        self.serving = dict(cfgfile["serving"])
        self.cfg = ArchConfig(name=cfgfile["name"], **self.arch)
        self.model = build_model(self.cfg)
        self.ref = spec.reference(cfgfile)
        self.sizes = counters.Sizes.from_reference(self.ref, self.arch)
        self.clients = int(mix["clients"])
        self.stream = traffic.make_stream(mix, self.arch["vocab_size"], seed)
        bs = self.arch["kv_block_size"]
        # the pool holds every client at the full context, so admission is
        # never held back by it: the loop stays closed and FIFO
        per_seq = -(-(int(mix["max_total"]) + 1) // bs) + 1
        self.pool_blocks = self.clients * per_seq + self.clients
        self.n_workers = int(self.serving["n_workers"])

    def make_weights(self) -> None:
        dev = weights.make_params(self.model, self.cell.config["init"],
                                  self.seed, self.devices[0])
        self.params = jax.device_get(dev)
        del dev
        gc.collect()

    def engine(self, sampler):
        from repro.serving import ServingEngine
        return ServingEngine(self.cfg, self.model, self.params,
                             self.n_workers, paged=True, replication=True,
                             kv_pool_blocks=self.pool_blocks,
                             devices=self.devices, sampler=sampler)

    def serve(self, rec: Recorder, injector, on_engine=None) -> tuple:
        """One `run_continuous` over the stream until a hook closes the
        window; returns (engine, requests).  The caller frees the engine."""
        from repro.serving import Request
        from repro.serving import sampling

        def sampler(logits, step):
            rec.on_sample(True)
            tok = sampling.greedy(logits, step)
            # the logit the token was chosen with, for the comparison
            rec.on_sample(False, float(_served_logit(logits, tok)))
            return tok

        eng = self.engine(sampler)
        if on_engine is not None:
            on_engine(eng)
        reqs = [Request(rid=i, prompt=p, max_new=o,
                        tokens=TimedTokens(i, rec))
                for i, (p, o) in enumerate(self.stream)]
        rec.t_start = rec.clock()
        try:
            eng.run_continuous(reqs, max_active=self.clients,
                               fault_injector=injector)
        except WindowClosed:
            return eng, reqs
        raise RuntimeError("the request stream ran out before the window "
                           "closed; the mix needs more requests")

    def recorder(self) -> Recorder:
        return Recorder(self.sizes,
                        {i: len(p) for i, (p, _) in enumerate(self.stream)},
                        {i: o for i, (_, o) in enumerate(self.stream)},
                        int(self.arch["prefill_chunk_tokens"]), self.clients,
                        self.cell.traffic.get("fill"))


def _free(eng) -> None:
    eng.cluster.streamer.close()
    del eng
    gc.collect()


def warm_up(c: Served, seconds: float, counter: CompileCounter) -> None:
    """Serve the run's own stream on a throwaway engine until its loop has
    filled and then WARMUP_FACTOR x the window + WARMUP_EXTRA_S more, in
    serving time (compile time excluded)."""
    rec = c.recorder()
    c0 = counter.seconds
    budget = WARMUP_FACTOR * seconds + WARMUP_EXTRA_S
    fill: List[float] = []

    def serving_time(now: float) -> float:
        return now - rec.t_start - (counter.seconds - c0)

    def close(now: float) -> bool:
        if not fill:
            if rec.filled():
                fill.append(serving_time(now))
            return False
        return serving_time(now) - fill[0] >= budget

    rec.close = close
    eng, _ = c.serve(rec, _injector(rec))
    _free(eng)


def measure(c: Served, seconds: float, trace: bool, counter: CompileCounter,
            t_process: float, pk: Optional[dict]) -> tuple:
    """The measured window on a fresh engine.  Returns (Run, requests)."""
    rec = c.recorder()
    box: Dict[str, object] = {}
    pauses: List[tuple] = []

    def on_gc(phase: str, info: dict) -> None:
        # the collector's pauses, to tell them from the program's own time
        if phase == "start":
            box["gc0"] = time.perf_counter()
        elif "gc0" in box:
            pauses.append((box.pop("gc0"), time.perf_counter(),
                           info["generation"]))

    def snapshot(tag: str) -> None:
        box[tag] = (counter.compiles,
                    sum(box["eng"].transfer_summary().values()))
        if trace:
            box[tag + "_counters"] = _telemetry_counters()

    def close(now: float) -> bool:
        if rec.t_open is None:
            if rec.filled():
                rec.t_open = now
                snapshot("open")
            return False
        if now < rec.t_open + seconds:
            return False
        box["t_close"] = now
        snapshot("close")
        return True

    rec.close = close
    prof = _Profiler(seconds) if trace else None
    if prof is not None:
        rec.new_pass = prof.new_pass
    gc.callbacks.append(on_gc)
    try:
        eng, reqs = c.serve(rec, _injector(rec),
                            on_engine=lambda e: box.update(eng=e))
    finally:
        gc.callbacks.remove(on_gc)
        if prof is not None:
            prof.stop(rec)
    del box["eng"]
    peak = None
    stats = [d.memory_stats() or {} for d in c.devices]
    if all("peak_bytes_in_use" in s for s in stats):
        peak = max(int(s["peak_bytes_in_use"]) for s in stats)
    _free(eng)
    (c_open, b_open), (c_close, b_close) = box["open"], box["close"]
    t_close = box["t_close"]
    run = Run(cell=c.cell, ref=c.ref, arch=c.arch, rec=rec,
              window_s=t_close - rec.t_open, t_open=rec.t_open,
              t_close=t_close,
              setup_s=rec.t_open - t_process, clients=c.clients,
              stages=_stages(c.arch["num_layers"], c.n_workers),
              compiles_in_window=c_close - c_open,
              stream_bytes=b_close - b_open, peak_bytes=peak, peaks=pk,
              gc_pauses=[g for g in pauses
                         if rec.t_open <= g[0] and g[1] <= t_close])
    if prof is not None:
        run.trace = prof.summary()
        run.trace_t0, run.trace_t1 = prof.t0, prof.t1
        run.spans = prof.program_spans()
        prof.cleanup()
        start = box["open_counters"]
        run.counters = {k: v - start.get(k, 0)
                        for k, v in box["close_counters"].items()}
    return run, reqs


def _telemetry_counters() -> Dict[str, int]:
    """The installed telemetry registry's counters (the engine installs one
    for each run); empty without one."""
    from repro.core import telemetry
    t = telemetry.current()
    return {} if t is None else dict(t.snapshot()["counters"])


def compare(c: Served, reqs, rec: Recorder):
    """The comparison with the reference over every request served a token
    in the window (after the engine is freed)."""
    items = [correct.Item(r.rid, r.prompt, list(r.tokens),
                          [rec.logit[r.rid][i] for i in range(len(r.tokens))])
             for r in reqs if len(r.tokens) > 0]
    return items, correct.compare(c.ref, c.params, c.arch, c.cell.config,
                                  items, pad_to=int(c.arch["max_seq_len"]),
                                  device=c.devices[0])


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, require_chip: bool = True,
             log: Callable[[str], None] = None) -> dict:
    """One run; returns the result line's object (checks last)."""
    log = log or (lambda m: print(m, file=sys.stderr, flush=True))
    devs = devices_for(cell.chips, require_chip)
    pk = peaks.peaks_for(devs[0].device_kind) if require_chip else None
    counter = CompileCounter().install()
    c = Served(cell, seed, devs)
    c.make_weights()
    log(f"weights: {time.perf_counter() - t_process:.3f} s after start")
    warm_up(c, seconds, counter)
    log(f"warm-up: done {time.perf_counter() - t_process:.3f} s after start, "
        f"{counter.compiles} compilations ({counter.cache_hits} from the "
        f"persistent cache)")
    run, reqs = measure(c, seconds, trace, counter, t_process, pk)
    items, (widest, logit_err, n_cmp) = compare(c, reqs, run.rec)
    lim = cell.config["limits"]
    chk = correct.checks(widest, float(lim["widest_gap"]), logit_err,
                         float(lim["logit_error"]), n_cmp,
                         run.rec.regen_changed)
    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for name in names:
        v = cell.readers[name](run)
        if v is not None:
            metrics[name] = {"value": float(v), "unit": cell.units[name]}
    sends = send_times(run.rec.completions, c.clients, len(c.stream),
                       run.rec.t_start)
    breaks = order_breaks(run.rec.admitted, run.rec.completions, c.clients)
    log(f"samples: set-up {run.setup_s:.3f} s (loop filled "
        f"{run.t_open - run.rec.t_start:.3f} s after serving began), "
        f"gaps {len(run.gaps())}, tokens {run.tokens_out()}, "
        f"requests sent {attempted(sends, run.t_close)}, completed "
        f"{sum(1 for t in run.rec.completions if t <= run.t_close)}, "
        f"compared {len(items)} requests / {n_cmp} tokens, "
        f"compilations in window {run.compiles_in_window}, "
        f"closed-loop order breaks {breaks}")
    log(window_profile(run))
    if run.spans is not None:
        log("traced " + spans.totals_line(run.spans, run.trace_t0,
                                          run.trace_t1))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.peak_bytes}
    out = {"correct": correct.passed(chk),
           "attempted": attempted(sends, run.t_close), "failed": 0,
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in run.trace.op_seconds.items()],
            "idle_gaps": [[k, v] for k, v in run.trace.idle_gaps]}
    out["checks"] = chk
    out["_run"] = run
    out["_compared"] = (c, items)
    return out


def order_breaks(admitted, completions, clients: int) -> int:
    """Admissions that broke the closed loop: out of rid order, or request
    k >= clients admitted before k - clients + 1 requests had completed."""
    done = sorted(completions)
    breaks = 0
    for j, (t, rid) in enumerate(admitted):
        n = int(np.searchsorted(done, t, side="right"))
        if rid != j or (rid >= clients and n < rid - clients + 1):
            breaks += 1
    return breaks


def window_profile(run: Run) -> str:
    """One log line: each pass of the window in order (kind, batch, host
    ms) and the collector's pauses in it, so a slow pass can be told
    apart from a collection."""
    passes = " ".join(f"{p.kind[0]}{p.batch}:{(p.t1 - p.t0) * 1e3:.0f}"
                      for p in run.passes())
    by_gen: Dict[int, List[float]] = {}
    for t0, t1, gen in run.gc_pauses:
        by_gen.setdefault(gen, []).append(t1 - t0)
    pauses = ", ".join(f"gen{g} {len(d)} x, {sum(d) * 1e3:.1f} ms, longest "
                       f"{max(d) * 1e3:.1f} ms"
                       for g, d in sorted(by_gen.items())) or "none"
    return f"window passes (kind batch:ms): {passes}; collector: {pauses}"
