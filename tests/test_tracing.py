"""Flight recorder (repro.core.tracing): ring-buffer semantics, causal
span nesting, the host and modeled clocks, cross-thread routing, the
profiler annotations, the spans of the served page path, byte-identical
dumps across identical serving runs on the modeled clock, the wire-format
exporters, and the trace_report critical-path gate."""
import dataclasses
import glob
import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from repro.core import exporters, telemetry, tracing
from repro.core.dejavulib import faults

_TOOL = os.path.join(os.path.dirname(__file__), "..", "tools",
                     "trace_report.py")
_spec = importlib.util.spec_from_file_location("trace_report", _TOOL)
trace_report = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace_report)


# ---------------------------------------------------------------------------
# unit level: ring buffer, spans, thread routing
# ---------------------------------------------------------------------------

def test_ring_overwrites_oldest_and_counts_drops():
    t = tracing.Tracer(capacity=4)
    for i in range(10):
        t.event("e", n=i)
    tr = t.snapshot()["tracks"][tracing.SERVE_TRACK]
    assert tr["emitted"] == 10
    assert tr["dropped"] == 6                  # visible, never silent
    assert [e["eid"] for e in tr["events"]] == [6, 7, 8, 9]
    assert [e["args"]["n"] for e in tr["events"]] == [6, 7, 8, 9]


def test_span_nesting_parents_and_modeled_clock():
    prev = telemetry.install(telemetry.Telemetry())
    tele = telemetry.current()
    t = tracing.Tracer(clock=tracing.modeled_ns)
    try:
        with t.span("round"):
            tele.advance(1e-6)
            with t.span("pass", rid=7, kind="fused_decode"):
                tele.advance(2e-6)
                t.event("emit.first_token", rid=7)
    finally:
        telemetry.uninstall(prev)
    evs = t.snapshot()["tracks"][tracing.SERVE_TRACK]["events"]
    # spans record at CLOSE (innermost first); eids are reserved at open
    assert [e["name"] for e in evs] == ["emit.first_token", "pass", "round"]
    emit = evs[0]
    pas = evs[1]
    rnd = evs[2]
    assert rnd["eid"] == 0 and "parent" not in rnd
    assert pas["parent"] == rnd["eid"]
    assert emit["parent"] == pas["eid"]
    # integer-ns timestamps on the modeled clock
    assert (rnd["ts"], rnd["dur"]) == (0, 3000)
    assert (pas["ts"], pas["dur"]) == (1000, 2000)
    assert emit["ts"] == 3000 and emit["ph"] == "I"
    assert pas["rid"] == 7 and pas["args"] == {"kind": "fused_decode"}


def test_nonowner_thread_routes_to_streamer_cursor():
    t = tracing.Tracer(clock=tracing.modeled_ns)

    def worker():
        t.event("xfer", dur_ns=100, bytes=5)
        t.event("stream.task", dur_ns=50)

    th = threading.Thread(target=worker)
    th.start()
    th.join()
    evs = t.snapshot()["tracks"][tracing.STREAM_TRACK]["events"]
    # never reads the modeled clock: FIFO cursor chaining instead
    assert [e["ts"] for e in evs] == [0, 100]
    assert evs[0]["dur"] == 100 and evs[0]["ph"] == "X"
    assert all("parent" not in e for e in evs)


def test_span_raises_off_owner_thread():
    t = tracing.Tracer()
    errs = []

    def worker():
        try:
            with t.span("x"):
                pass
        except RuntimeError as e:
            errs.append(e)

    th = threading.Thread(target=worker)
    th.start()
    th.join()
    assert len(errs) == 1 and "owner" in str(errs[0])


def test_module_helpers_noop_when_uninstalled():
    assert tracing.current() is None
    assert not tracing.active()
    tracing.event("x", rid=1)                  # silent no-ops
    with tracing.span("y"):
        pass
    assert tracing.current() is None


def test_install_uninstall_restores_previous():
    a = tracing.Tracer()
    prev = tracing.install(a)
    assert prev is None
    b = tracing.Tracer()
    prev = tracing.install(b)
    assert prev is a
    tracing.uninstall(prev)
    assert tracing.current() is a
    tracing.uninstall()
    assert tracing.current() is None


def test_default_tracer_reads_the_host_clock_on_every_thread():
    t = tracing.Tracer()
    assert t.clock is tracing.host_ns and not t.modeled
    lo = time.perf_counter_ns()
    prev = tracing.install(t)
    try:
        with tracing.span("round"):
            with tracing.span("pass"):
                time.sleep(0.002)
            tracing.event("sched.plan")

        def worker():
            tracing.event("xfer", dur_ns=500, bytes=5)
            tracing.event("stream.task", dur_ns=50)

        th = threading.Thread(target=worker)
        th.start()
        th.join()
    finally:
        tracing.uninstall(prev)
    hi = time.perf_counter_ns()
    evs = {e["name"]: e for e in t.snapshot()["tracks"]["serve"]["events"]}
    rnd, pas, plan = evs["round"], evs["pass"], evs["sched.plan"]
    assert lo <= rnd["ts"] <= pas["ts"]
    assert pas["dur"] >= 2_000_000                     # the sleep, in ns
    assert pas["ts"] + pas["dur"] <= plan["ts"]
    assert plan["ts"] <= rnd["ts"] + rnd["dur"] <= hi
    # streamer-thread events: host times in order, modeled cost as an arg
    stream = t.snapshot()["tracks"][tracing.STREAM_TRACK]["events"]
    assert [e["name"] for e in stream] == ["xfer", "stream.task"]
    assert rnd["ts"] + rnd["dur"] <= stream[0]["ts"] <= stream[1]["ts"] <= hi
    assert all(e["ph"] == "I" and "dur" not in e for e in stream)
    assert [e["args"]["model_ns"] for e in stream] == [500, 50]


def _profiled_annotations(tmp_path, body):
    """The ``repro.`` events a JAX profile of `body` holds, per plane."""
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    return [(plane.name, ev.name)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for ev in line.events
            if ev.name.startswith(tracing.ANNOTATION_PREFIX)]


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    t = tracing.Tracer()

    def body():
        prev = tracing.install(t)
        try:
            with tracing.span("pass"):
                with tracing.span("worker.gather", wid=0):
                    time.sleep(0.001)
        finally:
            tracing.uninstall(prev)

    found = _profiled_annotations(tmp_path, body)
    assert ("/host:CPU", "repro.worker.gather") in found
    assert ("/host:CPU", "repro.pass") in found
    assert not any(p.startswith("/device:") for p, _ in found)


def test_nothing_recorded_or_annotated_without_a_tracer(tmp_path):
    def body():
        with tracing.span("worker.gather", wid=0) as args:
            assert args is None
        tracing.event("xfer", bytes=1)

    assert tracing.current() is None
    assert _profiled_annotations(tmp_path, body) == []
    assert tracing.current() is None


# ---------------------------------------------------------------------------
# engine level: a traced faulted run through the real serving stack
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    import jax
    from repro.configs.registry import PAPER_ARCHS
    from repro.models import build_model

    cfg = dataclasses.replace(PAPER_ARCHS["gpt2-1.5b"].reduced(),
                              dtype="float32", num_layers=2)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size, (3, 8)).astype(np.int32)
    return cfg, model, params, prompts


def _traced_run(served):
    """Tiered + replicated continuous run with one worker death at step 5
    and one injected streamer delay — exercises every trace source."""
    from repro.serving import Request, ServingEngine
    cfg, model, params, prompts = served
    prev_tele = telemetry.install(telemetry.Telemetry())
    tracer = tracing.Tracer(clock=tracing.modeled_ns)
    prev_tr = tracing.install(tracer)
    try:
        eng = ServingEngine(cfg, model, params, 2, paged=True, tiered=True,
                            kv_pool_blocks=128, host_cache_blocks=16,
                            ssd_cache_blocks=32, replication=True)
        reqs = [Request(rid=i, prompt=p.copy(), max_new=4)
                for i, p in enumerate(prompts)]
        plan = faults.FaultPlan([faults.FaultSpec(
            "stream.task", nth=2, kind="delay", delay_s=1e-3)])
        rep = eng.run_continuous(reqs, max_active=2, fail_at={5: 1},
                                 fault_plan=plan)
        snapshot = telemetry.current().snapshot()
    finally:
        tracing.uninstall(prev_tr)
        telemetry.uninstall(prev_tele)
    assert rep.recoveries == 1
    return rep, tracer, snapshot


@pytest.fixture(scope="module")
def traced(served):
    rep, tracer, tele_snap = _traced_run(served)
    return rep, tracer.snapshot(), tracer.to_json(), tele_snap


def test_traced_run_covers_all_sources(traced):
    _, trace, _, _ = traced
    serve_names = {e["name"] for e in trace["tracks"]["serve"]["events"]}
    assert {"round", "pass", "sched.admit", "sched.plan", "sched.retire",
            "emit.first_token", "cluster.kill", "recovery"} <= serve_names
    # the host-side layers of every stage op, tagged with their stage
    assert {"worker.gather", "worker.upload", "worker.stage",
            "kvpool.append", "kvpool.write_window",
            "dejavulib.replicate", "engine.sample"} <= serve_names
    # the paged passes read no window back: their pages stay on the device
    assert "worker.readback" not in serve_names
    assert {e["args"]["wid"] for e in trace["tracks"]["serve"]["events"]
            if e["name"] == "worker.stage"} >= {0, 1}
    stream_names = {e["name"]
                    for e in trace["tracks"]["streamer"]["events"]}
    assert {"xfer", "stream.task", "fault.delay"} <= stream_names
    assert sorted(trace["tracks"]) == ["serve", "streamer"]
    assert all(t["dropped"] == 0 for t in trace["tracks"].values())


def _children(events, parent):
    out = {}
    for e in events:
        if e.get("parent") == parent["eid"]:
            out.setdefault(e["name"], []).append(e)
    return out


def test_page_path_spans_nest_under_each_pass(served):
    """On a 2-stage model, every fused decode pass and chunk-set pass holds
    the page path's spans, once per stage: the gather builds the block
    tables, the upload moves only the tables and the per-sequence vectors,
    and nothing is read back; replication nests under the pass with the
    bytes the streamer sent, sampling does not."""
    from repro.serving import Request, ServingEngine
    cfg, model, params, _ = served
    # prompts of three chunks: after each admission's first chunk, the
    # rest run as chunk-set passes beside the decodes
    cfg = dataclasses.replace(cfg, prefill_chunk_tokens=8)
    prompts = np.random.default_rng(1).integers(0, cfg.vocab_size, (3, 20))
    tracer = tracing.Tracer()
    prev = tracing.install(tracer)
    try:
        eng = ServingEngine(cfg, model, params, 2, paged=True,
                            kv_pool_blocks=64, replication=True)
        eng.run_continuous([Request(rid=i, prompt=p.astype(np.int32),
                                    max_new=4)
                            for i, p in enumerate(prompts)], max_active=2)
    finally:
        tracing.uninstall(prev)
    snap = tracer.snapshot()
    evs = snap["tracks"]["serve"]["events"]
    xfers = snap["tracks"]["streamer"]["events"]
    width = -(-cfg.max_seq_len // cfg.kv_block_size)    # table blocks
    per_stage = ("worker.gather", "worker.stage", "kvpool.write_window")
    passes = [e for e in evs if e["name"] == "pass"]
    kinds = {e["args"]["kind"] for e in passes}
    assert {"fused_decode", "chunkset"} <= kinds
    assert not [e for e in evs if e["name"] == "worker.readback"]
    for p in passes:
        kind = p["args"]["kind"]
        if kind not in ("fused_decode", "chunkset"):
            continue
        kids = _children(evs, p)
        want = per_stage + (("kvpool.append",) if kind == "fused_decode"
                            else ())
        for name in want:
            assert sorted(e["args"]["wid"] for e in kids[name]) == [0, 1], \
                (kind, name)
        n = len(p["args"]["rids"])
        # int32 tables [n, width], positions, and chunk lengths
        vectors = 1 if kind == "fused_decode" else 2
        for g in kids["worker.gather"]:
            ups = _children(evs, g)["worker.upload"]
            assert [u["args"]["wid"] for u in ups] == [g["args"]["wid"]]
            assert g["args"]["n"] == n and g["args"]["width"] == width
            assert ups[0]["args"]["bytes"] == 4 * n * (width + vectors)
        if kind == "fused_decode":
            rep, = kids["dejavulib.replicate"]
            assert rep["args"]["n"] == n
            # what the streamer sent while the pass waited for it
            sent = sum(x["args"]["bytes"] for x in xfers
                       if x["name"] == "xfer"
                       and rep["ts"] <= x["ts"] <= rep["ts"] + rep["dur"])
            assert rep["args"]["bytes"] == sent > 0
        # children lie inside their pass
        for es in kids.values():
            for e in es:
                assert p["ts"] <= e["ts"]
                assert e["ts"] + e.get("dur", 0) <= p["ts"] + p["dur"]
    samples = [e for e in evs if e["name"] == "engine.sample"]
    assert samples and all(
        next(q for q in evs if q["eid"] == e["parent"])["name"] == "round"
        for e in samples)


def test_determinism_byte_identical_dumps(served, traced):
    """Two identical runs must produce byte-identical trace dumps — the
    recorder's headline guarantee (same as telemetry's)."""
    _, _, dump_a, _ = traced
    _, tracer_b, _ = _traced_run(served)
    assert dump_a == tracer_b.to_json()


def test_trace_report_attributes_wall_time(traced, tmp_path):
    _, trace, dump, _ = traced
    report = trace_report.analyze(trace)
    assert len(report["requests"]) == 3
    for r in report["requests"].values():
        assert r["coverage"] >= 0.95            # acceptance criterion (c)
    assert report["bubbles"]["wall_total_ns"] > 0
    assert not report["dropped"]
    # the CLI gate CI runs over the failures-benchmark artifact
    p = tmp_path / "trace.json"
    p.write_text(dump)
    assert trace_report.main([str(p), "--assert"]) == 0


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_perfetto_export_tracks_and_instants(traced):
    _, trace, _, _ = traced
    doc = exporters.trace_to_perfetto(trace)
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert len(meta) == len(trace["tracks"])    # one named thread per track
    names = {m["tid"]: m["args"]["name"] for m in meta}
    assert names[1] == "serve"                  # serve first, streamer last
    assert names[max(names)] == "streamer"
    assert any(e["ph"] == "X" and e.get("dur", 0) > 0 for e in evs)
    insts = [e for e in evs if e["ph"] == "i"]
    assert any(e["name"].startswith("fault.") for e in insts)
    assert all(e["s"] == "t" for e in insts)
    json.dumps(doc)                             # serialisable as-is


def test_prometheus_export_text_format(traced):
    _, _, _, tele_snap = traced
    text = exporters.telemetry_to_prometheus(tele_snap)
    assert text.endswith("\n")
    lines = text.splitlines()
    assert any(line.startswith("# TYPE engine_ttft_s histogram")
               for line in lines)
    assert any('engine_ttft_s_bucket{le="+Inf"}' in line for line in lines)
    assert any(line.startswith("faults_fired_total{") for line in lines)
    assert any(line.startswith("modeled_clock_seconds ") for line in lines)
    # cumulative buckets: counts never decrease within a histogram family
    buckets = [int(line.rsplit(" ", 1)[1]) for line in lines
               if line.startswith("engine_ttft_s_bucket{")]
    assert buckets == sorted(buckets)


# ---------------------------------------------------------------------------
# golden schemas: exported key sets and version strings are API
# ---------------------------------------------------------------------------

def test_golden_schema_key_sets(traced):
    """Renderers (render_tables / render_compare / exporters / CI trend
    gate) all consume these exact key sets; a rename is a breaking change
    that must show up here, not in a downstream tool."""
    _, trace, _, tele_snap = traced
    assert tele_snap["schema"] == "repro.telemetry/v1"
    assert sorted(tele_snap) == ["clock_s", "counters", "gauges",
                                 "histograms", "schema", "spans"]
    for h in tele_snap["histograms"].values():
        assert sorted(h) == ["buckets_s", "count", "counts", "max_s",
                             "min_s", "p50_s", "p90_s", "p99_s", "sum_s"]
    for s in tele_snap["spans"].values():
        assert sorted(s) == ["count", "max_s", "total_s"]

    assert trace["schema"] == "repro.trace/v1"
    assert sorted(trace) == ["capacity", "schema", "tracks"]
    required = {"eid", "name", "ph", "ts"}
    allowed = required | {"dur", "parent", "rid", "seq", "args"}
    for tr in trace["tracks"].values():
        assert sorted(tr) == ["dropped", "emitted", "events"]
        for ev in tr["events"]:
            keys = set(ev)
            assert required <= keys <= allowed, f"unexpected keys in {ev}"
            assert ev["ph"] in ("X", "I")
