"""Paper Figs. 4/14/15: failure impact on latency and completions.

Fig. 14: cumulative latency of one microbatch with a stage failure at a given
token step — restart-from-scratch vs DéjàVu replica recovery.
Fig. 15: request completions over time with failures injected at 600/1200/
1800 s — total runtime ratio (paper: 1.16× shorter with DéjàVu).
Also runs the REAL in-process cluster with an injected failure and verifies
recovery work (steps redone) stays at the replication lag.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import numpy as np

from benchmarks.common import emit, emit_metric
from repro.configs.registry import PAPER_ARCHS
from repro.core import costmodel as cm
from repro.core import exporters, telemetry, tracing
from repro.core.simulator import failure_latency
from repro.models import build_model
from repro.serving import Request, ServingEngine
from tools import trace_report


def run() -> None:
    cfg = PAPER_ARCHS["opt-66b"]
    wl = cm.WorkloadSpec(500, 1000, 8)
    for step in (250, 500, 750):
        bl = failure_latency(cfg, wl, 4, fail_step=step, dejavu=False)
        dv = failure_latency(cfg, wl, 4, fail_step=step, dejavu=True)
        emit(f"fig14/opt-66b/fail@{step}/baseline_slowdown",
             bl["slowdown"] * 1e6, f"{bl['slowdown']:.2f}x (paper 1.91x)")
        emit(f"fig14/opt-66b/fail@{step}/dejavu_slowdown",
             dv["slowdown"] * 1e6, f"{dv['slowdown']:.2f}x (paper 1.24x)")
        cut = bl["slowdown"] / dv["slowdown"]
        emit_metric(f"fig14_latency_cut_fail{step}", cut, "(paper 1.54x)")
        # headline invariant: replica recovery beats restart-from-scratch
        assert cut > 1.0, (
            f"fail@{step}: DejaVu recovery slowdown {dv['slowdown']:.2f}x "
            f">= baseline restart {bl['slowdown']:.2f}x")

    # Fig. 15: 3 failures across a long serving trace -> total runtime ratio.
    # Each failure costs (redo of in-flight work + restart) for the baseline
    # vs (replication-lag redo + replica restore) for DéjàVu, added to the
    # failure-free trace makespan.
    wl15 = cm.WorkloadSpec(500, 1000, 8)
    bl1 = failure_latency(cfg, wl15, 4, fail_step=500, dejavu=False)
    dv1 = failure_latency(cfg, wl15, 4, fail_step=500, dejavu=True)
    t0 = 40 * bl1["no_fail_s"] / 4          # ~40 microbatches, 4-deep pipeline
    extra_bl = bl1["with_fail_s"] - bl1["no_fail_s"]   # per-failure overhead
    extra_dv = dv1["with_fail_s"] - dv1["no_fail_s"]
    ratio = (t0 + 3 * extra_bl) / (t0 + 3 * extra_dv)
    emit_metric("fig15_trace_ratio", ratio,
                f"{ratio:.2f}x shorter trace with DejaVu (paper 1.16x)")
    assert ratio > 1.0, f"fig15: trace ratio {ratio:.2f}x <= 1x"

    # real-cluster recovery: tokens identical, redone work == replication lag
    rcfg = dataclasses.replace(PAPER_ARCHS["gpt2-1.5b"].reduced(),
                               dtype="float32", num_layers=8)
    model = build_model(rcfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, rcfg.vocab_size, (4, 8)).astype(np.int32)

    def reqs():
        return [Request(rid=i, prompt=prompts[i].copy(), max_new=6)
                for i in range(4)]

    ref = ServingEngine(rcfg, model, params, 4, microbatch=2).run(reqs())
    eng = ServingEngine(rcfg, model, params, 4, microbatch=2, replication=True)
    rep = eng.run(reqs(), fail_at={9: 2})
    emit("fig15/real_cluster/tokens_identical",
         float(rep.tokens == ref.tokens) * 1e6,
         f"recoveries={rep.recoveries} redone_steps={rep.steps_redone}")
    # headline invariants on the real cluster: recovered tokens are
    # bit-identical and the recovery-time span is populated and bounded
    assert rep.tokens == ref.tokens, "post-recovery tokens diverged"
    assert rep.recoveries == 1, f"expected 1 recovery, got {rep.recoveries}"
    rec = rep.telemetry["histograms"].get("cluster.recovery_s")
    assert rec is not None and rec["count"] >= 1, \
        "cluster.recovery_s span missing from telemetry"
    emit_metric("failures_recovery_model_s_max", rec["max_s"],
                "fail -> first post-restore token, modeled clock")
    assert rec["max_s"] < 60.0, \
        f"recovery time {rec['max_s']:.1f}s unbounded on the modeled clock"

    _export_trace_artifacts(rcfg, model, params, prompts)


def _export_trace_artifacts(rcfg, model, params, prompts) -> None:
    """Flight-recorder export: run the continuous-batching engine with a
    tracer installed and an injected worker death, and write the raw
    ``repro.trace/v1`` dump plus its Perfetto and Prometheus renderings
    into ``$BENCH_JSON_DIR``.  CI uploads these as workflow artifacts and
    gates ``tools/trace_report.py --assert`` on the dump; without
    ``BENCH_JSON_DIR`` only the coverage row is emitted.  The tracer reads
    the modeled clock, which the critical-path replay assumes."""
    tracer = tracing.Tracer(clock=tracing.modeled_ns)
    prev_trace = tracing.install(tracer)
    tele = telemetry.Telemetry()
    prev_tele = telemetry.install(tele)
    try:
        eng = ServingEngine(rcfg, model, params, 2, paged=True, tiered=True,
                            kv_pool_blocks=128, host_cache_blocks=16,
                            ssd_cache_blocks=32, replication=True)
        reqs = [Request(rid=i, prompt=prompts[i].copy(), max_new=6)
                for i in range(4)]
        rep = eng.run_continuous(reqs, max_active=2, fail_at={5: 1})
        assert rep.recoveries == 1, \
            f"traced run: expected 1 recovery, got {rep.recoveries}"
        trace_json = tracer.to_json()
        trace = json.loads(trace_json)
        snapshot = tele.snapshot()
    finally:
        telemetry.uninstall(prev_tele)
        tracing.uninstall(prev_trace)

    report = trace_report.analyze(trace)
    cov = min(r["coverage"] for r in report["requests"].values())
    emit_metric("failures_trace_min_coverage", cov,
                "min per-request named-phase coverage of the traced run")
    assert cov >= 0.95, f"traced run coverage {cov:.4f} < 0.95"

    out_dir = os.environ.get("BENCH_JSON_DIR")
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "failures_trace.json"), "w",
              encoding="utf-8") as f:
        f.write(trace_json)
    with open(os.path.join(out_dir, "failures_trace.perfetto.json"), "w",
              encoding="utf-8") as f:
        f.write(exporters.dumps(exporters.trace_to_perfetto(trace)))
    with open(os.path.join(out_dir, "failures_prometheus.prom"), "w",
              encoding="utf-8") as f:
        f.write(exporters.telemetry_to_prometheus(snapshot))


if __name__ == "__main__":
    run()
