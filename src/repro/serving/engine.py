"""ServingEngine: serving loops over a DejaVuCluster.

Two schedulers share the cluster, the sampler, and the failure machinery:

`run` — microbatch round-robin (FasterTransformer semantics, the paper's
setting): in-flight microbatch slots advance one step per round; a slot only
frees when its WHOLE microbatch drains, and each microbatch holds a padded
prompt+max_new cache for its entire lifetime.

`run_continuous` — continuous batching over the paged KV pool
(`paged=True`): requests are admitted into the running batch the moment
blocks free up, finished sequences retire (and release their blocks)
immediately, and a full pool preempts the youngest sequence (block-granular
swap-out) instead of stalling.  With greedy sampling its outputs are
bit-identical to `run`'s, which tests assert.

`tiered=True` additionally backs every stage's pool with the HBM→host→SSD
hierarchy of `repro.kvcache.tiers`: preemption swaps through the tiers
(write-behind, spilling to SSD under host pressure), retired prompt blocks
enter a persistent prefix cache, and a new request whose prompt prefix
matches streams those blocks back in instead of re-prefilling them
(`EngineReport.prefill_tokens_saved` / `tier_stats`).

Failure injection / detection / 4-step recovery run between steps in both
loops; recovered work rolls back to its last replicated step and regenerates
bit-identically.
"""
from __future__ import annotations

from dataclasses import asdict, dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import telemetry
from repro.core import tracing
from repro.core.cluster import DejaVuCluster
from repro.core.dejavulib import faults
from repro.core.dejavulib.transport import DEFAULT_HW, HardwareModel
from repro.kvcache.paged import PoolExhausted
from repro.serving.request import Microbatch, Request, form_microbatches
from repro.serving.sampling import greedy
from repro.serving.scheduler import RoundScheduler, StepPlan


class _SingleSeq:
    """Adapter: one request viewed as a 1-element microbatch for `_emit`."""

    def __init__(self, r: Request):
        self.requests = [r]


@dataclass
class EngineReport:
    tokens: Dict[int, List[int]]            # rid -> generated tokens
    steps_executed: int = 0
    steps_redone: int = 0
    failures: int = 0
    recoveries: int = 0
    preemptions: int = 0
    peak_kv_bytes: int = 0
    # one entry per continuous-batching round: live batch size that round
    batch_trace: List[int] = field(default_factory=list)
    transfer_bytes: Dict[str, int] = field(default_factory=dict)
    events: List[dict] = field(default_factory=list)
    # cross-request prefix reuse through the tier hierarchy (tiered=True)
    prefill_tokens_total: int = 0
    prefill_tokens_saved: int = 0           # prompt tokens served from cache
    tier_stats: Dict[str, float] = field(default_factory=dict)
    # one entry per continuous-batching round that executed >=1 decode step:
    # modeled prefill seconds co-scheduled in that round (the decode stall a
    # long prompt inflicts; chunk-interleaving bounds it to one chunk pass)
    prefill_stall_trace: List[float] = field(default_factory=list)
    # one entry per continuous-batching round: pipeline passes executed that
    # round.  Fused rounds run ONE batched decode pass (plus one chunk-set
    # pass while prefills are in flight, plus admission first-passes); the
    # per-sequence oracle path runs one pass per live sequence per round.
    pass_trace: List[int] = field(default_factory=list)
    # one dict per fault the run's FaultInjector realized (point, n, kind,
    # tag, wid) — lets tests assert WHERE a fault landed, not just that
    # failures/recoveries were counted (see repro.core.dejavulib.faults)
    fault_trace: List[dict] = field(default_factory=list)
    # telemetry snapshot (schema `repro.telemetry/v1`): counters, gauges,
    # SLO histograms (TTFT / inter-token / queue wait / recovery time) and
    # span aggregates on the modeled clock — see repro.core.telemetry and
    # docs/observability.md.  Cumulative across runs when an ambient
    # registry is installed (benchmarks do this to aggregate a module).
    telemetry: Dict[str, object] = field(default_factory=dict)
    # rid -> the logits its first token was sampled from ([V], as served):
    # what a correctness check compares with a reference forward
    first_token_logits: Dict[int, np.ndarray] = field(default_factory=dict)


class ServingEngine:
    def __init__(self, cfg: ArchConfig, model, params, n_workers: int, *,
                 mode: str = "colocated",
                 dp_split: Optional[tuple] = None,
                 microbatch: int = 2,
                 swapping: bool = False, replication: bool = False,
                 compress_replicas: bool = False,
                 paged: bool = False, kv_block_size: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 tiered: bool = False,
                 host_cache_blocks: Optional[int] = None,
                 ssd_cache_blocks: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 fused_rounds: Optional[bool] = None,
                 devices: Optional[Sequence[jax.Device]] = None,
                 hw: HardwareModel = DEFAULT_HW,
                 sampler: Callable = greedy):
        self.cfg = cfg
        self.microbatch = microbatch
        self.sampler = sampler
        self.cluster = DejaVuCluster(cfg, model, params, n_workers, mode=mode,
                                     dp_split=dp_split, swapping=swapping,
                                     replication=replication,
                                     compress_replicas=compress_replicas, hw=hw,
                                     paged=paged, kv_block_size=kv_block_size,
                                     kv_pool_blocks=kv_pool_blocks,
                                     tiered=tiered,
                                     host_cache_blocks=host_cache_blocks,
                                     ssd_cache_blocks=ssd_cache_blocks,
                                     prefill_chunk_tokens=prefill_chunk_tokens,
                                     fused_rounds=fused_rounds,
                                     devices=devices)
        # rid -> modeled clock of its last emitted token (inter-token SLO)
        self._emit_clock: Dict[int, float] = {}

    # ------------------------------------------------------------------
    # telemetry plumbing (shared by both serving loops)
    # ------------------------------------------------------------------
    def _install_telemetry(self) -> Tuple[telemetry.Telemetry, bool]:
        """Reuse the ambient registry when one is installed (benchmarks
        install one per module to aggregate across runs); otherwise create
        a fresh per-run registry.  Returns (registry, created)."""
        t = telemetry.current()
        if t is not None:
            return t, False
        t = telemetry.Telemetry()
        telemetry.install(t)
        return t, True

    def _teardown_telemetry(self, t: telemetry.Telemetry, created: bool,
                            report: EngineReport) -> None:
        # write-behinds still queued count on the streamer thread: let them
        # land so two identical runs snapshot identical counters
        self.cluster.streamer.quiesce()
        report.telemetry = t.snapshot()
        if created:
            telemetry.uninstall()

    def _tele_emit(self, requests: List[Request], i: int) -> None:
        """Per-token SLO observations at emit time, on the modeled clock:
        TTFT (arrival -> first token), inter-token gap, and — at the first
        token emitted after a failure's restore — the recovery-time span."""
        t = telemetry.current()
        if t is None:
            return
        now = t.clock_s
        trc = tracing.active()
        for r in requests:
            if i == 0:
                ttft = max(now - r.arrival, 0.0)
                t.observe("engine.ttft_s", ttft)
                if trc:
                    tracing.event("emit.first_token", rid=r.rid,
                                  ttft_ns=int(round(ttft * 1e9)))
            else:
                prev = self._emit_clock.get(r.rid)
                if prev is not None:
                    t.observe("engine.inter_token_s", max(now - prev, 0.0))
            self._emit_clock[r.rid] = now
        for mark in self.cluster.take_recovery_marks():
            rec = max(now - mark, 0.0)
            t.observe("cluster.recovery_s", rec)
            if trc:
                # failure -> first post-restore token, on the modeled clock
                tracing.event("recovery.first_token",
                              recovery_ns=int(round(rec * 1e9)))

    # ------------------------------------------------------------------
    # fault-injection plumbing (shared by both serving loops)
    # ------------------------------------------------------------------
    def _install_faults(self, fail_at, fault_plan, fault_injector,
                        report: EngineReport
                        ) -> Tuple[Optional[faults.FaultInjector], object]:
        """Bind this run's FaultInjector and install it as the process-wide
        active injector.  The legacy ``fail_at={gstep: wid}`` kwarg becomes
        ``engine.step`` worker_death specs (that point fires exactly once
        per scheduled step, so occurrence == gstep).  Returns (injector,
        previously-active injector) for `_teardown_faults`."""
        if fault_injector is None and not fail_at and fault_plan is None:
            return None, None
        inj = fault_injector if fault_injector is not None \
            else faults.FaultInjector(fault_plan)
        for g, w in sorted((fail_at or {}).items()):
            inj.plan.add(faults.FaultSpec("engine.step", nth=g,
                                          kind="worker_death", wid=w))

        def _kill(wid):
            self.cluster.inject_failure(wid)
            report.failures += 1

        inj.worker_killer = _kill
        prev = faults.current()
        faults.install(inj)
        return inj, prev

    @staticmethod
    def _teardown_faults(inj, prev, report: EngineReport) -> None:
        if inj is None:
            return
        if prev is None:
            faults.uninstall()
        else:
            faults.install(prev)
        report.fault_trace = [asdict(f) for f in inj.fired]

    # ------------------------------------------------------------------
    def run(self, requests: List[Request], *,
            fail_at: Optional[Dict[int, int]] = None,
            migrate_at: Optional[Dict[int, int]] = None,
            repartition_at: Optional[Dict[int, int]] = None,
            fault_plan: Optional[faults.FaultPlan] = None,
            fault_injector: Optional[faults.FaultInjector] = None
            ) -> EngineReport:
        """fail_at / migrate_at: {global_step: worker_id}; repartition_at:
        {global_step: new_depth}.  `fault_plan` / `fault_injector` drive the
        general injection layer (`repro.core.dejavulib.faults`); `fail_at`
        is the legacy shim for worker death at a step boundary."""
        migrate_at = dict(migrate_at or {})
        repartition_at = dict(repartition_at or {})
        mbs = form_microbatches(requests, self.microbatch)
        queue = list(mbs)
        depth = len(self.cluster.token_group)
        slots: List[Optional[Microbatch]] = [None] * depth
        report = EngineReport(tokens={r.rid: r.tokens for r in requests})
        inj, prev = self._install_faults(fail_at, fault_plan, fault_injector,
                                         report)
        tele, tele_created = self._install_telemetry()
        self._emit_clock = {}
        gstep = 0
        slot_rounds = slot_busy = 0   # microbatch-slot occupancy -> bubbles

        def active_ids() -> List[int]:
            return [s.mb for s in slots if s is not None]

        try:
            while any(s is not None for s in slots) or queue:
                for q in range(depth):
                    if slots[q] is None and queue:
                        slots[q] = queue.pop(0)
                slot_rounds += depth
                slot_busy += sum(s is not None for s in slots)
                with telemetry.span("round"), tracing.span("round"):
                    for q in range(depth):
                        mb = slots[q]
                        if mb is None:
                            continue
                        gstep += 1
                        # --- scheduled control events -----------------------
                        faults.fire("engine.step", tag=f"mb{mb.mb}")
                        if gstep in migrate_at:
                            res = self.cluster.migrate_worker(
                                migrate_at.pop(gstep), active_ids())
                            report.recoveries += 1
                            self._apply_resume(res, slots, report)
                        if gstep in repartition_at:
                            self.cluster.repartition(
                                repartition_at.pop(gstep), active_ids())

                        # --- advance this slot one step ---------------------
                        try:
                            self._advance(mb, report)
                        except RuntimeError:
                            # dead worker hit mid-pipeline: detect + recover
                            resume = self.cluster.detect_and_recover(
                                active_ids())
                            report.recoveries += 1
                            self._apply_resume(resume, slots, report)
                            self._advance(mb, report)  # re-execute the step
                        if mb.done:
                            slots[q] = None
        finally:
            # empty microbatch slots ARE the pipeline bubbles of the paper's
            # joint/FasterTransformer setting (slots drain at the speed of
            # their slowest member)
            tele.gauge("engine.bubble_frac",
                       1.0 - slot_busy / slot_rounds if slot_rounds else 0.0)
            self._teardown_faults(inj, prev, report)
            self._teardown_telemetry(tele, tele_created, report)
        report.peak_kv_bytes = self.cluster.kv_bytes_peak
        return report

    # ------------------------------------------------------------------
    # continuous batching over the paged KV pool
    # ------------------------------------------------------------------
    def run_continuous(self, requests: List[Request], *,
                       max_active: int = 4,
                       fail_at: Optional[Dict[int, int]] = None,
                       fault_plan: Optional[faults.FaultPlan] = None,
                       fault_injector: Optional[faults.FaultInjector] = None
                       ) -> EngineReport:
        """Continuous-batching loop (requires ``paged=True``).

        The policy (admission, resume, preemption victims, retirement) lives
        in `RoundScheduler`; this method is the thin driver that executes one
        `StepPlan` per round: (1) the scheduler resumes preempted / admits
        queued requests into freed pool space, (2) every live request
        advances one step, (3) finished requests retire, returning their
        blocks.  `fail_at` counts per-request steps exactly like `run`'s
        global steps.  Each request generates exactly `max_new` tokens (or
        stops at eos) — unlike `run`, no request is held hostage by the
        longest peer in its microbatch.

        A request whose prompt plus `max_new` tokens would outgrow the
        block tables' fixed width raises ValueError before any request is
        served.

        Prompts longer than `prefill_chunk_tokens` prefill CHUNK-INTERLEAVED:
        each round runs one chunk pass per in-flight prefill alongside one
        decode step per running sequence, so a long prompt stalls co-resident
        decodes by at most one chunk instead of its whole length
        (`EngineReport.prefill_stall_trace` records the per-round stall).

        Fused rounds are the DEFAULT (`ArchConfig.fused_rounds=True`): for
        every config the cluster's `fused_ok` gate accepts — all dense/moe
        attention variants, ALiBi (bloom) and sliding-window+meta included —
        the round's decodes run as ONE batched pipeline pass over ragged
        per-sequence lengths and all in-flight chunk prefills pack into one
        chunk-set pass — `EngineReport.pass_trace` records the per-round
        pass count — with outputs token-identical to the per-sequence
        oracle path.  Pass ``fused_rounds=False`` to the engine to force
        the oracle path; unsupported families (ssm/hybrid/encdec/vlm) fall
        back to it automatically.
        """
        cl = self.cluster
        assert cl.paged, "run_continuous requires ServingEngine(..., paged=True)"
        for r in requests:   # admission: refuse before any request is served
            cl.check_context_fits(r.prompt_len + r.max_new - 1)
        sched = RoundScheduler(cl, requests, max_active=max_active)
        report = EngineReport(tokens={r.rid: r.tokens for r in requests})
        inj, prev = self._install_faults(fail_at, fault_plan, fault_injector,
                                         report)
        tele, tele_created = self._install_telemetry()
        self._emit_clock = {}
        clock0 = tele.clock_s
        fused = cl.fused_ok
        try:
            while sched.pending():
                cl.round_prefill_model_s = 0.0
                self._round_decodes = 0
                self._round_passes = 0
                with telemetry.span("round"), tracing.span("round"):
                    plan = sched.plan_round(
                        lambda r: self._advance_seq(r, sched, report))
                    report.batch_trace.append(plan.n_active)
                    if tracing.active():
                        tracing.event("sched.plan", round=plan.round_idx,
                                      n_active=plan.n_active,
                                      rids=[r.rid for r in plan.work])
                    if fused:
                        self._execute_round_fused(plan, sched, report)
                    else:
                        self._execute_round(plan, sched, report)
                    # --- retire finished sequences (blocks free at once) ----
                    sched.retire()
                if self._round_decodes:
                    report.prefill_stall_trace.append(cl.round_prefill_model_s)
                report.pass_trace.append(self._round_passes)
        finally:
            # bubble fraction: share of the run's modeled time that decodes
            # spent stalled behind co-scheduled prefill passes (chunked
            # prefill exists to bound exactly this)
            busy = tele.clock_s - clock0
            stall = sum(report.prefill_stall_trace)
            tele.gauge("engine.bubble_frac",
                       stall / busy if busy > 0.0 else 0.0)
            self._teardown_faults(inj, prev, report)
            self._teardown_telemetry(tele, tele_created, report)
        report.peak_kv_bytes = cl.kv_bytes_peak
        report.prefill_tokens_total = cl.prefill_tokens_total
        report.prefill_tokens_saved = cl.prefill_tokens_saved
        if cl.tiered:
            report.tier_stats = cl.tier_stats()
        return report

    # ------------------------------------------------------------------
    # per-sequence oracle path: one pipeline pass per request per round
    # ------------------------------------------------------------------
    def _execute_round(self, plan: StepPlan, sched: RoundScheduler,
                       report: EngineReport) -> None:
        for r in plan.work:
            if not sched.is_active(r.rid):
                continue        # dropped by a mid-round preemption
            if sched.next_step[r.rid] >= r.max_new or r.done:
                continue        # budget spent at admission (or eos'd)
            while True:
                try:
                    self._advance_seq(r, sched, report)
                    break
                except PoolExhausted:
                    self._preempt_victim_or_raise(sched, report,
                                                  exclude=(r.rid,))

    # ------------------------------------------------------------------
    # fused rounds: ONE batched pass per round (+ one chunk-set pass while
    # prefills are in flight)
    # ------------------------------------------------------------------
    def _execute_round_fused(self, plan: StepPlan, sched: RoundScheduler,
                             report: EngineReport) -> None:
        # snapshot the round's split BEFORE running anything: like the oracle
        # path, every request advances ONE step per round — a prompt whose
        # prefill completes this round decodes only from the NEXT round on
        pf = [r for r in plan.work if sched.is_active(r.rid)
              and sched.next_step[r.rid] == 0 and not r.done]
        dec0 = [r for r in plan.work if sched.next_step[r.rid] >= 1]
        if pf and not self._fused_prefill_pass(pf, sched, report):
            return              # a worker died: recovered state runs next round
        while True:
            dec = [r for r in dec0 if sched.is_active(r.rid) and not r.done
                   and 1 <= sched.next_step[r.rid] < r.max_new]
            if not dec:
                return
            try:
                self._fused_decode_pass(dec, sched, report)
                return
            except PoolExhausted:
                # same victim policy as the oracle path, except the whole
                # batch is "the current request": shrink the round instead —
                # preempt the youngest resident sequence (possibly a batch
                # member) and retry the pass without it
                if len(dec) == 1:
                    self._preempt_victim_or_raise(sched, report,
                                                  exclude=(dec[0].rid,))
                else:
                    self._preempt_victim_or_raise(sched, report)

    def _preempt_victim_or_raise(self, sched: RoundScheduler,
                                 report: EngineReport,
                                 exclude=()) -> None:
        """Handle a full pool mid-round: swap out the scheduler's chosen
        victim and let the caller retry, or re-raise the active
        PoolExhausted when nothing preemptible remains."""
        victim = sched.pick_victim(exclude=exclude)
        if victim is None:
            raise
        self.cluster.preempt_seq(victim.rid)
        sched.preempt(victim)
        report.preemptions += 1

    def _fused_prefill_pass(self, pf: List[Request], sched: RoundScheduler,
                            report: EngineReport) -> bool:
        """Advance every in-flight prefill one chunk: chunk-mode prefills
        pack into ONE pipeline pass; oracle-mode ones (chunking disabled)
        fall back to one pass each.  Returns False if a worker death was
        recovered (the round ends; rolled-back work reruns next round)."""
        cl = self.cluster
        for r in pf:            # one logical step per packed prefill, so
            # engine.step occurrences land like the oracle path's
            faults.fire("engine.step", tag=f"prefill-r{r.rid}")
        try:
            for r in pf:
                # staging allocates (adopt_prefix / whole-prompt tables), and
                # the oracle-mode passes below append — both can hit a full
                # pool, which preempts a victim and retries like the oracle
                # path (a mid-prefill sequence is never a victim, so retrying
                # cannot disturb the prefills already staged)
                while not cl.prefill_pending(r.rid):
                    try:
                        cl.prefill_seq_begin(r.rid, r.prompt, r.max_new)
                    except PoolExhausted:
                        self._preempt_victim_or_raise(sched, report)
            chunk = [r for r in pf if cl.prefill_mode(r.rid) == "chunk"]
            rest = [r for r in pf if cl.prefill_mode(r.rid) != "chunk"]
            if chunk:
                out = cl.prefill_chunkset_pass([r.rid for r in chunk])
                self._round_passes += 1
                report.steps_executed += len(chunk)
                for r in chunk:
                    self._finish_prefill_step(r, out[r.rid], sched, report)
            for r in rest:
                while True:
                    try:
                        logits = cl.prefill_seq_step(r.rid)
                        break
                    except PoolExhausted:
                        self._preempt_victim_or_raise(sched, report)
                self._round_passes += 1
                report.steps_executed += 1
                self._finish_prefill_step(r, logits, sched, report)
        except RuntimeError:
            self._recover_fused(sched, report)
            return False
        return True

    def _finish_prefill_step(self, r: Request, logits, sched,
                             report: EngineReport) -> None:
        if logits is None:
            return              # prefill still in flight
        report.first_token_logits[r.rid] = np.asarray(logits[0])
        tok = self.sampler(logits, 0)
        self._emit(_SingleSeq(r), tok, 0)
        sched.next_step[r.rid] = 1

    def _fused_decode_pass(self, dec: List[Request], sched: RoundScheduler,
                           report: EngineReport) -> None:
        cl = self.cluster
        for r in dec:
            faults.fire("engine.step", tag=f"decode-r{r.rid}")
        rids = [r.rid for r in dec]
        steps = [sched.next_step[r.rid] for r in dec]
        last = np.asarray([r.tokens[s - 1] for r, s in zip(dec, steps)],
                          np.int32)
        try:
            logits = cl.decode_batch(rids, last, steps)
        except RuntimeError:
            self._recover_fused(sched, report)
            return              # rolled-back steps rerun next round
        self._round_passes += 1
        with tracing.span("engine.sample", n=len(dec)):
            for i, (r, s) in enumerate(zip(dec, steps)):
                tok = self.sampler(logits[i:i + 1], s)
                self._emit(_SingleSeq(r), tok, s)
                sched.next_step[r.rid] = s + 1
                self._round_decodes += 1
                report.steps_executed += 1

    def _recover_fused(self, sched: RoundScheduler,
                       report: EngineReport) -> None:
        """Detect-and-recover after a worker died inside a fused pass: every
        covered sequence rolls back to its last replicated step (mid-prefill
        ones restart from scratch), exactly like the per-sequence path —
        the next rounds regenerate the rolled-back tokens bit-identically."""
        cl = self.cluster
        covered = sched.covered()
        live = [a.rid for a in covered if not a.done]
        resume = cl.detect_and_recover(live)
        report.recoveries += 1
        self._apply_resume_seqs(resume, covered, sched.next_step, report)
        for rr in covered:
            if sched.next_step.get(rr.rid, 1) == 0:
                cl.abort_prefill(rr.rid)

    def _advance_seq(self, r: Request, sched: RoundScheduler,
                     report: EngineReport) -> None:
        """One per-request step (prefill if next_step==0, else decode), with
        the same failure-injection / detect-recover contract as `_advance`.
        Preempted sequences join the recovery set: their swap copies on the
        failed worker die with it, so they too must rebuild from replicas
        and roll back."""
        cl = self.cluster
        next_step = sched.next_step
        faults.fire("engine.step", tag=f"r{r.rid}")
        covered = sched.covered()
        live = [a.rid for a in covered if not a.done]
        if r.rid not in live:
            live.append(r.rid)
        try:
            self._step_seq(r, next_step, report)
        except RuntimeError:
            resume = cl.detect_and_recover(live)
            report.recoveries += 1
            self._apply_resume_seqs(resume, covered + [r], next_step, report)
            # a worker death takes mid-prefill partial tables with it (their
            # sequences have no replicated steps to restore from): restart
            # those prefills from scratch on the recovered cluster
            for rr in covered + [r]:
                if next_step.get(rr.rid, 1) == 0:
                    cl.abort_prefill(rr.rid)
            self._step_seq(r, next_step, report)

    def _step_seq(self, r: Request, next_step: Dict[int, int],
                  report: EngineReport) -> None:
        """One pipeline pass for one request: a (chunk of) prefill while
        next_step is 0 — next_step stays 0 until the final chunk returns the
        prefill logits — else one decode step."""
        cl = self.cluster
        i = next_step[r.rid]
        self._round_passes += 1
        if i == 0:
            if not cl.prefill_pending(r.rid):
                cl.prefill_seq_begin(r.rid, r.prompt, r.max_new)
            logits = cl.prefill_seq_step(r.rid)
            report.steps_executed += 1
            if logits is None:
                return                   # prefill still in flight
            report.first_token_logits[r.rid] = np.asarray(logits[0])
            tok = self.sampler(logits, 0)
        else:
            last = np.asarray([r.tokens[i - 1]], np.int32)
            logits = cl.decode_seq(r.rid, jnp.asarray(last), i)
            tok = self.sampler(logits, i)
            self._round_decodes += 1
            report.steps_executed += 1
        self._emit(_SingleSeq(r), tok, i)
        next_step[r.rid] = i + 1

    def _apply_resume_seqs(self, resume: Dict[int, int],
                           requests: List[Request],
                           next_step: Dict[int, int],
                           report: EngineReport) -> None:
        seen = set()
        for r in requests:
            if r.rid in seen or r.rid not in resume:
                continue
            seen.add(r.rid)
            rr = max(resume[r.rid], 0)
            redone = max(0, next_step[r.rid] - rr)
            report.steps_redone += redone
            next_step[r.rid] = min(next_step[r.rid], rr)
            del r.tokens[next_step[r.rid]:]

    # ------------------------------------------------------------------
    def _advance(self, mb: Microbatch, report: EngineReport) -> None:
        cl = self.cluster
        if mb.next_step == 0:
            tokens = jnp.asarray(mb.batch_prompts())
            logits = cl.prefill_mb(mb.mb, tokens, mb.n_new)
            for b, r in enumerate(mb.requests):
                report.first_token_logits[r.rid] = np.asarray(logits[b])
            tok = self.sampler(logits, 0)
            self._emit(mb, tok, 0)
            mb.next_step = 1
        else:
            i = mb.next_step
            last = np.asarray([r.tokens[i - 1] if len(r.tokens) >= i else 0
                               for r in mb.requests], np.int32)
            logits = cl.decode_mb(mb.mb, jnp.asarray(last), i)
            tok = self.sampler(logits, i)
            self._emit(mb, tok, i)
            mb.next_step = i + 1
        report.steps_executed += 1
        # n_new tokens total: token_0 from prefill + decode steps 1..n_new-1
        if mb.next_step >= mb.n_new:
            mb.done = True

    def _emit(self, mb: Microbatch, tok: np.ndarray, i: int) -> None:
        for b, r in enumerate(mb.requests):
            if len(r.tokens) == i:
                r.tokens.append(int(tok[b]))
            else:                      # regeneration after rollback
                r.tokens[i] = int(tok[b])
            if r.eos_id is not None and int(tok[b]) == r.eos_id:
                r.done = True
        self._tele_emit(mb.requests, i)

    def _apply_resume(self, resume: Dict[int, int],
                      slots: List[Optional[Microbatch]],
                      report: EngineReport) -> None:
        for s in slots:
            if s is not None and s.mb in resume:
                r = resume[s.mb]
                redone = max(0, s.next_step - r)
                report.steps_redone += redone
                s.next_step = min(s.next_step, max(r, 0))
                for req in s.requests:   # truncate tokens beyond resume point
                    del req.tokens[s.next_step:]

    # ------------------------------------------------------------------
    def transfer_summary(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        groups = set(self.cluster.prompt_group + self.cluster.token_group)
        transports = [self.cluster.net]
        for w in groups:
            transports += [w.cache.net, w.cache.hostlink, w.cache.local]
            if getattr(w, "tier", None) is not None:
                transports += [w.tier.hostlink, w.tier.ssdlink]
        for t in transports:
            out[t.kind] = out.get(t.kind, 0) + t.bytes_total()
        return out
