#!/usr/bin/env python3
"""Serve gpt2-1.5b at its published widths on one TPU chip, end to end.

    python3 chip_smoke.py [--seed N]        # one chip: phases (a)-(c)
    python3 chip_smoke.py --chips 4         # four chips: placement check only

Run from the root of a checkout.  Everything goes through the public API:
``build_model`` + ``model.init`` (random weights from ``--seed``), then
``ServingEngine(cfg, model, params, n_workers=2, paged=True,
replication=True).run_continuous(requests, max_active=4)`` with the
engine's defaults (fused rounds, chunked prefill).

(a) Serve: 6 seeded requests, prompts of 96-384 tokens, 32 new tokens each.
    A cold run compiles (set-up); a second run over the same requests is
    the serve time, and counts the compilations it needed (none expected).
(b) Correctness: the logits each request's first token was sampled from
    against ``model.prefill`` of the whole unpipelined model in float32
    under ``jax.default_matmul_precision("highest")`` (`LOGITS_RTOL`).
(c) Fault tolerance: the same requests again with stage 1 killed in
    mid-decode; the tokens must equal (a)'s after one recovery.

``--chips 4`` runs only the placement path: the requests of (a) over four
stages with one stage per chip, then with all four stages on chip 0; the
tokens must agree and each stage's arrays must sit on its own chip.

Times are host-clock seconds of one run; they are bring-up observations,
not benchmark metrics.  No number printed here comes from an assumed
device peak or from the engine's modeled clock.

The last line of standard output is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``; the exit code is
0 only when every phase passed on a TPU.  The script needs a TPU: on any
other platform it fails at its first check.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

ARCH = "gpt2-1.5b"
N_REQUESTS = 6
MAX_NEW = 32
MAX_ACTIVE = 4
# Prompt lengths are drawn from this set: 96-384 tokens, so prompts span two
# to six 64-token prefill chunks, two of them with a ragged final chunk.  A
# small set keeps the distinct (batch, pad) stage shapes, each one a
# compilation, and the reference's prompt lengths few.
PROMPT_LENS = (96, 224, 384)
# Relative L2 error of the served first-token logits against the float32
# reference.  The served path computes in bf16 (unit roundoff 2**-8): 48
# layers of bf16 rounding land near 1e-2 at these widths.  The bound sits
# above that and well below what rounding the weights alone to float8
# (unit roundoff 2**-4) gives, which phase (b) measures and must exceed it.
LOGITS_RTOL = 0.04

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileCounter:
    """Counts XLA compilations and persistent-cache hits in this process
    through JAX's monitoring events."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0

    def install(self) -> "CompileCounter":
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == _BACKEND_COMPILE:
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == _CACHE_HIT:
            self.cache_hits += 1


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B ({peak / 2**30:.3f} GiB)"


def make_requests(cfg, seed: int):
    """The phase-(a) requests, rebuilt fresh for every run (a run appends
    generated tokens to its Request objects)."""
    from repro.serving import Request
    rng = np.random.default_rng(seed)
    lens = rng.choice(PROMPT_LENS, size=N_REQUESTS)
    prompts = [rng.integers(0, cfg.vocab_size, int(n)).astype(np.int32)
               for n in lens]
    return lambda: [Request(rid=i, prompt=p, max_new=MAX_NEW)
                    for i, p in enumerate(prompts)]


def init_params(model, seed: int):
    """Random weights from `seed`, handed to the engine from host memory so
    the device holds only the stages' slices while serving."""
    import jax
    return jax.device_get(model.init(jax.random.PRNGKey(seed)))


def serve(cfg, model, params, requests, *, n_workers: int = 2, devices=None,
          **run_kw):
    """One `run_continuous` over `requests` on a fresh engine, which is
    freed before returning (report, host seconds, {wid: devices of its
    stage parameters}).  `run_continuous` returns after every token is on
    the host (the sampler reads each round's logits back), so the host
    clock at its return covers all device work of the run."""
    from repro.serving import ServingEngine
    eng = ServingEngine(cfg, model, params, n_workers, paged=True,
                        replication=True, devices=devices)
    try:
        t0 = time.perf_counter()
        rep = eng.run_continuous(requests, max_active=MAX_ACTIVE, **run_kw)
        secs = time.perf_counter() - t0
    finally:
        eng.cluster.streamer.close()
    placed = stage_devices(eng)
    del eng
    gc.collect()
    return rep, secs, placed


def check_retired(rep, requests) -> None:
    short = {r.rid: len(rep.tokens[r.rid]) for r in requests
             if len(rep.tokens[r.rid]) != r.max_new}
    if short:
        raise AssertionError(f"requests retired short of {MAX_NEW} tokens: "
                             f"{short}")


def stage_devices(eng):
    """{wid: the devices its stage parameters live on}."""
    import jax
    return {w.wid: sorted({str(d) for a in jax.tree.leaves(w.sp)
                           for d in a.devices()})
            for w in eng.cluster.token_group}


def check_kernel_lowering(cfg, pad: int) -> None:
    """The ragged KV pack of the `kv_pack` family (`kv_pack_ragged_auto`)
    must lower to a compiled TPU kernel, not interpret.  The paged passes
    no longer call it: they write their blocks on the device in XLA."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    layers = cfg.num_layers // 2
    cache = jax.ShapeDtypeStruct(
        (layers, MAX_ACTIVE, pad, cfg.num_kv_heads, cfg.resolved_head_dim),
        jnp.dtype(cfg.dtype))
    starts = jax.ShapeDtypeStruct((MAX_ACTIVE,), jnp.int32)
    text = jax.jit(kops.kv_pack_ragged_auto, static_argnums=(2, 3)).lower(
        cache, starts, 8, 8).as_text()
    found = "tpu_custom_call" in text
    log(f"phase a: kv_pack_ragged lowering {cache.shape} contains "
        f"tpu_custom_call: {found}")
    if not found:
        raise AssertionError("kv_pack_ragged did not lower to a TPU kernel")


# ---------------------------------------------------------------------------
# phases (plain functions over a config, so they can be rehearsed at a small
# size on the CPU)
# ---------------------------------------------------------------------------

def phase_serve(cfg, model, params, requests_fn, counter: CompileCounter):
    """(a) Cold run (set-up, compiles), then the timed run.  Returns the
    timed run's report and the recorded engine.step trace."""
    from repro.core.dejavulib import faults
    c0 = counter.compiles
    reqs = requests_fn()
    rep, cold, _ = serve(cfg, model, params, reqs)
    check_retired(rep, reqs)
    log(f"phase a: set-up (cold run, compiles included) {cold:.3f} s host "
        f"clock, {counter.compiles - c0} compilations")
    c1 = counter.compiles
    reqs = requests_fn()
    recorder = faults.FaultInjector(record=True)
    rep2, warm, _ = serve(cfg, model, params, reqs, fault_injector=recorder)
    check_retired(rep2, reqs)
    n_tok = sum(len(t) for t in rep2.tokens.values())
    log(f"phase a: serve {warm:.3f} s host clock, {len(reqs)} requests, "
        f"{n_tok} tokens, prompts {sorted(r.prompt_len for r in reqs)}, "
        f"compilations after warm-up: {counter.compiles - c1}")
    log(f"phase a: all {len(reqs)} requests retired with {MAX_NEW} tokens "
        f"each")
    if rep2.tokens != rep.tokens:
        raise AssertionError("the warm run's tokens differ from the cold run's")
    return rep2, recorder.trace


def phase_reference(cfg, model, params, requests_fn, served_logits):
    """(b) Served first-token logits against the float32 reference.
    Returns the worst relative L2 error."""
    import jax
    import jax.numpy as jnp

    reqs = requests_fn()
    by_len = {}
    for r in reqs:
        by_len.setdefault(r.prompt_len, []).append(r)
    t0 = time.perf_counter()
    p32 = jax.tree.map(lambda a: jnp.asarray(a).astype(jnp.float32), params)
    ref = {}
    with jax.default_matmul_precision("highest"):
        prefill = jax.jit(model.prefill)
        for group in by_len.values():
            toks = np.stack([r.prompt for r in group])
            logits = jax.block_until_ready(
                prefill(p32, {"tokens": toks})[0])
            for r, row in zip(group, np.asarray(logits)):
                ref[r.rid] = row
    del p32
    gc.collect()
    secs = time.perf_counter() - t0
    errs = {}
    for r in reqs:
        got = np.asarray(served_logits[r.rid], np.float32)
        want = ref[r.rid]
        errs[r.rid] = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        log(f"phase b: rid {r.rid} len {r.prompt_len}: rel L2 err "
            f"{errs[r.rid]:.6f}, max abs err {np.max(np.abs(got - want)):.6f}, "
            f"argmax served {int(np.argmax(got))} ref {int(np.argmax(want))}")
    worst = max(errs.values())
    log(f"phase b: reference {secs:.3f} s host clock (compiles included); "
        f"worst rel L2 err {worst:.6f} vs tolerance {LOGITS_RTOL}")
    # the tolerance must be tight: the served computation with its weights
    # rounded to float8 (one request) has to miss it
    r = reqs[0]
    coarse = jax.tree.map(
        lambda a: np.asarray(a).astype(jnp.float8_e4m3fn).astype(a.dtype)
        if a.ndim >= 2 else a, params)
    got = np.asarray(jax.jit(model.prefill)(
        coarse, {"tokens": r.prompt[None]})[0][0], np.float32)
    del coarse
    gc.collect()
    want = ref[r.rid]
    f8 = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    log(f"phase b: float8-weight control rid {r.rid}: rel L2 err {f8:.6f} "
        f"(must exceed {LOGITS_RTOL})")
    if worst > LOGITS_RTOL:
        raise AssertionError(f"first-token logits off the float32 reference: "
                             f"{worst} > {LOGITS_RTOL}")
    if f8 <= LOGITS_RTOL:
        raise AssertionError("tolerance too loose: float8 weights pass it")
    return worst


def phase_recovery(cfg, model, params, requests_fn, served_tokens, trace):
    """(c) Kill stage 1 at the middle decode step of (a)'s schedule; the
    recovered run must reproduce (a)'s tokens."""
    from repro.core.dejavulib import faults
    decode_steps = [n for point, n, tag in trace
                    if point == "engine.step" and tag.startswith("decode-")]
    nth = decode_steps[len(decode_steps) // 2]
    plan = faults.FaultPlan([faults.FaultSpec("engine.step", nth=nth,
                                              kind="worker_death", wid=1)])
    reqs = requests_fn()
    rep, secs, _ = serve(cfg, model, params, reqs, fault_plan=plan)
    check_retired(rep, reqs)
    same = rep.tokens == served_tokens
    log(f"phase c: stage 1 killed at engine.step #{nth} (decode); "
        f"{secs:.3f} s host clock; failures {rep.failures}, recoveries "
        f"{rep.recoveries}, steps redone {rep.steps_redone}; tokens identical "
        f"to phase a: {same}")
    if not (rep.failures == rep.recoveries == 1):
        raise AssertionError(f"expected one failure and one recovery, got "
                             f"{rep.failures} / {rep.recoveries}")
    if not same:
        raise AssertionError("tokens after recovery differ from phase a")


def phase_four_chips(cfg, model, params, requests_fn, devices):
    """Four stages, one per device, against the same four stages all on
    devices[0]: identical tokens, and each stage on its own device."""
    runs = {}
    for name, devs in (("one stage per chip", list(devices)),
                       ("all stages on chip 0", [devices[0]])):
        reqs = requests_fn()
        rep, secs, placed = serve(cfg, model, params, reqs, n_workers=4,
                                  devices=devs)
        check_retired(rep, reqs)
        log(f"four chips: {name}: {secs:.3f} s host clock (cold, compiles "
            f"included); stage devices {placed}")
        runs[name] = (rep.tokens, placed)
    (spread, spread_at), (single, single_at) = runs.values()
    want = {i: [str(d)] for i, d in enumerate(devices)}
    if spread_at != want:
        raise AssertionError(f"stages not one per chip: {spread_at}")
    if any(v != [str(devices[0])] for v in single_at.values()):
        raise AssertionError(f"stages not all on chip 0: {single_at}")
    log(f"four chips: tokens identical across placements: {spread == single}")
    if spread != single:
        raise AssertionError("tokens differ between placements")


# ---------------------------------------------------------------------------

def run(args, device: dict) -> None:
    import jax

    devices = jax.devices()
    device.update(platform=devices[0].platform, kind=devices[0].device_kind,
                  count=len(devices))
    if devices[0].platform != "tpu":
        raise RuntimeError(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < args.chips:
        raise RuntimeError(f"--chips {args.chips}: JAX found "
                           f"{len(devices)} devices")
    sys.path.insert(0, SRC)
    from repro.configs import get_arch
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import build_model

    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{use_compile_cache()}")
    counter = CompileCounter().install()
    cfg = get_arch(ARCH)
    model = build_model(cfg)
    t0 = time.perf_counter()
    params = init_params(model, args.seed)
    log(f"init: {ARCH} {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.dtype}, {time.perf_counter() - t0:.3f} s host clock")
    requests_fn = make_requests(cfg, args.seed)

    if args.chips == 4:
        phase_four_chips(cfg, model, params, requests_fn, devices[:4])
    else:
        rep, trace = phase_serve(cfg, model, params, requests_fn, counter)
        pad = -(-(max(PROMPT_LENS) + MAX_NEW) // 8) * 8
        check_kernel_lowering(cfg, pad)
        log(f"phase a: peak HBM {peak_bytes(devices[0])}")
        # (c) before (b): the float32 reference comes last, when no engine
        # holds device memory any more
        phase_recovery(cfg, model, params, requests_fn, rep.tokens, trace)
        log(f"phase c: peak HBM {peak_bytes(devices[0])}")
        phase_reference(cfg, model, params, requests_fn,
                        rep.first_token_logits)
        log(f"phase b: peak HBM {peak_bytes(devices[0])}")
    log(f"compilations {counter.compiles}, persistent-cache hits "
        f"{counter.cache_hits}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the one-stage-per-chip placement check")
    args = ap.parse_args(argv)
    device = {"platform": None, "kind": None, "count": 0}
    ok = False
    try:
        run(args, device)
        ok = True
    except Exception:            # every failure ends in "ok": false, exit 1
        traceback.print_exc()
    sys.stderr.flush()
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
