"""Continuous batching over the paged KV pool: token-identity with the
static round-robin path on a mixed-length trace, block-granular streaming
(swap / disaggregation / replication), preemption, and failure recovery."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.registry import PAPER_ARCHS
from repro.models import build_model
from repro.serving import Request, ServingEngine

CFG = dataclasses.replace(PAPER_ARCHS["gpt2-1.5b"].reduced(),
                          dtype="float32", num_layers=8)
MODEL = build_model(CFG)
PARAMS = MODEL.init(jax.random.PRNGKey(0))
RNG = np.random.default_rng(0)

# mixed-length trace: two prompt-length buckets, per-request token budgets
PLENS = [8, 12, 8, 12, 8, 8]
MAXNEW = [6, 3, 7, 4, 3, 6]
PROMPTS = [RNG.integers(0, CFG.vocab_size, (p,)).astype(np.int32)
           for p in PLENS]


def mkreqs(n=len(PLENS)):
    return [Request(rid=i, prompt=PROMPTS[i].copy(), max_new=MAXNEW[i])
            for i in range(n)]


def _tokens_match_static(cont_tokens, static_tokens):
    """Static holds every request to its GROUP's max_new (overgenerating for
    short requests); continuous stops each at its own budget — so compare
    the per-request prefix, which must be bit-identical (greedy)."""
    for rid, toks in cont_tokens.items():
        assert len(toks) == MAXNEW[rid]
        assert static_tokens[rid][:MAXNEW[rid]] == toks, rid
    return True


# 2-stage pipelines keep the fast suite fast; worker count never changes the
# greedy tokens (asserted across depths by the slow tests + test_system)
@pytest.fixture(scope="module")
def static_report():
    eng = ServingEngine(CFG, MODEL, PARAMS, 2, mode="colocated", microbatch=2)
    return eng.run(mkreqs())


@pytest.fixture(scope="module")
def continuous_report():
    eng = ServingEngine(CFG, MODEL, PARAMS, 2, paged=True, kv_pool_blocks=64)
    return eng.run_continuous(mkreqs(), max_active=4)


def test_mixed_length_trace_token_identical(static_report, continuous_report):
    assert _tokens_match_static(continuous_report.tokens, static_report.tokens)


def test_continuous_uses_less_peak_kv(static_report, continuous_report):
    assert 0 < continuous_report.peak_kv_bytes < static_report.peak_kv_bytes


def test_continuous_admits_into_freed_slots(continuous_report):
    # with 6 requests and max_active=4, the earliest retirement happens after
    # round 2 (min max_new beats the prefill) — without backfill the trace
    # could hold 4 for at most 2 rounds; admission into freed slots keeps the
    # batch full for longer
    trace = continuous_report.batch_trace
    assert max(trace) == 4
    assert trace.count(4) >= 4, f"batch not backfilled: {trace}"


@pytest.mark.slow
def test_eos_retires_early():
    reqs = mkreqs()
    base = ServingEngine(CFG, MODEL, PARAMS, 2, paged=True, kv_pool_blocks=64)
    toks = base.run_continuous(mkreqs(), max_active=3).tokens
    eos = toks[0][2]                      # force an early stop for rid 0
    stop = toks[0].index(eos) + 1         # first occurrence may be earlier
    assert stop < MAXNEW[0]
    reqs[0].eos_id = int(eos)
    eng = ServingEngine(CFG, MODEL, PARAMS, 2, paged=True, kv_pool_blocks=64)
    rep = eng.run_continuous(reqs, max_active=3)
    assert len(rep.tokens[0]) == stop and rep.tokens[0] == toks[0][:stop]
    for rid in range(1, len(PLENS)):      # peers unaffected
        assert rep.tokens[rid] == toks[rid]


def test_failure_recovery_regenerates_identical_tokens(static_report):
    eng = ServingEngine(CFG, MODEL, PARAMS, 2, paged=True, replication=True,
                        kv_pool_blocks=64)
    rep = eng.run_continuous(mkreqs(), max_active=4, fail_at={9: 1})
    assert rep.failures == 1 and rep.recoveries == 1
    assert _tokens_match_static(rep.tokens, static_report.tokens)
    kinds = [e["kind"] for e in eng.cluster.controller.events]
    assert "failure" in kinds and "recovery" in kinds


@pytest.mark.slow
@pytest.mark.parametrize("fail_step,wid", [(9, 2), (5, 0), (14, 3)])
def test_failure_recovery_more_points(static_report, fail_step, wid):
    eng = ServingEngine(CFG, MODEL, PARAMS, 4, paged=True, replication=True,
                        kv_pool_blocks=64)
    rep = eng.run_continuous(mkreqs(), max_active=4,
                             fail_at={fail_step: wid})
    assert rep.recoveries == 1
    assert _tokens_match_static(rep.tokens, static_report.tokens)


@pytest.mark.slow
def test_swapping_streams_blocks(static_report):
    eng = ServingEngine(CFG, MODEL, PARAMS, 4, paged=True, swapping=True,
                        kv_pool_blocks=64)
    rep = eng.run_continuous(mkreqs(), max_active=4)
    assert _tokens_match_static(rep.tokens, static_report.tokens)
    assert eng.transfer_summary()["hostlink"] > 0


@pytest.mark.slow
def test_disaggregated_streams_prompt_blocks(static_report):
    eng = ServingEngine(CFG, MODEL, PARAMS, 4, mode="disaggregated",
                        dp_split=(2, 2), paged=True, kv_pool_blocks=64)
    rep = eng.run_continuous(mkreqs(), max_active=4)
    assert _tokens_match_static(rep.tokens, static_report.tokens)
    assert eng.transfer_summary()["net"] > 0      # blocks crossed the wire


@pytest.mark.slow
def test_preemption_under_tiny_pool():
    prompts = [RNG.integers(0, CFG.vocab_size, (8,)).astype(np.int32)
               for _ in range(2)]

    def reqs():
        return [Request(rid=i, prompt=prompts[i].copy(), max_new=10)
                for i in range(2)]

    base = ServingEngine(CFG, MODEL, PARAMS, 2, paged=True, kv_pool_blocks=64)
    rb = base.run_continuous(reqs(), max_active=2)
    eng = ServingEngine(CFG, MODEL, PARAMS, 2, paged=True, kv_pool_blocks=4)
    rp = eng.run_continuous(reqs(), max_active=2)
    assert rp.preemptions >= 1
    assert rp.tokens == rb.tokens


def test_prefix_sharing_saves_blocks():
    shared = RNG.integers(0, CFG.vocab_size, (16,)).astype(np.int32)
    reqs = [Request(rid=i, prompt=shared.copy(), max_new=4) for i in range(3)]
    eng = ServingEngine(CFG, MODEL, PARAMS, 2, paged=True, kv_pool_blocks=64)
    rep = eng.run_continuous(reqs, max_active=3)
    assert len({tuple(t) for t in rep.tokens.values()}) == 1
    w = eng.cluster.token_group[0]
    # 3 seqs x (2 full prompt blocks shared + own growth blocks): well under
    # the 9 blocks an unshared pool would peak at
    assert w.pool.peak_used_blocks < 9


def test_paged_shared_prefix_and_chunk_padding_match_static():
    """The paged data plane against the static cache end to end: prompts
    that share two full blocks, prefilled in ragged 6-token chunks (so the
    chunk-set passes carry padding rows), decode the same greedy tokens as
    the non-paged path, which never touches a block table."""
    shared = RNG.integers(0, CFG.vocab_size, (16,)).astype(np.int32)
    prompts = [np.concatenate([shared, RNG.integers(0, CFG.vocab_size, (n,))
                               ]).astype(np.int32) for n in (1, 1, 5, 5)]

    def reqs():
        return [Request(rid=i, prompt=p.copy(), max_new=5)
                for i, p in enumerate(prompts)]

    static = ServingEngine(CFG, MODEL, PARAMS, 2, mode="colocated",
                           microbatch=2).run(reqs())
    eng = ServingEngine(CFG, MODEL, PARAMS, 2, paged=True, kv_pool_blocks=64,
                        prefill_chunk_tokens=6)
    rep = eng.run_continuous(reqs(), max_active=4)
    # 17- and 21-token prompts chunk as 6,6,5 and 6,6,6,3 in shared passes;
    # unshared, the four sequences would peak at 3 + 3 + 4 + 4 blocks
    assert eng.cluster.token_group[0].pool.peak_used_blocks < 14
    for rid, toks in rep.tokens.items():
        assert toks == static.tokens[rid][:5], rid


def test_max_new_one_emits_exactly_one_token():
    # a request admitted and retired in the same round must not be decoded
    # past its budget by the round's step loop
    reqs = [Request(rid=i, prompt=PROMPTS[i].copy(), max_new=[1, 4, 2][i])
            for i in range(3)]
    eng = ServingEngine(CFG, MODEL, PARAMS, 2, paged=True, kv_pool_blocks=64)
    rep = eng.run_continuous(reqs, max_active=3)
    assert [len(rep.tokens[i]) for i in range(3)] == [1, 4, 2]


@pytest.mark.slow
def test_failure_while_preempted_recovers():
    """A worker dies while a sequence is swapped out by preemption: its swap
    copy dies with the worker, so recovery must rebuild it from the ring
    replica and the rolled-back sequence must regenerate identically."""
    prompts = [RNG.integers(0, CFG.vocab_size, (8,)).astype(np.int32)
               for _ in range(2)]

    def reqs():
        return [Request(rid=i, prompt=prompts[i].copy(), max_new=10)
                for i in range(2)]

    base = ServingEngine(CFG, MODEL, PARAMS, 2, paged=True,
                         kv_pool_blocks=64).run_continuous(reqs(), max_active=2)
    eng = ServingEngine(CFG, MODEL, PARAMS, 2, paged=True, replication=True,
                        kv_pool_blocks=4)
    rep = eng.run_continuous(reqs(), max_active=2, fail_at={12: 1})
    assert rep.preemptions >= 1 and rep.recoveries == 1
    assert rep.tokens == base.tokens


@pytest.mark.slow
def test_paged_repartition_streams_blocks(static_report):
    """Elastic repartitioning mid-flight moves live blocks only."""
    eng = ServingEngine(CFG, MODEL, PARAMS, 4, paged=True, kv_pool_blocks=64)
    cl = eng.cluster
    reqs = mkreqs(2)
    import jax.numpy as jnp
    from repro.serving.sampling import greedy
    toks = {r.rid: [] for r in reqs}
    for r in reqs:
        logits = cl.prefill_seq(r.rid, r.prompt, r.max_new)
        toks[r.rid].append(int(greedy(logits)[0]))
    for step in range(1, 4):
        if step == 2:
            cl.repartition(3, [r.rid for r in reqs])
        for r in reqs:
            last = np.asarray([toks[r.rid][-1]], np.int32)
            logits = cl.decode_seq(r.rid, jnp.asarray(last), step)
            toks[r.rid].append(int(greedy(logits)[0]))
    assert len(cl.token_group) == 3
    for r in reqs:
        assert toks[r.rid] == static_report.tokens[r.rid][:4]


def test_first_token_logits_match_reference_forward(continuous_report,
                                                    static_report):
    """Both serving loops expose the logits each request's first token was
    sampled from; at float32 they agree with one unpipelined prefill."""
    for rep in (continuous_report, static_report):
        assert sorted(rep.first_token_logits) == list(range(len(PLENS)))
        for rid, prompt in enumerate(PROMPTS):
            want = MODEL.prefill(PARAMS, {"tokens": prompt[None]})[0][0]
            np.testing.assert_allclose(rep.first_token_logits[rid],
                                       np.asarray(want), rtol=1e-5,
                                       atol=1e-5)
            assert int(np.argmax(rep.first_token_logits[rid])) == \
                rep.tokens[rid][0]


def test_stages_run_on_their_devices_and_share_compiles():
    """Each stage's parameters sit on the device the cluster gave it, the
    cluster keeps the full weights in host memory, and a second engine over
    the same model reuses the first one's compiled stage functions."""
    dev = jax.local_devices()[0]
    eng = ServingEngine(CFG, MODEL, PARAMS, 2, paged=True, devices=[dev])
    assert all(isinstance(a, np.ndarray)
               for a in jax.tree.leaves(eng.cluster.params))
    for w in eng.cluster.token_group:
        assert w.device == dev
        assert {d for a in jax.tree.leaves(w.sp) for d in a.devices()} == {dev}
    first = eng.run_continuous(mkreqs(2), max_active=2)
    jits = MODEL.stage_jits(first=True, last=False)
    sizes = {k: f._cache_size() for k, f in jits.items()}
    again = ServingEngine(CFG, MODEL, PARAMS, 2, paged=True, devices=[dev])
    assert again.cluster.token_group[0]._decode_batch_paged is \
        jits["decode_batch_paged"]
    second = again.run_continuous(mkreqs(2), max_active=2)
    assert second.tokens == first.tokens
    assert {k: f._cache_size() for k, f in jits.items()} == sizes
