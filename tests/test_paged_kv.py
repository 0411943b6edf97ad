"""Paged KV pool: allocator invariants (fuzz + hypothesis), prefix sharing,
copy-on-write, defrag, paged-attention kernel vs the dense reference, and
paged-aware planning."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core import costmodel as cm
from repro.core.planner import MachineSpec, min_token_depth, plan
from repro.kernels import ref
from repro.kernels.decode_attention import paged_decode_attention
from repro.kvcache.paged import (BlockPool, PagedKVCache, PoolExhausted,
                                 blocks_for, gather_layer, window_blocks)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                       # optional dev dep (requirements-dev.txt)
    HAVE_HYPOTHESIS = False

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# allocator invariants
# ---------------------------------------------------------------------------

def _check_invariants(pool: BlockPool):
    free = set(pool._free)
    multiplicity = {}
    for table in pool.tables.values():
        for bid in table:
            multiplicity[bid] = multiplicity.get(bid, 0) + 1
    # free XOR referenced, never both; ref count == table multiplicity
    assert not (free & set(multiplicity)), "block both free and referenced"
    for bid, blk in enumerate(pool.blocks):
        assert blk.ref == multiplicity.get(bid, 0)
        assert (bid in free) == (blk.ref == 0)
    assert len(free) + sum(1 for b in pool.blocks if b.ref > 0) == pool.num_blocks


def _run_ops(num_blocks, block_size, ops):
    """Interpret an op tape against a pool; ops are (kind, seq, arg)."""
    pool = BlockPool(num_blocks, block_size)
    live = set()
    for kind, seq, arg in ops:
        try:
            if kind == "alloc" and seq not in live:
                pool.allocate(seq, arg % (num_blocks * block_size) + 1,
                              token_ids=list(range(arg % 40)) if arg % 2 else None)
                live.add(seq)
            elif kind == "append" and seq in live:
                pool.append(seq, 1 + arg % 3)
            elif kind == "free" and seq in live:
                pool.free_seq(seq)
                live.discard(seq)
            elif kind == "truncate" and seq in live:
                pool.truncate(seq, max(1, pool.seq_lens[seq] - arg % 5))
        except PoolExhausted:
            pass                     # legal outcome under a random tape
        _check_invariants(pool)
    for seq in list(live):
        pool.free_seq(seq)
    _check_invariants(pool)
    assert pool.num_free() == pool.num_blocks, "leak: blocks not returned"


def test_fuzz_alloc_free_never_leaks():
    rng = np.random.default_rng(0)
    kinds = ["alloc", "append", "append", "free", "truncate"]
    for trial in range(15):
        ops = [(kinds[rng.integers(len(kinds))], int(rng.integers(6)),
                int(rng.integers(64))) for _ in range(60)]
        _run_ops(int(rng.integers(4, 24)), int(rng.integers(2, 9)), ops)


if HAVE_HYPOTHESIS:
    @settings(max_examples=60, deadline=None)
    @given(num_blocks=st.integers(2, 32), block_size=st.integers(1, 8),
           ops=st.lists(st.tuples(
               st.sampled_from(["alloc", "append", "free", "truncate"]),
               st.integers(0, 5), st.integers(0, 63)), max_size=40))
    def test_property_no_double_alloc_no_leak(num_blocks, block_size, ops):
        _run_ops(num_blocks, block_size, ops)


def test_pool_exhaustion_raises():
    pool = BlockPool(2, 4)
    pool.allocate(0, 8)
    with pytest.raises(PoolExhausted):
        pool.allocate(1, 1)
    assert not pool.can_allocate(1) and pool.can_allocate(0)


def test_prefix_sharing_and_copy_on_write():
    pool = BlockPool(16, 4)
    toks = list(range(10))
    t1, fresh1 = pool.allocate(1, 10, token_ids=toks)
    t2, fresh2 = pool.allocate(2, 10, token_ids=toks)
    assert t1[:2] == t2[:2] and t1[2] != t2[2]     # full blocks shared
    assert fresh1 == [0, 1, 2] and fresh2 == [2]
    assert pool.blocks[t1[0]].ref == 2
    # seq 2 appends into its own partial block: no CoW needed
    assert pool.append(2) == []
    # force CoW: a sequence ending exactly on a shared full block
    t3, _ = pool.allocate(3, 8, token_ids=toks[:8])
    assert t3 == t1[:2]
    cow = pool.append(3)               # grows into a NEW block, no divergence
    assert cow == [] and len(pool.tables[3]) == 3
    pool.free_seq(1); pool.free_seq(2); pool.free_seq(3)
    assert pool.num_free() == pool.num_blocks


def test_cow_on_shared_partial_block():
    # sharing a partial tail can only arise via append over a shared FULL
    # block boundary; emulate divergence by ref-bumping then appending
    pool = BlockPool(8, 4)
    pool.allocate(1, 4, token_ids=list(range(4)))
    t2, _ = pool.allocate(2, 4, token_ids=list(range(4)))
    pool.truncate(2, 3)                # seq 2 now ends INSIDE the shared block
    cow = pool.append(2)
    assert len(cow) == 1               # diverged: copy-on-write
    old, new = cow[0]
    assert pool.tables[2] == [new] and pool.tables[1] == [old]
    pool.free_seq(1); pool.free_seq(2)
    assert pool.num_free() == pool.num_blocks


def _dense(pages, table, layers, h=2, d=4):
    """Every layer's dense cache gathered on the device by block table
    -> numpy [Lstage, B, T*bs, H, D]."""
    return np.stack([np.asarray(gather_layer(pages, jnp.asarray(table), l,
                                             h, d))
                     for l in range(layers)])


def _blocks(pages, bs, h=2, d=4):
    """A stage's page array [Lstage, N, bs*H*D] as blocks [N, Lstage, bs,
    H, D] (numpy)."""
    a = np.asarray(pages)
    return a.reshape(a.shape[0], a.shape[1], bs, h, d).swapaxes(0, 1)


def test_defrag_compacts_and_preserves_pages():
    pool = BlockPool(16, 4)
    pages = PagedKVCache(pool, layers=2, num_kv_heads=2, head_dim=4)
    t1, _ = pool.allocate(1, 8)
    t2, _ = pool.allocate(2, 6)
    one = {"k": np.ones((2, 4, 2, 4), np.float32)}
    one["v"] = one["k"]
    pages.install_blocks([(b, one) for b in t1]
                         + [(b, {"k": 2 * one["k"], "v": 2 * one["v"]})
                            for b in t2])
    pool.free_seq(1)
    moves = pool.defrag()
    pages.apply_defrag(moves)
    _check_invariants(pool)
    assert pool.tables[2] == [0, 1]               # compacted to lowest ids
    assert isinstance(pages.k, jax.Array)
    dense = _dense(pages.k, pool.padded_tables([2], 2), 2)
    assert (dense[:, :, :6] == 2.0).all()


def test_write_window_gather_roundtrip():
    pool = BlockPool(8, 4)
    pages = PagedKVCache(pool, layers=3, num_kv_heads=2, head_dim=4)
    pool.allocate(7, 10)
    rng = np.random.default_rng(0)
    win = {leaf: rng.standard_normal((3, 1, 10, 2, 4)).astype(np.float32)
           for leaf in ("k", "v")}
    table = pool.padded_tables([7], 3)
    # the window as the slots of its blocks from block 0, zero past it
    nb = window_blocks(10, 4)
    slots = {leaf: np.pad(w, ((0, 0), (0, 0), (0, nb * 4 - 10), (0, 0),
                              (0, 0))) for leaf, w in win.items()}
    pages.write_window(table, [0], slots, [10])
    for leaf in ("k", "v"):
        dense = _dense(getattr(pages, leaf), table, 3)
        np.testing.assert_array_equal(dense[:, 0, :10], win[leaf][:, 0])
        assert (dense[:, 0, 10:] == 0).all()
    # and block by block off the device, clipped to the live width
    spans = list(pool.block_span(7))
    got = pages.read_blocks([(bid, t1 - t0) for _, bid, t0, t1 in spans])
    for (_, _, t0, t1), arrays in zip(spans, got):
        np.testing.assert_array_equal(arrays["v"], win["v"][:, 0, t0:t1])


# ---------------------------------------------------------------------------
# paged stage programs vs a numpy reference: gather by block table (pad
# entries included), the layers over the gathered cache, and the write-back
# of exactly the slots the pass wrote (shared prefixes + copy-on-write,
# chunk padding rows)
# ---------------------------------------------------------------------------

def _np_gather(blocks, table):
    """blocks [N, Lstage, bs, H, D] (numpy) -> [Lstage, B, T*bs, H, D]."""
    n, layers, bs = blocks.shape[:3]
    b, t = table.shape
    dense = blocks[table]                           # [B, T, L, bs, H, D]
    return np.moveaxis(dense, 2, 0).reshape(layers, b, t * bs,
                                            *blocks.shape[3:])


def _random_pages(pool, layers=2, h=2, d=4, seed=0):
    """Pages of random values, and the same values as numpy blocks."""
    pages = PagedKVCache(pool, layers=layers, num_kv_heads=h, head_dim=d)
    rng = np.random.default_rng(seed)
    dev = {leaf: rng.standard_normal(pages.k.shape).astype(np.float32)
           for leaf in ("k", "v")}
    pages.k, pages.v = jnp.asarray(dev["k"]), jnp.asarray(dev["v"])
    return pages, {leaf: _blocks(a, pool.block_size, h, d).copy()
                   for leaf, a in dev.items()}


def _case_random_tables():
    """Random tables of unequal length, padded with block 0, out of
    allocation order: one decode step per sequence."""
    pool = BlockPool(24, 4)
    lens = [3, 9, 14]
    for seq, n in enumerate(lens):
        pool.allocate(seq, n)
    seqs = [int(s) for s in np.random.default_rng(1).permutation(3)]
    pos = np.asarray([lens[s] - 1 for s in seqs], np.int32)
    return pool, seqs, pos, np.ones(3, np.int32), 1, []


def _case_shared_prefix_cow():
    """Two sequences share full prompt blocks; the second ends inside a
    shared block and diverges by copy-on-write before its decode step."""
    pool = BlockPool(16, 4)
    toks = list(range(8))
    pool.allocate(0, 8, token_ids=toks)
    pool.allocate(1, 8, token_ids=toks)
    assert pool.tables[0] == pool.tables[1]
    pool.truncate(1, 6)
    cow = pool.append(1)                   # slot 6: inside the shared block
    assert len(cow) == 1
    return pool, [0, 1], np.asarray([7, 6], np.int32), \
        np.ones(2, np.int32), 1, cow


def _case_chunk_padding():
    """A chunk set padded to 6 rows: one chunk of 3 rows starting inside a
    block near the end of its table, one of 5 from a block boundary; the
    padding rows must leave every block as it was."""
    pool = BlockPool(16, 4)
    pool.allocate(0, 12)                   # chunk at 9: tokens 9, 10, 11
    pool.allocate(1, 9)                    # chunk at 4: tokens 4 .. 8
    return pool, [0, 1], np.asarray([9, 4], np.int32), \
        np.asarray([3, 5], np.int32), 6, []


def _dense_stage(model, sp, x, kd, vd, pos, q_lens):
    """The stage's layers over dense numpy caches [Lstage, B, S, H, D]."""
    s = kd.shape[2]
    slots = jnp.arange(s, dtype=jnp.int32)[None, :]
    kv_positions = jnp.where(slots < (pos + q_lens)[:, None], slots, -1)
    kd, vd = list(kd), list(vd)
    for l in range(len(kd)):
        lp = jax.tree.map(lambda a: a[l], sp["layers"])
        x, (kd[l], vd[l]), _ = model._layer(
            x, lp, mode="decode_batch", kc=jnp.asarray(kd[l]),
            vc=jnp.asarray(vd[l]), kv_positions=kv_positions, pos=pos,
            q_lens=None if x.shape[1] == 1 else q_lens,
            window=sp["layer_window"][l])
    return x, np.stack(kd), np.stack(vd)


@pytest.mark.parametrize("case", [_case_random_tables,
                                  _case_shared_prefix_cow,
                                  _case_chunk_padding],
                         ids=["random_tables_padded", "shared_prefix_cow",
                              "chunk_padding_rows"])
def test_paged_programs_gather_and_write_back_match_numpy(case):
    import dataclasses

    from repro.models import build_model
    pool, seqs, pos, lens, width, cow = case()
    cfg = dataclasses.replace(get_arch("gpt2-1.5b").reduced(),
                              dtype="float32", num_layers=2)
    model = build_model(cfg)
    sp = model.slice_params(model.init(jax.random.PRNGKey(0)), 0, 2,
                            first=False, last=False)
    h, d = cfg.num_kv_heads, cfg.resolved_head_dim
    pages, ref = _random_pages(pool, 2, h, d)
    pages.apply_cow(cow)
    for leaf in ("k", "v"):
        for old, new in cow:
            ref[leaf][new] = ref[leaf][old]
    table = pool.padded_tables(seqs, 4)
    for b, seq in enumerate(seqs):                 # pad entries: block 0
        assert (table[b, len(pool.tables[seq]):] == 0).all()
    for leaf in ("k", "v"):                        # gather, every layer
        np.testing.assert_array_equal(
            _dense(getattr(pages, leaf), table, 2, h, d),
            _np_gather(ref[leaf], table))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (len(seqs), width, cfg.d_model))
    jpos, jlens = jnp.asarray(pos), jnp.asarray(lens)
    jits = model.stage_jits(first=False, last=False)
    if width == 1:
        out, k, v = jits["decode_batch_paged"](sp, x, pages.k, pages.v,
                                               jnp.asarray(table), jpos)
    else:
        out, k, v = jits["prefill_chunk_batch_paged"](
            sp, x, pages.k, pages.v, jnp.asarray(table), jpos, jlens)
    pages.write_window(table, pos, {"k": k, "v": v}, lens)
    want_x, kd, vd = _dense_stage(model, sp, x, _np_gather(ref["k"], table),
                                  _np_gather(ref["v"], table), jpos, jlens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_x),
                               rtol=1e-6, atol=1e-6)
    bs = pool.block_size
    for leaf, dense in (("k", kd), ("v", vd)):
        want = ref[leaf].copy()
        for b in range(len(seqs)):
            for i in range(int(lens[b])):          # only the valid rows
                tok = int(pos[b]) + i
                want[table[b, tok // bs], :, tok % bs] = dense[:, b, tok]
        np.testing.assert_allclose(_blocks(getattr(pages, leaf), bs, h, d),
                                   want, rtol=1e-6, atol=1e-6)
    if cow:          # seq 1 wrote its copy; the original took seq 0's only
        (old, new), = cow
        assert old in pool.tables[0] and new in pool.tables[1]
        got = _blocks(pages.k, bs, h, d)
        np.testing.assert_array_equal(got[old][:, :3], ref["k"][old][:, :3])
        np.testing.assert_array_equal(got[new][:, :2], ref["k"][old][:, :2])


def test_block_reads_and_installs_round_trip_by_bucket():
    """Blocks leave the device as one take (any count, padded to a power of
    two) and come back as one update that keeps each block's slots past its
    width; `kvpool.block_reads` counts the blocks read."""
    from repro.core import telemetry
    pool = BlockPool(16, 4)
    pages, ref = _random_pages(pool)
    spans = [(3, 4), (0, 2), (9, 4), (5, 1), (12, 3)]
    t = telemetry.Telemetry()
    prev = telemetry.install(t)
    try:
        got = pages.read_blocks(spans)
    finally:
        telemetry.uninstall(prev)
    counters = t.snapshot()["counters"]
    assert counters["kvpool.block_reads"] == len(spans)
    assert counters["kvpool.block_read_bytes"] == \
        sum(w for _, w in spans) * pages.block_bytes // 4
    for (bid, w), arrays in zip(spans, got):
        np.testing.assert_array_equal(arrays["k"], ref["k"][bid, :, :w])
        np.testing.assert_array_equal(arrays["v"], ref["v"][bid, :, :w])
    new = {"k": np.full((2, 2, 2, 4), 7.0, np.float32),
           "v": np.full((2, 2, 2, 4), 8.0, np.float32)}
    pages.install_blocks([(1, new), (14, new)])
    k, v = _blocks(pages.k, 4), _blocks(pages.v, 4)
    for bid in (1, 14):
        assert (k[bid, :, :2] == 7.0).all() and (v[bid, :, :2] == 8.0).all()
        np.testing.assert_array_equal(k[bid, :, 2:], ref["k"][bid, :, 2:])
    untouched = [b for b in range(16) if b not in (1, 14)]
    np.testing.assert_array_equal(v[untouched], ref["v"][untouched])


def test_fused_decode_uploads_tables_and_reads_only_replicated_blocks(
        monkeypatch):
    """One fused decode pass of 4 sequences over a 2-stage pipeline: the
    pages stay on each stage's device, the host uploads only the block
    tables and positions (the `worker.upload` span) besides the tokens, and
    the only pages that leave the device are the blocks replication
    pushes, one per sequence per stage."""
    import dataclasses

    from repro.core import telemetry, tracing
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = dataclasses.replace(get_arch("gpt2-1.5b").reduced(),
                              dtype="float32", max_seq_len=64)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    cl = ServingEngine(cfg, model, params, 2, paged=True, replication=True,
                       kv_pool_blocks=64).cluster
    rng = np.random.default_rng(0)
    rids = [0, 1, 2, 3]
    for rid, n in zip(rids, (5, 9, 12, 16)):
        cl.prefill_seq(rid, rng.integers(0, cfg.vocab_size, (n,)
                                         ).astype(np.int32), max_new=4)
    for w in cl.token_group:
        for leaf in (w.pages.k, w.pages.v):
            assert isinstance(leaf, jax.Array)
            assert leaf.devices() == {w.device}
    uploaded = []
    put = jax.device_put

    def counting_put(x, *a, **k):
        uploaded.extend(l.nbytes for l in jax.tree.leaves(x)
                        if isinstance(l, np.ndarray))
        return put(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", counting_put)
    tokens = np.arange(4, dtype=np.int32)
    tracer, tele = tracing.Tracer(), telemetry.Telemetry()
    prev_tr, prev_te = tracing.install(tracer), telemetry.install(tele)
    try:
        cl.decode_batch(rids, tokens, [1, 1, 1, 1])
    finally:
        tracing.uninstall(prev_tr)
        telemetry.uninstall(prev_te)
    width = cl.token_group[0].table_width
    ups = [e["args"]["bytes"] for e in tracer.snapshot()["tracks"]["serve"]
           ["events"] if e["name"] == "worker.upload"]
    assert ups == [4 * 4 * (width + 1)] * 2       # int32 [4, T] + [4]
    # besides: the tokens, and each stage's int32 ids of the 4 blocks that
    # replication reads
    assert sum(uploaded) == sum(ups) + tokens.nbytes + 2 * 4 * 4
    counters = tele.snapshot()["counters"]
    assert counters["kvpool.block_reads"] == 4 * 2
    row = 2 * (cfg.num_layers // 2) * cfg.num_kv_heads \
        * cfg.resolved_head_dim * 4                 # K+V of one token
    lens = [5 + 1, 9 + 1, 12 + 1, 16 + 1]
    touched = sum((n - 1) % cfg.kv_block_size + 1 for n in lens)
    assert counters["kvpool.block_read_bytes"] == 2 * touched * row


def _traced(fn):
    """Run `fn` under a fresh tracer; returns its `worker.upload` bytes."""
    from repro.core import tracing

    tracer = tracing.Tracer()
    prev = tracing.install(tracer)
    try:
        fn()
    finally:
        tracing.uninstall(prev)
    return [e["args"]["bytes"] for e in tracer.snapshot()["tracks"]["serve"]
            ["events"] if e["name"] == "worker.upload"]


@pytest.mark.parametrize("name", ["yi-34b", "qwen3-moe-30b-a3b"])
def test_table_width_is_bounded_by_the_pool(name):
    """A registry model that keeps the default context bound (524,288
    tokens) gets tables as wide as its pool, not 65,536 blocks: a
    sequence never holds more blocks than the pool has."""
    import dataclasses

    from repro.configs.base import ArchConfig
    from repro.models import build_model
    from repro.serving import ServingEngine

    full = get_arch(name)
    assert full.max_seq_len == ArchConfig.max_seq_len
    cfg = dataclasses.replace(full.reduced(), dtype="float32",
                              max_seq_len=full.max_seq_len)
    model = build_model(cfg)
    cl = ServingEngine(cfg, model, model.init(jax.random.PRNGKey(0)), 2,
                       paged=True, kv_pool_blocks=24).cluster
    assert [w.table_width for w in cl.token_group] == [24, 24]
    rng = np.random.default_rng(0)
    for rid, n in ((0, 5), (1, 11)):
        cl.prefill_seq(rid, rng.integers(0, cfg.vocab_size, (n,)
                                         ).astype(np.int32), max_new=2)
    ups = _traced(lambda: cl.decode_batch([0, 1], np.arange(2, dtype=np.int32),
                                          [1, 1]))
    assert ups == [4 * 2 * (24 + 1)] * 2          # int32 [2, 24] + [2]


def test_context_past_the_table_width_is_refused_before_any_pool_change():
    """A context that would outgrow the fixed table width raises before a
    pool, its pages or the cluster's lengths change, on every stage."""
    import copy
    import dataclasses

    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = dataclasses.replace(get_arch("gpt2-1.5b").reduced(),
                              dtype="float32", max_seq_len=16)
    model = build_model(cfg)
    cl = ServingEngine(cfg, model, model.init(jax.random.PRNGKey(0)), 2,
                       paged=True, kv_pool_blocks=32).cluster
    assert [w.table_width for w in cl.token_group] == [2, 2]

    def state():
        return ([(copy.deepcopy(w.pool.tables), dict(w.pool.seq_lens),
                  w.pool.num_free(), np.asarray(w.pages.k),
                  np.asarray(w.pages.v)) for w in cl.token_group],
                dict(cl.seq_len))

    def same(a, b):
        for wa, wb in zip(a[0], b[0]):
            assert wa[:3] == wb[:3]
            np.testing.assert_array_equal(wa[3], wb[3])
            np.testing.assert_array_equal(wa[4], wb[4])
        assert a[1] == b[1]

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (16,)).astype(np.int32)
    cl.prefill_seq(0, prompt, max_new=2)           # fills both blocks
    before = state()
    with pytest.raises(ValueError, match="table width"):
        cl.decode_batch([0], np.zeros(1, np.int32), [1])
    same(before, state())
    with pytest.raises(ValueError, match="table width"):
        cl.decode_seq(0, np.zeros(1, np.int32), 1)
    same(before, state())
    with pytest.raises(ValueError, match="table width"):
        cl.prefill_seq(1, np.concatenate([prompt, prompt[:1]]), max_new=2)
    same(before, state())
    assert 1 not in cl.seq_prompt_len


def test_run_continuous_refuses_a_request_past_the_table_width_up_front():
    """Admission: a request whose prompt plus `max_new` would outgrow the
    table width is refused before any request, fitting ones included, is
    served; at the width exactly, the run serves every token."""
    import dataclasses

    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    cfg = dataclasses.replace(get_arch("gpt2-1.5b").reduced(),
                              dtype="float32", max_seq_len=16)
    model = build_model(cfg)
    eng = ServingEngine(cfg, model, model.init(jax.random.PRNGKey(0)), 2,
                        paged=True, kv_pool_blocks=32)
    rng = np.random.default_rng(0)

    def req(rid, plen, max_new):
        return Request(rid, rng.integers(0, cfg.vocab_size, (plen,)
                                         ).astype(np.int32), max_new)

    over = [req(0, 8, 4), req(1, 12, 6)]           # 12 + 6 - 1 = 17 > 16
    with pytest.raises(ValueError, match="table width"):
        eng.run_continuous(over)
    assert all(not r.tokens for r in over)
    assert all(w.pool.num_free() == 32 and not w.pool.tables
               for w in eng.cluster.token_group)
    fits = [req(2, 8, 4), req(3, 12, 5)]           # 12 + 5 - 1 = 16
    eng.run_continuous(fits)
    assert [len(r.tokens) for r in fits] == [4, 5]


# ---------------------------------------------------------------------------
# paged-attention decode kernel vs references
# ---------------------------------------------------------------------------

def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,hq,hkv,d,bs,lens", [
    (3, 8, 2, 16, 8, (5, 17, 24)),          # odd lengths, GQA
    (2, 4, 4, 32, 16, (1, 31)),             # MHA, length-1 edge
    (1, 6, 2, 64, 4, (13,)),                # tiny blocks
    (4, 8, 1, 16, 8, (8, 16, 9, 3)),        # MQA, block-aligned + odd
])
def test_paged_decode_matches_dense_reference(b, hq, hkv, d, bs, lens, dtype):
    n_pages = 48
    lens = np.asarray(lens, np.int32)
    mx = int(max(-(-lens // bs)))
    rng = np.random.default_rng(0)
    perm = list(rng.permutation(n_pages))
    tables = np.zeros((b, mx), np.int32)
    for i, L in enumerate(lens):
        for j in range(-(-int(L) // bs)):
            tables[i, j] = perm.pop()
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    kp = jax.random.normal(ks[1], (n_pages, bs, hkv, d), dtype)
    vp = jax.random.normal(ks[2], (n_pages, bs, hkv, d), dtype)
    out = paged_decode_attention(q, kp, vp, tables, lens)
    # vs paged oracle
    expect = ref.paged_decode_attention_ref(q, kp, vp, jnp.asarray(tables),
                                            jnp.asarray(lens))
    err = np.max(np.abs(np.asarray(out, np.float32)
                        - np.asarray(expect, np.float32)))
    assert err < _tol(dtype), err
    # vs the DENSE reference per sequence (gather pages -> contiguous cache)
    for i in range(b):
        kd = ref.paged_gather_ref(kp, jnp.asarray(tables[i:i + 1]))
        vd = ref.paged_gather_ref(vp, jnp.asarray(tables[i:i + 1]))
        valid = jnp.arange(kd.shape[1]) < int(lens[i])
        dense = ref.decode_attention_ref(q[i:i + 1], kd, vd, valid)
        err = np.max(np.abs(np.asarray(out[i:i + 1], np.float32)
                            - np.asarray(dense, np.float32)))
        assert err < _tol(dtype), (i, err)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(b=st.integers(1, 4), bs=st.sampled_from([4, 8]),
           seed=st.integers(0, 100))
    def test_property_paged_decode_matches_reference(b, bs, seed):
        hq, hkv, d, n_pages = 4, 2, 16, 32
        rng = np.random.default_rng(seed)
        lens = rng.integers(1, 3 * bs, size=b).astype(np.int32)
        mx = int(max(-(-lens // bs)))
        perm = list(rng.permutation(n_pages))
        tables = np.zeros((b, mx), np.int32)
        for i, L in enumerate(lens):
            for j in range(-(-int(L) // bs)):
                tables[i, j] = perm.pop()
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(k1, (b, hq, d), jnp.float32)
        kp = jax.random.normal(k2, (n_pages, bs, hkv, d), jnp.float32)
        vp = jax.random.normal(k3, (n_pages, bs, hkv, d), jnp.float32)
        out = paged_decode_attention(q, kp, vp, tables, lens)
        expect = ref.paged_decode_attention_ref(q, kp, vp, jnp.asarray(tables),
                                                jnp.asarray(lens))
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# planner: paged accounting
# ---------------------------------------------------------------------------

def test_blocks_for():
    assert blocks_for(0, 8) == 0 and blocks_for(1, 8) == 1
    assert blocks_for(8, 8) == 1 and blocks_for(9, 8) == 2


def test_paged_state_bytes_rounds_to_blocks():
    cfg = get_arch("opt-66b")
    assert cfg.paged_state_bytes(9) == cfg.decode_state_bytes(16)
    assert cfg.paged_state_bytes(9) < cfg.decode_state_bytes(1220)


def test_planner_paged_needs_no_more_memory_than_static():
    cfg = get_arch("opt-66b")
    mach = MachineSpec()
    wl = cm.WorkloadSpec(prompt_len=1000, new_tokens=220, microbatch=16)
    dt_static = min_token_depth(cfg, wl, mach)
    dt_paged = min_token_depth(cfg, wl, mach, paged=True)
    assert dt_static > 0 and 0 < dt_paged <= dt_static
    # a generation-heavy workload that is static-infeasible (the full
    # prompt+new reservation per request overflows every split) becomes
    # feasible when the planner accounts live blocks only
    wl_gen = cm.WorkloadSpec(prompt_len=200, new_tokens=1500, microbatch=32)
    assert min_token_depth(cfg, wl_gen, mach) == -1        # static: never fits
    assert min_token_depth(cfg, wl_gen, mach, paged=True) > 0
    assert not plan(cfg, wl_gen, 6, mach).feasible
    p_paged = plan(cfg, wl_gen, 6, mach, paged=True)
    assert p_paged.feasible and p_paged.d_prompt + p_paged.d_token == 6
