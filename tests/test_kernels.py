"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import (batched_decode_attention,
                                            decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kv_pack import kv_pack, kv_pack_ragged, kv_unpack
from repro.kernels.paged_prefill import paged_prefill_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models.ssm import ssd_chunked

pytestmark = pytest.mark.slow  # full sweep; excluded from `pytest -m "not slow"`

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,sq,skv,hq,hkv,d,causal,bq,bk", [
    (2, 64, 64, 4, 2, 16, True, 32, 32),
    (1, 100, 100, 6, 2, 32, True, 32, 32),       # non-multiple seq
    (2, 32, 96, 4, 4, 16, True, 16, 32),         # cross-length causal
    (1, 64, 64, 2, 1, 64, False, 64, 64),        # bidirectional
    (1, 128, 128, 8, 8, 16, True, 128, 128),     # MHA single block
])
def test_flash_attention(b, sq, skv, hq, hkv, d, causal, bq, bk, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, skv, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, skv, hkv, d), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    expected = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,hq,hkv,d,bk,n_valid", [
    (2, 128, 4, 2, 16, 32, 100),
    (1, 100, 8, 2, 32, 64, 100),                 # padding path
    (3, 64, 4, 4, 16, 64, 1),                    # single valid slot
    (1, 256, 2, 1, 64, 256, 200),
])
def test_decode_attention(b, s, hq, hkv, d, bk, n_valid, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    valid = jnp.arange(s) < n_valid
    out = decode_attention(q, k, v, valid, block_k=bk)
    expected = ref.decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,hq,hkv,d,bk,lengths", [
    (3, 128, 4, 2, 16, 32, (100, 128, 1)),       # ragged incl. extremes
    (2, 100, 8, 2, 32, 64, (37, 99)),            # padding path
    (4, 64, 4, 4, 16, 64, (64, 64, 64, 64)),     # uniform full
    (1, 256, 2, 1, 64, 256, (200,)),
])
def test_batched_decode_attention(b, s, hq, hkv, d, bk, lengths, dtype):
    """Fused-round kernel vs dense oracle: one launch, B sequences each
    masked to its OWN live length (vs `decode_attention`'s shared mask)."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    lens = jnp.asarray(lengths, jnp.int32)
    out = batched_decode_attention(q, k, v, lens, block_k=bk)
    expected = ref.batched_decode_attention_ref(q, k, v, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), **_tol(dtype))


def test_batched_decode_matches_per_sequence():
    """Semantic check behind fused rounds: the batched launch reproduces B
    independent single-sequence `decode_attention` calls bit-for-bit."""
    b, s, hq, hkv, d = 3, 64, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    lens = jnp.asarray([17, 64, 5], jnp.int32)
    out = batched_decode_attention(q, k, v, lens, block_k=32)
    for i in range(b):
        one = decode_attention(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                               jnp.arange(s) < int(lens[i]), block_k=32)
        np.testing.assert_allclose(np.asarray(out[i:i + 1]), np.asarray(one),
                                   rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("use_win,use_bias,num_meta", [
    (True, False, 0),            # sliding window only
    (True, False, 2),            # window + meta-token attention sinks
    (False, True, 0),            # ALiBi slopes only
    (True, True, 2),             # window + meta + ALiBi combined
])
def test_batched_decode_attention_window_bias(use_win, use_bias, num_meta,
                                              dtype):
    """ALiBi / sliding-window variants of the fused-round kernel vs oracle:
    per-sequence window starts and per-head slopes ride scalar prefetch, so
    one launch still serves B ragged sequences with heterogeneous masks."""
    b, s, hq, hkv, d, bk = 3, 96, 4, 2, 16, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    lens = jnp.asarray([90, 96, 7], jnp.int32)
    # window starts as the engine computes them: max(len - w, 0), w = 24;
    # the short sequence starts at 0 (whole context inside the window)
    wins = jnp.maximum(lens - 24, 0) if use_win else None
    slopes = (jnp.asarray([2.0 ** -(i + 1) for i in range(hq)], jnp.float32)
              if use_bias else None)
    out = batched_decode_attention(q, k, v, lens, wins, slopes,
                                   block_k=bk, num_meta=num_meta)
    expected = ref.batched_decode_attention_ref(q, k, v, lens, wins, slopes,
                                                num_meta=num_meta)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), **_tol(dtype))


def test_batched_decode_window_bias_matches_per_sequence():
    """The windowed/ALiBi batched launch reproduces B independent dense
    attends with the per-sequence mask/bias semantics the engine's oracle
    path uses (meta sinks visible below `num_meta`, window elsewhere)."""
    b, s, hq, hkv, d, g = 3, 64, 4, 2, 16, 2
    num_meta, w = 2, 12
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    lens = jnp.asarray([40, 64, 9], jnp.int32)
    wins = jnp.maximum(lens - w, 0)
    slopes = jnp.asarray([0.5, 0.25, 0.125, 0.0625], jnp.float32)
    out = batched_decode_attention(q, k, v, lens, wins, slopes,
                                   block_k=32, num_meta=num_meta)
    scale = 1.0 / np.sqrt(d)
    for i in range(b):
        n = int(lens[i])
        pos = np.arange(s)
        visible = (pos < n) & ((pos >= int(wins[i])) | (pos < num_meta))
        qi = np.asarray(q[i], np.float32).reshape(hkv, g, d)
        ki = np.asarray(k[i], np.float32)
        sc = np.einsum("hgd,shd->hgs", qi, ki) * scale
        sc = sc - slopes.reshape(hkv, g)[:, :, None] * np.maximum(
            (n - 1) - pos, 0)[None, None, :]
        sc = np.where(visible[None, None, :], sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o = np.einsum("hgs,shd->hgd", p, np.asarray(v[i], np.float32))
        np.testing.assert_allclose(np.asarray(out[i], np.float32),
                                   o.reshape(hq, d), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,B,S,H,D,starts,w,tb", [
    (3, 3, 64, 4, 16, (0, 16, 56), 8, 8),
    (2, 2, 32, 2, 8, (24, 0), 8, 8),             # tail + head windows
    (1, 4, 48, 2, 16, (8, 8, 40, 16), 8, 4),     # repeated offsets, tb 4
    (2, 1, 16, 1, 8, (8,), 8, 8),                # single row
])
def test_kv_pack_ragged(L, B, S, H, D, starts, w, tb, dtype):
    """Multi-sequence buffered copy vs oracle: one launch packs one window
    per batch row, each at its OWN offset (the fused-round writeback)."""
    cache = jax.random.normal(KEY, (L, B, S, H, D), dtype)
    st = jnp.asarray(starts, jnp.int32)
    packed = kv_pack_ragged(cache, st, width=w, token_block=tb)
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(ref.kv_pack_ragged_ref(cache, st, w)))
    # row b of the ragged pack == the scalar kv_pack of that row's window
    for bi in range(B):
        one = kv_pack(cache[:, bi:bi + 1], int(st[bi]), width=w, token_block=tb)
        np.testing.assert_array_equal(np.asarray(packed[:, bi:bi + 1]),
                                      np.asarray(one))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,c,hq,hkv,d,bs,prefixes", [
    (2, 8, 4, 2, 16, 8, (16, 9)),          # aligned + mid-block prefix
    (1, 5, 6, 2, 32, 8, (0,)),             # no prefix (pure self-attention)
    (3, 3, 4, 4, 16, 4, (4, 7, 1)),        # chunk < block, ragged prefixes
    (1, 16, 2, 1, 64, 8, (24,)),           # chunk spans multiple blocks
])
def test_paged_prefill_attention(b, c, hq, hkv, d, bs, prefixes, dtype):
    """Kernel vs dense oracle: a Q chunk attends over its paged prefix plus
    itself, for prefixes/chunks that do and don't align to block boundaries."""
    rng = np.random.default_rng(0)
    max_blocks = max((p + c + bs - 1) // bs for p in prefixes)
    n_pages = b * max_blocks + 1
    ks = jax.random.split(KEY, 3)
    k_pages = jax.random.normal(ks[0], (n_pages, bs, hkv, d), dtype)
    v_pages = jax.random.normal(ks[1], (n_pages, bs, hkv, d), dtype)
    q = jax.random.normal(ks[2], (b, c, hq, d), dtype)
    perm = rng.permutation(n_pages - 1) + 1      # page 0 reserved as padding
    bt = jnp.asarray(perm[:b * max_blocks].reshape(b, max_blocks), jnp.int32)
    q_starts = jnp.asarray(list(prefixes), jnp.int32)
    q_lens = jnp.full((b,), c, jnp.int32)
    out = paged_prefill_attention(q, k_pages, v_pages, bt, q_starts, q_lens)
    expected = ref.paged_prefill_attention_ref(q, k_pages, v_pages, bt,
                                               q_starts, q_lens)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expected, np.float32), **_tol(dtype))


def test_paged_prefill_chunks_match_dense_causal():
    """Semantic check: running a sequence through consecutive chunks over
    pages reproduces the rows of one dense causal flash prefill — the
    exactness claim behind chunked prefix adoption."""
    b, s, hq, hkv, d, bs, chunk = 1, 48, 4, 2, 16, 8, 10   # 10 ∤ 48
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.float32)
    dense = ref.flash_attention_ref(q, k, v, causal=True)
    n_blocks = s // bs
    k_pages = k.reshape(n_blocks, bs, hkv, d)
    v_pages = v.reshape(n_blocks, bs, hkv, d)
    bt = jnp.arange(n_blocks, dtype=jnp.int32)[None]
    for pos in range(0, s, chunk):
        c = min(chunk, s - pos)
        out = paged_prefill_attention(q[:, pos:pos + c], k_pages, v_pages, bt,
                                      jnp.asarray([pos], jnp.int32),
                                      jnp.asarray([c], jnp.int32))
        np.testing.assert_allclose(np.asarray(out), np.asarray(dense[:, pos:pos + c]),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,B,S,H,D,t0,w,tb", [
    (3, 2, 64, 4, 16, 16, 24, 8),
    (2, 1, 32, 2, 8, 0, 32, 8),                  # whole cache
    (4, 2, 48, 2, 16, 40, 8, 8),                 # tail window
    (1, 1, 16, 1, 8, 8, 8, 4),
])
def test_kv_pack_unpack_roundtrip(L, B, S, H, D, t0, w, tb, dtype):
    ks = jax.random.split(KEY, 2)
    cache = jax.random.normal(ks[0], (L, B, S, H, D), dtype)
    packed = kv_pack(cache, t0, width=w, token_block=tb)
    np.testing.assert_array_equal(np.asarray(packed),
                                  np.asarray(ref.kv_pack_ref(cache, t0, w)))
    buf = jax.random.normal(ks[1], (L, B, w, H, D), dtype)
    restored = kv_unpack(cache.copy(), buf, t0, token_block=tb)
    np.testing.assert_array_equal(np.asarray(restored),
                                  np.asarray(ref.kv_unpack_ref(cache, buf, t0)))


@pytest.mark.parametrize("B,S,NH,HD,G,N,CH", [
    (2, 96, 4, 16, 1, 8, 32),
    (1, 64, 8, 8, 2, 16, 16),
    (2, 50, 4, 16, 1, 8, 32),                    # non-multiple of chunk
    (1, 33, 2, 8, 1, 4, 16),
])
def test_ssd_kernel_and_chunked_vs_sequential(B, S, NH, HD, G, N, CH):
    ks = jax.random.split(KEY, 4)
    x = jax.random.normal(ks[0], (B, S, NH, HD), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, NH)))
    a_neg = -jnp.exp(jax.random.normal(ks[2], (NH,)) * 0.3)
    bm = 0.5 * jax.random.normal(ks[3], (B, S, G, N), jnp.float32)
    cm = 0.5 * jax.random.normal(ks[0], (B, S, G, N), jnp.float32)
    h0 = 0.1 * jax.random.normal(ks[1], (B, NH, HD, N), jnp.float32)
    y_ref, h_ref = ref.ssd_sequential_ref(x, dt, a_neg, bm, cm, h0=h0)
    y_k, h_k = ssd_scan(x, dt, a_neg, bm, cm, h0=h0, chunk=CH)
    y_j, h_j = ssd_chunked(x, dt, a_neg, bm, cm, chunk=CH, h0=h0)
    np.testing.assert_allclose(y_k, y_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(h_k, h_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(y_j, y_ref, rtol=2e-4, atol=2e-4)


def test_ssd_decode_step_matches_sequential():
    from repro.kernels.ref import ssd_sequential_ref
    from repro.models.ssm import ssd_decode_step
    ks = jax.random.split(KEY, 4)
    B, NH, HD, G, N = 2, 4, 8, 1, 8
    x = jax.random.normal(ks[0], (B, 5, NH, HD))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, 5, NH)))
    a_neg = -jnp.exp(0.3 * jax.random.normal(ks[2], (NH,)))
    bm = 0.5 * jax.random.normal(ks[3], (B, 5, G, N))
    cm = 0.5 * jax.random.normal(ks[0], (B, 5, G, N))
    y_ref, h_ref = ssd_sequential_ref(x, dt, a_neg, bm, cm)
    h = jnp.zeros((B, NH, HD, N))
    for t in range(5):
        y, h = ssd_decode_step(x[:, t], dt[:, t], a_neg, bm[:, t], cm[:, t], h)
    np.testing.assert_allclose(y, y_ref[:, -1], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(h, h_ref, rtol=1e-5, atol=1e-5)


def test_interpret_mode_follows_the_backend(monkeypatch):
    """Kernels run interpreted on the CPU, compiled on a TPU, and refuse any
    other backend instead of interpreting there; the choice is made per
    call, so importing the kernels touches no backend."""
    from repro.kernels import ops
    assert ops.interpret_mode() is (jax.default_backend() == "cpu")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        ops.interpret_mode()
