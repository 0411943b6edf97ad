"""jit'd dispatch wrappers around the Pallas kernels.

The serving path calls the ``kv_pack`` family (KV writeback, swap and
replication windows); models call the attention kernels via
``backend="pallas"``.  Each call decides how its kernel runs from the
default backend at call time (`interpret_mode`): compiled on a TPU,
interpreted on the CPU (the test suite), and an error anywhere else — a
kernel never falls back to interpret mode silently on an accelerator.
Wrappers adapt the models' masked-attention interface to the kernels'
position-based one and fall back to the jnp reference for shapes the
kernels don't cover (e.g. additive-bias attention).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import (batched_decode_attention,
                                            decode_attention,
                                            paged_decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kv_pack import kv_pack, kv_pack_ragged, kv_unpack
from repro.kernels.paged_prefill import paged_prefill_attention
from repro.kernels.ssd_scan import ssd_scan


def interpret_mode() -> bool:
    """Whether a Pallas kernel launched now runs interpreted: True on the
    CPU backend, False on a TPU; any other backend raises, since these
    kernels are written for the TPU and only the CPU may interpret them."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on 'tpu' or interpreted on 'cpu'; "
        f"the default backend is {backend!r}")


def attention_auto(q, k, v, mask=None, bias=None):
    """Prefill attention entry point.  Uses the flash kernel for the plain
    causal case; falls back to the reference for exotic masks/bias."""
    b, sq, hq, d = q.shape
    plain_causal = bias is None and (mask is None or _is_plain_causal(mask, sq, k.shape[1]))
    if plain_causal:
        return flash_attention(q, k, v, causal=mask is not None,
                               interpret=interpret_mode())
    from repro.models.attention import attend
    return attend(q, k, v, mask=mask, bias=bias, backend="xla")


def _is_plain_causal(mask, sq, skv) -> bool:
    # static structural check only (trace-safe): 2-D mask of full extent
    return mask.ndim == 2 and mask.shape == (sq, skv) and sq == skv


def decode_attention_auto(q, k_cache, v_cache, mask):
    """Decode attention entry point.  q: [B,1,Hq,D]; mask: [1,Skv] bool."""
    valid = mask[0] if mask.ndim == 2 else mask
    out = decode_attention(q[:, 0], k_cache, v_cache, valid,
                           interpret=interpret_mode())
    return out[:, None]


def batched_decode_attention_auto(q, k_cache, v_cache, lengths, *,
                                  window=0, num_meta: int = 0, alibi=None):
    """Fused-round decode attention entry point: one launch, B sequences,
    ragged per-sequence lengths.  q: [B,Hq,D]; k/v: [B,S,Hkv,D].

    `window` (static or traced per-layer int32; 0 = full attention) becomes
    per-sequence window starts max(lengths - window, 0); `alibi` [Hq] slopes
    ride scalar prefetch into the kernel's additive bias."""
    win_starts = None
    if not (isinstance(window, int) and window == 0):
        w = jnp.asarray(window, jnp.int32)
        win_starts = jnp.where(w > 0, jnp.maximum(lengths - w, 0), 0)
    slopes = None if alibi is None else jnp.asarray(alibi, jnp.float32)
    return batched_decode_attention(q, k_cache, v_cache, lengths,
                                    win_starts, slopes,
                                    num_meta=int(num_meta),
                                    interpret=interpret_mode())


def paged_decode_attention_auto(q, k_pages, v_pages, block_tables, lengths):
    """Paged decode attention entry point.  q: [B,1,Hq,D] or [B,Hq,D]."""
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    out = paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                                 interpret=interpret_mode())
    return out[:, None] if squeeze else out


def paged_prefill_attention_auto(q, k_pages, v_pages, block_tables, q_starts,
                                 q_lens):
    """Chunked paged-prefill entry point.  q: [B,C,Hq,D]; the chunk's own
    K/V window must already be scattered into the pages (via kv_pack)."""
    return paged_prefill_attention(q, k_pages, v_pages, block_tables,
                                   q_starts, q_lens, interpret=interpret_mode())


def ssd_auto(x, dt, a_neg, bmat, cmat, chunk=128, h0=None):
    return ssd_scan(x, dt, a_neg, bmat, cmat, h0=h0, chunk=min(chunk, x.shape[1]),
                    interpret=interpret_mode())


def kv_pack_auto(cache, t0, width, token_block: int = 8):
    return kv_pack(cache, t0, width=width, token_block=token_block,
                   interpret=interpret_mode())


def kv_pack_ragged_auto(cache, starts, width, token_block: int = 8):
    """Multi-sequence buffered copy: one window per batch row at per-row
    offsets (the fused-round KV writeback)."""
    return kv_pack_ragged(cache, starts, width=width, token_block=token_block,
                          interpret=interpret_mode())


def kv_unpack_auto(cache, buf, t0, token_block: int = 8):
    return kv_unpack(cache, buf, t0, token_block=token_block,
                     interpret=interpret_mode())
