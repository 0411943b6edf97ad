"""Compile the Pallas kernels for a TPU v5e that is described, not attached.

The TPU compiler installed with JAX compiles for a described ``v5e:2x2``
topology without a chip, and refuses what the chip's compiler would refuse:
block shapes off the (8, 128) tiling, too much fast memory, a program too
large for the device.  Interpret mode (the rest of the suite) checks none of
that.  Each case is one kernel at published widths in bf16, compiled
(``interpret=False``) for one chip of the topology.

The served path calls only the ``kv_pack`` family; those must compile.  The
attention kernels tile K/V as ``(1, bk, 1, d)`` blocks over a
``[..., S, Hkv, D]`` array, which Mosaic refuses; they stay off the served
path until a head-major K/V layout lands, and their cases are strict xfails
that turn into failures to flip when it does.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (batched_decode_attention,
                                            decode_attention,
                                            paged_decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.kv_pack import kv_pack, kv_pack_ragged, kv_unpack
from repro.kernels.paged_prefill import paged_prefill_attention

BF16 = jnp.bfloat16
I32 = jnp.int32

# (name, layers per stage, kv heads, head_dim, cache length)
WIDTHS = [
    ("gpt2-1.5b", 24, 25, 64, 416),     # 48 layers over 2 stages
    ("opt-66b", 16, 72, 128, 256),      # 64 layers over 4 stages
]
BATCH = 4
WINDOW = 8                              # one DMA token block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip writes a cache entry that no later
    run here can read back; keep the persistent cache off around them."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def _kv_pack_case(s, layers, h, d, length):
    cache = s((layers, BATCH, length, h, d), BF16)
    return kv_pack.lower(cache, s((), I32), width=WINDOW, token_block=WINDOW,
                         interpret=False)


def _kv_pack_ragged_case(s, layers, h, d, length):
    cache = s((layers, BATCH, length, h, d), BF16)
    return kv_pack_ragged.lower(cache, s((BATCH,), I32), width=WINDOW,
                                token_block=WINDOW, interpret=False)


def _kv_unpack_case(s, layers, h, d, length):
    cache = s((layers, BATCH, length, h, d), BF16)
    buf = s((layers, BATCH, WINDOW, h, d), BF16)
    return kv_unpack.lower(cache, buf, s((), I32), token_block=WINDOW,
                           interpret=False)


@pytest.mark.parametrize("width", WIDTHS, ids=[w[0] for w in WIDTHS])
@pytest.mark.parametrize("case", [_kv_pack_case, _kv_pack_ragged_case,
                                  _kv_unpack_case],
                         ids=["kv_pack", "kv_pack_ragged", "kv_unpack"])
def test_served_kernel_compiles(case, width, one_chip, no_compile_cache):
    _, layers, h, d, length = width
    compiled = case(_spec(one_chip), layers, h, d, length).compile()
    assert "tpu_custom_call" in compiled.as_text()


# gpt2-1.5b widths: 25 heads of 64, a 416-token cache
H, D, S = 25, 64, 416
BLOCK, PAGES = 8, 256
ATTENTION_CASES = {
    "decode_attention": lambda s: decode_attention.lower(
        s((BATCH, H, D), BF16), s((BATCH, S, H, D), BF16),
        s((BATCH, S, H, D), BF16), s((S,), jnp.bool_), interpret=False),
    "batched_decode_attention": lambda s: batched_decode_attention.lower(
        s((BATCH, H, D), BF16), s((BATCH, S, H, D), BF16),
        s((BATCH, S, H, D), BF16), s((BATCH,), I32), interpret=False),
    "paged_decode_attention": lambda s: paged_decode_attention.lower(
        s((BATCH, H, D), BF16), s((PAGES, BLOCK, H, D), BF16),
        s((PAGES, BLOCK, H, D), BF16), s((BATCH, S // BLOCK), I32),
        s((BATCH,), I32), interpret=False),
    "paged_prefill_attention": lambda s: paged_prefill_attention.lower(
        s((BATCH, 64, H, D), BF16), s((PAGES, BLOCK, H, D), BF16),
        s((PAGES, BLOCK, H, D), BF16), s((BATCH, S // BLOCK), I32),
        s((BATCH,), I32), s((BATCH,), I32), interpret=False),
    "flash_attention": lambda s: flash_attention.lower(
        s((1, S, H, D), BF16), s((1, S, H, D), BF16), s((1, S, H, D), BF16),
        interpret=False),
}
LAYOUT_REFUSED = (
    "Mosaic refuses the (1, bk, 1, d) K/V block over [..., S, Hkv, D]: "
    "'the last two dimensions of your block shape are divisible by 8 and "
    "128 respectively, or be equal to the respective dimensions of the "
    "overall array'; needs a head-major K/V layout")


@pytest.mark.parametrize("name", [
    pytest.param(n, marks=pytest.mark.xfail(strict=True, raises=ValueError,
                                            reason=LAYOUT_REFUSED))
    for n in ATTENTION_CASES])
def test_attention_kernel_compiles(name, one_chip, no_compile_cache):
    ATTENTION_CASES[name](_spec(one_chip)).compile()
