"""Compile the Pallas kernels for a TPU v5e that is described, not attached.

The TPU compiler installed with JAX compiles for a described ``v5e:2x2``
topology without a chip, and refuses what the chip's compiler would refuse:
block shapes off the (8, 128) tiling, too much fast memory, a program too
large for the device.  Interpret mode (the rest of the suite) checks none of
that.  Each case is one kernel at published widths in bf16, compiled
(``interpret=False``) for one chip of the topology.

The served paged passes run no Pallas kernel: their stage programs gather
K/V from the pool pages by block table in XLA, and those programs are
compiled here too, one layer per stage at the served widths.  The
``kv_pack`` family serves the microbatch path and must compile.  The
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


# The paged stage programs at the served configurations' widths, one layer
# per stage (first stage: tokens in; last stage: the LM head out), over
# the cells' pools [1, blocks, 8, Hkv * D] with block tables of the fixed
# width blocks_for(max_seq_len, 8).
PAGED = [                               # (name, registry config, changes, pool)
    ("gpt2-1.5b", "gpt2-1.5b", {"max_seq_len": 1024}, 1048),
    ("opt-66b", "opt-66b", {"tie_embeddings": True}, 1036),
]
CHUNK = 64                              # prefill_chunk_tokens


def _paged_stage(name, changes, first, last):
    import dataclasses

    from repro.configs import get_arch
    from repro.models import build_model
    cfg = dataclasses.replace(get_arch(name), num_layers=2, dtype="bfloat16",
                              **changes)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    sp = jax.eval_shape(lambda p: model.slice_params(
        p, 0, 1, first=first, last=last), params)
    return cfg, model, sp


def _paged_case(program, s, name, changes, blocks, first, last):
    cfg, model, sp = _paged_stage(name, changes, first, last)
    fn = model.stage_jits(first=first, last=last)[program]
    hkv, d, bs = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.kv_block_size
    width = -(-cfg.max_seq_len // bs)
    pages = s((1, blocks, bs, hkv * d), BF16)
    c = 1 if program == "decode_batch_paged" else CHUNK
    if first:
        x = s((BATCH,) if c == 1 else (BATCH, c), I32)
    else:
        x = s((BATCH, c, cfg.d_model), BF16)
    vectors = [s((BATCH,), I32)] * (1 if c == 1 else 2)
    return fn.lower(jax.tree.map(lambda a: s(a.shape, a.dtype), sp), x,
                    pages, pages, s((BATCH, width), I32), *vectors)


def _write_window_case(s, name, changes, blocks, first, last):
    from repro.kvcache.paged import _write_window, window_blocks
    cfg, _, _ = _paged_stage(name, changes, first, last)
    hkv, d, bs = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.kv_block_size
    pages = s((1, blocks, bs, hkv * d), BF16)
    rows = s((1, BATCH, window_blocks(CHUNK, bs) * bs, hkv, d), BF16)
    vec = s((BATCH,), I32)
    return _write_window.lower(pages, pages,
                               s((BATCH, -(-cfg.max_seq_len // bs)), I32),
                               vec, vec, rows, rows)


@pytest.mark.parametrize("width", PAGED, ids=[w[0] for w in PAGED])
@pytest.mark.parametrize("program", ["decode_batch_paged",
                                     "prefill_chunk_batch_paged",
                                     "write_window"])
def test_paged_stage_program_compiles(program, width, one_chip,
                                      no_compile_cache):
    """Each paged pass program compiles for one v5e chip, as the first
    stage and as the last, within the chip's memory."""
    _, name, changes, blocks = width
    for first, last in ((True, False), (False, True)):
        s = _spec(one_chip)
        lowered = (_write_window_case(s, name, changes, blocks, first, last)
                   if program == "write_window" else
                   _paged_case(program, s, name, changes, blocks, first,
                               last))
        mem = lowered.compile().memory_analysis()
        used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes)
        assert used < 16 * 2 ** 30, (first, last, used)
        if program == "write_window":
            break                        # one program for every stage


# A whole stage's pool, as the cells hold it: (name, layers, Hkv * D, pool)
POOLS = [("gpt2-1.5b", 24, 25 * 64, 1048), ("opt-66b", 2, 72 * 128, 1036)]


@pytest.mark.parametrize("pool", POOLS, ids=[p[0] for p in POOLS])
@pytest.mark.parametrize("program", ["read", "copy", "install"])
def test_block_programs_leave_the_pool_in_place(program, pool, one_chip,
                                                no_compile_cache):
    """Reading, copying and installing 8 blocks of a whole stage's pool
    compiles for one v5e chip with no temporary beyond the blocks moved:
    no copy of the pool, or of part of it, before the gather."""
    from repro.kvcache import paged
    _, layers, hd, blocks = pool
    s = _spec(one_chip)
    pages, ids = s((layers, blocks, 8, hd), BF16), s((8,), I32)
    lowered = {
        "read": lambda: paged._read_blocks.lower(pages, pages, ids),
        "copy": lambda: paged._copy_blocks.lower(pages, pages, ids, ids),
        "install": lambda: paged._install_blocks.lower(
            pages, pages, ids, ids, s((2, layers, 8, 8, hd), BF16)),
    }[program]()
    moved = 2 * layers * 8 * 8 * hd * 2          # K and V of 8 blocks
    assert lowered.compile().memory_analysis().temp_size_in_bytes <= moved


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
