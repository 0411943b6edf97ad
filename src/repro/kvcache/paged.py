"""Paged KV-cache block pool (vLLM-style) under DéjàVu streaming.

The decode state of every live sequence is stored in fixed-size *blocks* of
``block_size`` token slots drawn from one shared pool, instead of one
contiguous per-microbatch cache sized ``prompt + max_new``:

  ``BlockPool``      control plane — ref-counted blocks, per-sequence block
                     tables, alloc/append/free, prefix-sharing (hash-chain
                     over full prompt blocks, copy-on-write on divergence),
                     and defragmentation (compaction to the lowest ids);
  ``PagedKVCache``   data plane — the page arrays of one pipeline stage
                     ``[Lstage, num_blocks, block_size, Hkv * Dh]`` on its
                     device, with the jitted writers and the one reader;
                     the stage programs gather pages by block table
                     (`gather_layer`) on the device.

Blocks are also DéjàVu's streaming unit: swapping, ring replication, and
recovery (see `repro.core.worker` / `repro.core.cluster`) move individual
live blocks through DéjàVuLib instead of whole padded caches, so the bytes
on the wire track actual occupancy.

The pool is only tier 0 of the KV-cache hierarchy: `repro.kvcache.tiers`
(`KVTierManager`) extends it with host-RAM and SSD tiers — cold blocks are
demoted down-tier as write-behind, preempted sequences swap to host instead
of being dropped, and the prefix hashes published here persist across
requests, so `adopt_prefix` can rebuild a new sequence's prompt prefix from
blocks streamed back out of ANY tier instead of re-prefilling them.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry


class PoolExhausted(MemoryError):
    """No free block to satisfy an alloc/append — callers preempt or queue."""


@dataclass
class Block:
    bid: int
    ref: int = 0
    # content hash (prefix chain) — only set for FULL immutable prompt blocks
    hash: Optional[int] = None


def blocks_for(num_tokens: int, block_size: int) -> int:
    """Blocks needed to hold `num_tokens` token slots."""
    return -(-max(num_tokens, 0) // block_size)


class BlockPool:
    """Ref-counted fixed-size block allocator with per-sequence block tables.

    Invariants (property-tested in tests/test_paged_kv.py):
      * a block id is on the free list XOR referenced by >= 1 table;
      * sum of table multiplicities of a block == its ref count;
      * after all sequences are freed, every block is free again.
    """

    def __init__(self, num_blocks: int, block_size: int):
        assert num_blocks > 0 and block_size > 0
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.blocks = [Block(i) for i in range(num_blocks)]
        self._free: List[int] = list(range(num_blocks - 1, -1, -1))  # pop() -> lowest id
        self.tables: Dict[int, List[int]] = {}       # seq -> block ids (logical order)
        self.seq_lens: Dict[int, int] = {}           # seq -> live token count
        self._hash_index: Dict[int, int] = {}        # prefix hash -> bid
        self.peak_used_blocks = 0

    # --- accounting ----------------------------------------------------
    def num_free(self) -> int:
        return len(self._free)

    def num_used(self) -> int:
        return self.num_blocks - len(self._free)

    def can_allocate(self, num_tokens: int) -> bool:
        return blocks_for(num_tokens, self.block_size) <= self.num_free()

    def _track_peak(self) -> None:
        self.peak_used_blocks = max(self.peak_used_blocks, self.num_used())

    # --- alloc / append / free -----------------------------------------
    def append_needs_block(self, seq: int) -> bool:
        """Would `append(seq, 1)` consume a free block?  (New block at a
        block boundary, or copy-on-write off a shared tail block.)"""
        cur = self.seq_lens[seq]
        table = self.tables[seq]
        if cur % self.block_size == 0 or not table:
            return True
        return self.blocks[table[-1]].ref > 1

    def _take_block(self) -> int:
        if not self._free:
            raise PoolExhausted("block pool exhausted")
        bid = self._free.pop()
        blk = self.blocks[bid]
        assert blk.ref == 0
        blk.ref = 1
        blk.hash = None
        return bid

    def _drop_ref(self, bid: int) -> None:
        blk = self.blocks[bid]
        blk.ref -= 1
        assert blk.ref >= 0
        if blk.ref == 0:
            if blk.hash is not None:
                self._hash_index.pop(blk.hash, None)
            blk.hash = None
            self._free.append(bid)

    @staticmethod
    def chain_hashes(token_ids: Sequence[int], block_size: int) -> List[int]:
        """Prefix hash chain over the FULL blocks of a token sequence."""
        hashes, prev = [], 0
        n_full = len(token_ids) // block_size
        for j in range(n_full):
            chunk = tuple(int(t) for t in token_ids[j * block_size:(j + 1) * block_size])
            prev = hash((prev, chunk))
            hashes.append(prev)
        return hashes

    def allocate(self, seq: int, num_tokens: int,
                 token_ids: Optional[Sequence[int]] = None,
                 hashes: Optional[Sequence[int]] = None,
                 publish: bool = True) -> Tuple[List[int], List[int]]:
        """Allocate a table for `seq` holding `num_tokens` live tokens.

        With `token_ids` (the prompt) — or a precomputed prefix-hash chain
        `hashes` (recovery/restore, where the prompt is no longer at hand) —
        full blocks whose prefix hash matches a live block are SHARED (ref++)
        instead of newly allocated.  Returns ``(table, fresh)`` where `fresh`
        lists the logical block indices the caller must actually write
        (shared ones already hold the data).

        ``publish=False`` still SHARES matching live blocks but does not
        publish the fresh blocks' hashes: chunked prefill writes pages over
        several passes, so it publishes each block via `publish_hashes` only
        once the pages actually hold the data — a concurrent allocate/adopt
        must never share unwritten pages.
        """
        assert seq not in self.tables, f"seq {seq} already allocated"
        n = blocks_for(num_tokens, self.block_size)
        if hashes is None:
            hashes = (self.chain_hashes(token_ids, self.block_size)
                      if token_ids is not None else [])
        else:
            hashes = list(hashes)
        # pre-flight so a mid-allocation PoolExhausted can't leak blocks
        need = sum(1 for j in range(n)
                   if not (j < len(hashes) and hashes[j] in self._hash_index))
        if need > self.num_free():
            raise PoolExhausted(
                f"need {need} blocks for seq {seq}, {self.num_free()} free")
        table: List[int] = []
        fresh: List[int] = []
        for j in range(n):
            h = hashes[j] if j < len(hashes) else None
            if h is not None and h in self._hash_index:
                bid = self._hash_index[h]
                self.blocks[bid].ref += 1
                table.append(bid)
                continue
            bid = self._take_block()
            if h is not None and publish:
                self.blocks[bid].hash = h
                self._hash_index[h] = bid
            table.append(bid)
            fresh.append(j)
        self.tables[seq] = table
        self.seq_lens[seq] = num_tokens
        self._track_peak()
        return table, fresh

    def publish_hashes(self, seq: int, hashes: Sequence[int]) -> int:
        """Publish prefix-chain hashes for the LEADING blocks of `seq` (one
        hash per logical block, starting at block 0).  Chunked prefill calls
        this as each block's pages complete, pairing with
        ``allocate(..., publish=False)``.  Blocks already hashed (shared) and
        hashes already in the index are skipped.  Returns #published."""
        table = self.tables[seq]
        n = 0
        for j, h in enumerate(hashes):
            if j >= len(table):
                break
            blk = self.blocks[table[j]]
            if blk.hash is None and h not in self._hash_index:
                blk.hash = h
                self._hash_index[h] = table[j]
                n += 1
        return n

    def has_hash(self, h: int) -> bool:
        """Is a live block holding this prefix-chain hash resident (tier 0)?"""
        return h in self._hash_index

    def adopt_prefix(self, seq: int, hashes: Sequence[int],
                     num_tokens: int) -> Tuple[List[int], List[Tuple[int, int]]]:
        """Build `seq`'s table from an already-materialised prefix chain
        (cross-request reuse: the bytes come from a co-resident shared block
        or are promoted out of a lower tier by `KVTierManager`).

        Each hash either refs the live block holding it or takes a fresh
        block and publishes the hash.  Returns ``(table, fills)`` where
        `fills` lists ``(hash, bid)`` pairs whose pages the caller must
        install.  Raises PoolExhausted BEFORE any mutation."""
        assert seq not in self.tables, f"seq {seq} already allocated"
        assert num_tokens <= len(hashes) * self.block_size
        need = sum(1 for h in hashes if h not in self._hash_index)
        if need > self.num_free():
            raise PoolExhausted(
                f"need {need} blocks to adopt prefix for seq {seq}, "
                f"{self.num_free()} free")
        table: List[int] = []
        fills: List[Tuple[int, int]] = []
        for h in hashes:
            bid = self._hash_index.get(h)
            if bid is None:
                bid = self._take_block()
                self.blocks[bid].hash = h
                self._hash_index[h] = bid
                fills.append((h, bid))
            else:
                self.blocks[bid].ref += 1
            table.append(bid)
        self.tables[seq] = table
        self.seq_lens[seq] = num_tokens
        self._track_peak()
        return table, fills

    def append(self, seq: int, n: int = 1) -> List[Tuple[int, int]]:
        """Grow `seq` by `n` token slots.  Returns copy-on-write directives
        ``[(old_bid, new_bid), ...]`` — the caller must copy page contents of
        `old_bid` into `new_bid` (a shared last block diverges on write)."""
        table = self.tables[seq]
        cur = self.seq_lens[seq]
        # pre-flight (atomicity): new blocks at boundary crossings + at most
        # one copy-on-write when the first slot lands inside a shared block
        need = blocks_for(cur + n, self.block_size) - len(table)
        if table and cur % self.block_size != 0 and \
                self.blocks[table[-1]].ref > 1:
            need += 1
        if need > self.num_free():
            raise PoolExhausted(
                f"need {need} blocks to append to seq {seq}, "
                f"{self.num_free()} free")
        cow: List[Tuple[int, int]] = []
        for _ in range(n):
            if cur % self.block_size == 0 or not table:
                table.append(self._take_block())
            else:
                last = self.blocks[table[-1]]
                if last.ref > 1:                       # diverging from a shared block
                    new_bid = self._take_block()
                    cow.append((table[-1], new_bid))
                    self._drop_ref(table[-1])
                    table[-1] = new_bid
                elif last.hash is not None:
                    # uniquely owned but published for sharing: unpublish, the
                    # block is about to be mutated past the hashed prefix
                    self._hash_index.pop(last.hash, None)
                    last.hash = None
            cur += 1
        self.seq_lens[seq] = cur
        self._track_peak()
        return cow

    def truncate(self, seq: int, num_tokens: int) -> List[int]:
        """Roll `seq` back to `num_tokens` live tokens (failure-recovery
        rollback), freeing now-empty tail blocks.  Returns freed bids."""
        table = self.tables[seq]
        keep = blocks_for(max(num_tokens, 1), self.block_size)
        freed = []
        while len(table) > keep:
            bid = table.pop()
            self._drop_ref(bid)
            freed.append(bid)
        self.seq_lens[seq] = num_tokens
        return freed

    def free_seq(self, seq: int) -> None:
        for bid in self.tables.pop(seq):
            self._drop_ref(bid)
        del self.seq_lens[seq]

    def block_span(self, seq: int) -> Iterator[Tuple[int, int, int, int]]:
        """Yield ``(logical_idx, bid, t0, t1)`` for every live block of `seq`
        (t0/t1 = global token range covered; t1 clipped to the live length)."""
        n = self.seq_lens[seq]
        for j, bid in enumerate(self.tables[seq]):
            t0 = j * self.block_size
            t1 = min(t0 + self.block_size, n)
            if t1 <= t0:
                return
            yield j, bid, t0, t1

    def padded_tables(self, seqs: Sequence[int], width: int) -> np.ndarray:
        """The block tables of `seqs` as one int32 ``[B, width]`` array,
        each padded with block 0.  Pad slots lie past every sequence's live
        length, where the attention mask hides them."""
        out = np.zeros((len(seqs), width), np.int32)
        for i, seq in enumerate(seqs):
            table = self.tables[seq]
            if len(table) > width:
                raise ValueError(f"seq {seq} holds {len(table)} blocks, past "
                                 f"the table width {width}")
            out[i, :len(table)] = table
        return out

    # --- defragmentation ------------------------------------------------
    def defrag(self) -> Dict[int, int]:
        """Compact live blocks onto the lowest ids (so a pool shrink / a
        contiguous DMA window is possible).  Returns {old_bid: new_bid};
        the data plane must apply the same moves to its pages."""
        live = sorted({bid for t in self.tables.values() for bid in t})
        moves: Dict[int, int] = {}
        target = 0
        for bid in live:
            if bid != target:
                moves[bid] = target
                src, dst = self.blocks[bid], self.blocks[target]
                dst.ref, dst.hash = src.ref, src.hash
                src.ref, src.hash = 0, None
                if dst.hash is not None:
                    self._hash_index[dst.hash] = target
            target += 1
        if moves:
            for table in self.tables.values():
                for i, bid in enumerate(table):
                    table[i] = moves.get(bid, bid)
            self._free = list(range(self.num_blocks - 1, target - 1, -1))
        return moves


# ---------------------------------------------------------------------------
# device data plane: pure functions over one stage's page array
# ``[Lstage, N, bs, Hkv * Dh]``.  Each (layer, block) is one ``[bs, Hkv*Dh]``
# slab of whole TPU tiles, so the array is stored all but unpadded (a 5-D
# ``[..., Hkv, Dh]`` layout pads its two minor dims to whole tiles: 2.56x
# at 25 heads of 64), and every gather, read and write moves whole slabs:
# the device never relays out the pool.  Writes therefore go a block at a
# time, the blocks a pass wrote into, whole.
# ---------------------------------------------------------------------------

def gather_layer(pages: jax.Array, table: jax.Array, layer, heads: int,
                 head_dim: int) -> jax.Array:
    """One layer's dense cache ``[B, T*bs, Hkv, Dh]`` from the pages by
    block table ``[B, T]``: slot s of sequence b is token s of its
    context."""
    slabs = pages[layer, table]                        # [B, T, bs, H*D]
    b, t, bs, _ = slabs.shape
    return slabs.reshape(b, t * bs, heads, head_dim)


def window_blocks(width: int, block_size: int) -> int:
    """Blocks a window of `width` tokens can touch, from any start."""
    return (width + block_size - 2) // block_size + 1


def block_window(cache: jax.Array, t0: jax.Array, width: int,
                 block_size: int) -> jax.Array:
    """The slots of the blocks that a pass writing tokens ``t0[b] ..
    t0[b]+width-1`` touches, from the block holding ``t0[b]`` on, out of a
    dense cache ``[B, S, H, D]`` -> ``[B, window_blocks(width) * bs, H, D]``
    (slots past S are clipped: their blocks lie past the table)."""
    n = window_blocks(width, block_size) * block_size
    idx = (t0 // block_size * block_size)[:, None] \
        + jnp.arange(n, dtype=jnp.int32)[None, :]
    idx = jnp.clip(idx, 0, cache.shape[1] - 1)
    return jnp.take_along_axis(cache, idx[:, :, None, None], axis=1)


def scatter_blocks(pages: jax.Array, table: jax.Array, t0: jax.Array,
                   lens: jax.Array, slots: jax.Array) -> jax.Array:
    """Write back the blocks each sequence's pass wrote into, whole:
    ``slots`` ``[Lstage, B, nb*bs, H, D]`` are sequence b's slots from the
    block holding token ``t0[b]`` on (`block_window`); the blocks holding
    tokens ``t0[b] .. t0[b]+lens[b]-1`` are written, every layer at once,
    and the others get an out-of-range id and are dropped."""
    layers, n, bs, hd = pages.shape
    _, b, w = slots.shape[:3]
    nb = w // bs
    first = t0 // bs
    j = first[:, None] + jnp.arange(nb, dtype=jnp.int32)[None, :]  # [B, nb]
    last = (t0 + lens - 1) // bs
    ok = (j <= last[:, None]) & (lens[:, None] > 0)
    blk = jnp.where(ok, jnp.take_along_axis(
        table, jnp.clip(j, 0, table.shape[1] - 1), axis=1), n)
    upd = slots.reshape(layers, b * nb, bs, hd).astype(pages.dtype)
    return pages.at[:, blk.reshape(-1)].set(upd, mode="drop")


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _write_window(kp, vp, table, t0, lens, k_slots, v_slots):
    return (scatter_blocks(kp, table, t0, lens, k_slots),
            scatter_blocks(vp, table, t0, lens, v_slots))


def _at_blocks(pages: jax.Array, bids: jax.Array):
    """Index of blocks `bids` in every layer of ``[Lstage, N, ...]``, as
    (layer, block) pairs: indexing the block axis alone makes XLA on TPU
    copy a third of the pool into a temporary before the gather."""
    return jnp.arange(pages.shape[0])[:, None], bids[None, :]


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _copy_blocks(kp, vp, src, dst):
    # every source is read before any destination is written
    return tuple(p.at[_at_blocks(p, dst)].set(p[_at_blocks(p, src)],
                                             mode="drop")
                 for p in (kp, vp))


@jax.jit
def _read_blocks(kp, vp, bids):
    return jnp.stack([p[_at_blocks(p, bids)] for p in (kp, vp)])


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _install_blocks(kp, vp, bids, widths, kv):
    # each block takes kv's first `widths` slots and keeps the rest
    keep = jnp.arange(kp.shape[2], dtype=jnp.int32)[None, :] \
        < widths[:, None]
    return tuple(
        p.at[_at_blocks(p, bids)].set(
            jnp.where(keep[None, :, :, None], kv[i].astype(p.dtype),
                      p[_at_blocks(p, bids)]), mode="drop")
        for i, p in enumerate((kp, vp)))


def _bucket(n: int) -> int:
    """The power of two at or above `n`: block-list programs compile once
    per bucket, not once per list length."""
    return 1 << max(n - 1, 0).bit_length()


@dataclass
class PagedKVCache:
    """Data plane for one pipeline stage: its pages as two ``jax.Array``s
    (K, V) ``[Lstage, N, bs, Hkv * Dh]`` on the stage's `device`.

    The stage programs gather a pass's dense cache from the pages by block
    table on the device (`gather_layer`); every write — a pass's new window,
    copy-on-write, defrag, installed blocks — is one jitted update that
    donates the pages, so they change in place.  Pages leave the device only
    through `read_blocks`, counted as ``kvpool.block_reads``; blocks cross
    the host boundary as ``[Lstage, width, Hkv, Dh]`` arrays."""
    pool: BlockPool
    layers: int
    num_kv_heads: int
    head_dim: int
    dtype: str = "float32"
    device: Optional[jax.Device] = None
    k: jax.Array = field(init=False)
    v: jax.Array = field(init=False)

    def __post_init__(self):
        if self.device is None:
            self.device = jax.local_devices()[0]
        shape = (self.layers, self.pool.num_blocks, self.pool.block_size,
                 self.num_kv_heads * self.head_dim)
        self.k = jnp.zeros(shape, jnp.dtype(self.dtype), device=self.device)
        self.v = jnp.zeros(shape, jnp.dtype(self.dtype), device=self.device)

    @property
    def block_bytes(self) -> int:
        return 2 * self.layers * self.pool.block_size * self.num_kv_heads \
            * self.head_dim * np.dtype(self.dtype).itemsize

    def used_bytes(self) -> int:
        return self.pool.num_used() * self.block_bytes

    def _ids(self, bids: Sequence[int], pad: int) -> jax.Array:
        out = np.full(_bucket(len(bids)), pad, np.int32)
        out[:len(bids)] = bids
        return jax.device_put(out, self.device)

    # --- writers --------------------------------------------------------
    def write_window(self, table, t0, kv: Dict[str, jax.Array],
                     lens=None) -> None:
        """Write a pass's window back into the pages: sequence b wrote
        tokens ``t0[b] .. t0[b]+lens[b]-1`` (``lens`` defaults to 1) into
        the blocks of row b of the block table ``[B, T]``, and ``kv["k"]`` /
        ``kv["v"]`` ``[Lstage, B, nb*bs, H, D]`` hold the slots of its
        blocks from the one holding ``t0[b]`` on (`block_window`).  Those
        blocks are written whole; a block past ``lens[b]`` is not touched."""
        t0 = jnp.asarray(t0, jnp.int32)
        if lens is None:
            lens = jnp.ones(t0.shape, jnp.int32)
        self.k, self.v = _write_window(self.k, self.v, table, t0,
                                       jnp.asarray(lens, jnp.int32),
                                       kv["k"], kv["v"])

    def apply_cow(self, cow: Sequence[Tuple[int, int]]) -> None:
        """Copy-on-write directives ``[(old, new), ...]`` from the pool."""
        if cow:
            self.k, self.v = _copy_blocks(
                self.k, self.v, self._ids([o for o, _ in cow], 0),
                self._ids([n for _, n in cow], self.pool.num_blocks))

    def apply_defrag(self, moves: Dict[int, int]) -> None:
        """The pool's defrag moves ``{old: new}``: each new id is either
        free or a block that moves too, so reading every source before
        writing gives the pool's order of moves."""
        self.apply_cow(sorted(moves.items(), key=lambda kv: kv[1]))

    def install_blocks(self, blocks: Sequence[Tuple[int, Dict[str, np.ndarray]]]
                       ) -> None:
        """Write host blocks ``[(bid, {"k": [Lstage, w, H, D], "v": ...})]``
        into their pages (each block's first w token slots): one upload and
        one update."""
        if not blocks:
            return
        n, bs = _bucket(len(blocks)), self.pool.block_size
        kv = np.zeros((2, self.layers, n, bs, self.num_kv_heads,
                       self.head_dim), np.dtype(self.dtype))
        widths = np.zeros(n, np.int32)
        for i, (_, arrays) in enumerate(blocks):
            for li, leaf in enumerate(("k", "v")):
                arr = np.asarray(arrays[leaf])
                kv[li, :, i, :arr.shape[1]] = arr
            widths[i] = min(arrays["k"].shape[1], bs)
        self.k, self.v = _install_blocks(
            self.k, self.v, self._ids([b for b, _ in blocks],
                                      self.pool.num_blocks),
            jax.device_put(widths, self.device),
            jax.device_put(kv.reshape(kv.shape[:4] + (-1,)), self.device))

    # --- readers --------------------------------------------------------
    def read_blocks(self, spans: Sequence[Tuple[int, int]]
                    ) -> List[Dict[str, np.ndarray]]:
        """Blocks off the device, as ``[(bid, width), ...]`` -> each block's
        first `width` token slots ``{"k": [Lstage, width, H, D], "v": ...}``
        on the host: one take over the block ids and one transfer.  The
        unit DéjàVuLib streams for swap / replication / recovery / tiers."""
        if not spans:
            return []
        both = np.asarray(_read_blocks(self.k, self.v,
                                       self._ids([b for b, _ in spans], 0)))
        both = both.reshape(both.shape[:4] + (self.num_kv_heads,
                                              self.head_dim))
        telemetry.count("kvpool.block_reads", len(spans))
        telemetry.count("kvpool.block_read_bytes",
                        sum(w for _, w in spans) * self.block_bytes
                        // self.pool.block_size)
        return [{"k": both[0, :, i, :w], "v": both[1, :, i, :w]}
                for i, (_, w) in enumerate(spans)]
