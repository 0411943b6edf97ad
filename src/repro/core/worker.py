"""DéjàVu workers: one logical machine = one pipeline stage.

A `StageWorker` owns a contiguous layer slice of the model (jitted stage
functions), its device-resident KV slots, a host memory store (swap target +
prompt-KV landing zone), and a replica store holding its ring-predecessor's
KV copies (paper §4.2.3: worker x streams to worker (x+1)%N).

Failure semantics (paper): killing a worker loses BOTH its device KV and the
replica it hosts; `CacheManager` streams are how every byte moves (DéjàVuLib
primitives only — no ad-hoc copies).
"""
from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry
from repro.core import tracing
from repro.core.dejavulib import (HostLinkTransport, HostMemoryStore,
                                  LocalTransport, NetworkTransport,
                                  StreamEngine)
from repro.core.dejavulib.transport import DEFAULT_HW, HardwareModel
from repro.kvcache.paged import (BlockPool, PagedKVCache, PoolExhausted,
                                 blocks_for, window_blocks)
from repro.kvcache.tiers import KVTierManager, TierConfig


class CacheManager:
    """Per-worker KV movement: swap in/out, replicate, receive (paper Fig. 5).

    `compress_replicas=True` (beyond-paper) int8-quantizes each replicated KV
    window (per-window scale) before it crosses the network and dequantizes
    into the peer's replica store — the wire bytes halve vs bf16 while the
    recovery path stays byte-layout-identical.  The quantization error only
    ever enters live state after an actual failure restore.
    """

    def __init__(self, wid: int, hw: HardwareModel, streamer: StreamEngine,
                 token_block: int = 8, compress_replicas: bool = False):
        self.wid = wid
        self.host = HostMemoryStore(f"w{wid}-host")        # swap + prompt landing
        self.replica = HostMemoryStore(f"w{wid}-replica")  # peer's KV copies
        self.hostlink = HostLinkTransport(hw)
        self.net = NetworkTransport(hw)
        self.local = LocalTransport(hw)
        self.streamer = streamer
        self.token_block = token_block
        self.compress_replicas = compress_replicas

    # --- swapping (microbatch granularity, paper §4.2.2) -------------------
    def swap_out(self, mb: int, kv: Dict[str, jax.Array],
                 token_range: Optional[Tuple[int, int]] = None) -> None:
        """Offload a microbatch's stage KV to host.  With `token_range`, only
        the newly-written window moves (buffered copies via kv_pack)."""
        from repro.kernels import ops as kops
        for leaf, arr in kv.items():
            key = f"swap/mb{mb}/{leaf}"
            if token_range is None:
                buf = self.hostlink.transfer(np.asarray(arr), tag=key)
                self.host.put(key, buf)     # transfer() copy is writable
                continue
            t0, t1 = token_range
            tb = self.token_block
            t0a = (t0 // tb) * tb
            w = min(-(-(t1 - t0a) // tb) * tb, arr.shape[2] - t0a)
            packed = np.asarray(kops.kv_pack_auto(arr, t0a, w, token_block=tb))
            self.hostlink.transfer(packed, tag=key)
            dense = self.host.get(key)          # update host copy in place
            dense[:, :, t0a:t0a + w] = packed
            self.host.put(key, dense)

    def swap_in(self, mb: int) -> Dict[str, np.ndarray]:
        """The microbatch's host copy, charged to the host link; the worker
        places it on its device."""
        out = {}
        for leaf in ("k", "v"):
            key = f"swap/mb{mb}/{leaf}"
            out[leaf] = self.host.get(key)
            self.hostlink.transfer(out[leaf], tag=key)
        return out

    def host_has(self, mb: int) -> bool:
        return f"swap/mb{mb}/k" in self.host

    # --- replication (ring, token-level, paper §4.2.3) ----------------------
    def replicate_to(self, peer: "CacheManager", mb: int,
                     kv: Dict[str, jax.Array], token_range: Tuple[int, int],
                     step: int, ack_cb) -> None:
        """Stream the KV delta [t0,t1) to the ring successor's replica store.
        Runs on the background streamer (overlapped with the next step)."""
        from repro.kernels import ops as kops
        t0, t1 = token_range
        tb = self.token_block
        t0a = (t0 // tb) * tb
        packed = {}
        for leaf, arr in kv.items():
            w = min(-(-(t1 - t0a) // tb) * tb, arr.shape[2] - t0a)
            packed[leaf] = (np.asarray(kops.kv_pack_auto(arr, t0a, w, token_block=tb)),
                            arr.shape, arr.dtype)

        def _send():
            nbytes = 0
            for leaf, (buf, shape, dtype) in packed.items():
                key = f"w{self.wid}/mb{mb}/{leaf}"
                if self.compress_replicas:
                    scale = max(float(np.max(np.abs(buf))), 1e-8) / 127.0
                    q = np.clip(np.round(buf.astype(np.float32) / scale),
                                -127, 127).astype(np.int8)
                    sent = self.net.transfer(q, tag=key + "/int8")
                    recv = (sent.astype(np.float32) * scale).astype(dtype)
                else:
                    sent = self.net.transfer(buf, tag=key)
                    recv = sent
                if key in peer.replica:
                    dense = peer.replica.get(key)
                else:
                    dense = np.zeros(shape, dtype)
                dense[:, :, t0a:t0a + recv.shape[2]] = recv
                peer.replica.put(key, dense)
                nbytes += sent.nbytes
            ack_cb(self.wid, mb, step)
            return nbytes

        raw = sum(b.nbytes for b, _, _ in packed.values())
        model_s = self.net.model_time(raw // 2 if self.compress_replicas else raw)
        self.streamer.submit(_send, model_seconds=model_s,
                             tag=f"rep-w{self.wid}-mb{mb}-s{step}")

    # --- paged-mode movement (block granularity) ------------------------
    def replicate_block_to(self, peer: "CacheManager", seq: int, j: int,
                           arrays: Dict[str, np.ndarray], step: int,
                           ack_cb) -> int:
        """Stream ONE live KV block to the ring successor's replica store.
        Only the block touched this step crosses the wire (vs the dense
        path's token-window of a padded cache).  Returns the block's bytes
        (before any compression)."""
        def _send():
            nbytes = 0
            for leaf, arr in arrays.items():
                key = f"w{self.wid}/seq{seq}/blk{j}/{leaf}"
                if self.compress_replicas:
                    scale = max(float(np.max(np.abs(arr))), 1e-8) / 127.0
                    q = np.clip(np.round(arr.astype(np.float32) / scale),
                                -127, 127).astype(np.int8)
                    sent = self.net.transfer(q, tag=key + "/int8")
                    recv = (sent.astype(np.float32) * scale).astype(arr.dtype)
                else:
                    sent = self.net.transfer(arr, tag=key)
                    recv = sent
                peer.replica.put(key, np.array(recv))
                nbytes += sent.nbytes
            ack_cb(self.wid, seq, step)
            return nbytes

        raw = sum(a.nbytes for a in arrays.values())
        model_s = self.net.model_time(raw // 2 if self.compress_replicas else raw)
        self.streamer.submit(_send, model_seconds=model_s,
                             tag=f"rep-w{self.wid}-seq{seq}-blk{j}-s{step}")
        return raw

    def replica_blocks(self, wid: int, seq: int) -> Dict[int, Dict[str, np.ndarray]]:
        """All replica blocks this store holds for (failed worker, seq)."""
        prefix = f"w{wid}/seq{seq}/blk"
        out: Dict[int, Dict[str, np.ndarray]] = {}
        for key in self.replica.keys():
            if key.startswith(prefix):
                j, leaf = key[len(prefix):].split("/")
                out.setdefault(int(j), {})[leaf] = self.replica.get(key)
        return out

    def swap_out_blocks(self, seq: int,
                        blocks: Dict[int, Dict[str, np.ndarray]]) -> int:
        """Offload the given (dirty) blocks of `seq` to host memory."""
        nbytes = 0
        for j, arrays in blocks.items():
            for leaf, arr in arrays.items():
                key = f"pagedswap/seq{seq}/blk{j}/{leaf}"
                buf = self.hostlink.transfer(arr, tag=key)
                self.host.put(key, buf)
                nbytes += buf.nbytes
        return nbytes

    def swap_in_blocks(self, seq: int) -> Dict[int, Dict[str, np.ndarray]]:
        prefix = f"pagedswap/seq{seq}/blk"
        out: Dict[int, Dict[str, np.ndarray]] = {}
        for key in self.host.keys():
            if key.startswith(prefix):
                j, leaf = key[len(prefix):].split("/")
                arr = self.host.get(key)
                self.hostlink.transfer(arr, tag=key)
                out.setdefault(int(j), {})[leaf] = arr
        return out

    def drop_seq_swap(self, seq: int) -> None:
        for key in [k for k in self.host.keys()
                    if k.startswith(f"pagedswap/seq{seq}/")]:
            self.host.delete(key)


class StageWorker:
    """One pipeline stage (a machine with `chips` accelerators running TP).

    The stage runs on `device` (default: the first local device): its slice
    of the parameters and every KV array it computes on are placed there,
    so its jitted stage functions execute there."""

    def __init__(self, wid: int, model, full_params, lo: int, hi: int, *,
                 first: bool, last: bool, role: str = "both",
                 hw: HardwareModel = DEFAULT_HW,
                 streamer: Optional[StreamEngine] = None,
                 compress_replicas: bool = False,
                 device: Optional[jax.Device] = None):
        self.wid = wid
        self.model = model
        self.lo, self.hi = lo, hi
        self.first, self.last = first, last
        self.role = role                      # "prompt" | "token" | "both"
        self.alive = True
        self.hw = hw
        self.device = device if device is not None else jax.local_devices()[0]
        self.last_heartbeat = time.monotonic()
        self.sp = self.put(model.slice_params(full_params, lo, hi,
                                              first=first, last=last))
        self.kv: Dict[int, Dict[str, jax.Array]] = {}   # device-resident slots
        self.cache = CacheManager(wid, hw, streamer or StreamEngine(f"w{wid}"),
                                  compress_replicas=compress_replicas)
        self.slow_factor = 1.0                # straggler injection knob
        # paged mode (enable_paging): block pool + pages for this layer slice
        self.pool: Optional[BlockPool] = None
        self.pages: Optional[PagedKVCache] = None
        self.tier: Optional[KVTierManager] = None   # enable_tiering
        self.paged_dirty: Dict[int, set] = {}       # seq -> dirty logical blocks
        self.paged_swapped: Dict[int, int] = {}     # seq -> offloaded length

        fns = model.stage_jits(first=first, last=last)
        self._prefill = fns["prefill"]
        self._decode = fns["decode"]
        self._decode_batch_paged = fns["decode_batch_paged"]
        self._prefill_chunk_batch_paged = fns["prefill_chunk_batch_paged"]

    def put(self, tree):
        """Place host or device arrays on this stage's device."""
        return jax.device_put(tree, self.device)

    # ------------------------------------------------------------------
    def heartbeat(self) -> bool:
        if self.alive:
            self.last_heartbeat = time.monotonic()
        return self.alive

    def kill(self) -> None:
        """Machine failure: device KV, host store, and hosted replica all die.
        The tier manager's host tier dies too; its SSD tier is disk and
        survives (recovery reattaches it on the replacement worker).

        Queued write-behinds are flushed before tier-1 state is wiped:
        already-issued DMA/disk writes complete even as the host dies (a
        transfer truly lost in flight is modeled by the transport ``drop``
        fault instead).  Without the flush a queued spill would observe the
        post-mortem empty host store and corrupt the tier index."""
        telemetry.count("worker.kills", 1, wid=self.wid)
        tracing.event("worker.kill", wid=self.wid)
        self.alive = False
        self.kv.clear()
        if (self.tier is not None
                and threading.current_thread() is not self.tier.streamer._thread):
            try:
                self.tier.streamer.drain()
            except Exception:
                # a write-behind racing the failure dies with the worker;
                # recovery must not trust its bytes (on_host_failure
                # re-verifies every on_ssd claim against the disk)
                pass
        self.cache.host.clear()
        self.cache.replica.clear()
        if self.tier is not None:
            self.tier.on_host_failure()

    def _check(self):
        if not self.alive:
            raise RuntimeError(f"worker {self.wid} is dead")
        # every stage op (prefill/decode, paged or not) passes through here
        telemetry.count("worker.stage_calls", 1, wid=self.wid)

    # A stage op's host layers are `tracing` spans tagged with `wid`:
    # worker.gather / upload / stage, kvpool.append / write_window
    # (docs/observability.md, "Spans of the served path").
    def _stage(self, fn, *args):
        """The jitted stage call.  Its span times dispatch only: JAX
        returns before the device finishes, and the host waits for it
        where it next reads a result (the sampler's logits)."""
        with tracing.span("worker.stage", wid=self.wid):
            return fn(self.sp, *args)

    # ------------------------------------------------------------------
    def prefill(self, mb: int, x_or_tokens, max_len: int):
        self._check()
        x, ks, vs = self._stage(self._prefill, x_or_tokens)
        kc = jnp.zeros(ks.shape[:2] + (max_len,) + ks.shape[3:], ks.dtype,
                       device=self.device)
        vc = jnp.zeros_like(kc)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, ks, 0, axis=2)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, vs, 0, axis=2)
        self.kv[mb] = {"k": kc, "v": vc}
        return x

    def decode(self, mb: int, x_or_token, pos: int):
        self._check()
        slot = self.kv[mb]
        x, kc, vc = self._stage(self._decode, x_or_token, slot["k"],
                                slot["v"], jnp.int32(pos))
        self.kv[mb] = {"k": kc, "v": vc}
        return x

    # --- swapping ------------------------------------------------------
    def offload(self, mb: int, token_range=None) -> None:
        if mb in self.kv:
            self.cache.swap_out(mb, self.kv[mb], token_range)
            del self.kv[mb]

    def restore(self, mb: int) -> None:
        if mb not in self.kv and self.cache.host_has(mb):
            self.kv[mb] = self.put(self.cache.swap_in(mb))

    def resident(self) -> int:
        return len(self.kv)

    def install_kv(self, mb: int, arrays: Dict[str, np.ndarray]) -> None:
        self.kv[mb] = self.put(arrays)

    # ------------------------------------------------------------------
    # paged mode: per-sequence KV in ref-counted blocks (see kvcache.paged)
    # ------------------------------------------------------------------
    def enable_paging(self, num_blocks: int, block_size: int) -> None:
        """A block pool and its pages on this stage's device.  Every paged
        pass hands the stage program block tables of one fixed width, the
        blocks of the longest context the model serves, so its shapes vary
        only with the batch (and the chunk length)."""
        cfg = self.model.cfg
        self.pool = BlockPool(num_blocks, block_size)
        self.pages = PagedKVCache(self.pool, layers=self.hi - self.lo,
                                  num_kv_heads=cfg.num_kv_heads,
                                  head_dim=cfg.resolved_head_dim,
                                  dtype=cfg.dtype, device=self.device)
        # a sequence never holds more blocks than the pool has, so a model
        # whose context bound is far past the pool gets the pool's width
        self.table_width = min(
            blocks_for(cfg.max_seq_len + cfg.context_overhead, block_size),
            num_blocks)

    def check_table_fits(self, n_tokens: int) -> None:
        """Raise ValueError if a context of `n_tokens` would outgrow the
        fixed table width.  Callers check before any pool change, so a
        refused context leaves the pool and its pages as they were."""
        need = blocks_for(n_tokens, self.pool.block_size)
        if need > self.table_width:
            raise ValueError(
                f"worker {self.wid}: a context of {n_tokens} tokens needs "
                f"{need} blocks, past the table width {self.table_width} "
                f"(max_seq_len {self.model.cfg.max_seq_len}, "
                f"{self.pool.num_blocks} blocks in the pool)")

    def enable_tiering(self, tier_cfg: TierConfig = TierConfig()) -> None:
        """Back this stage's pool with host-RAM and SSD tiers (see
        `repro.kvcache.tiers`): preemption swaps through the hierarchy,
        retired prompt blocks are demoted instead of dropped, and
        `adopt_prefix` promotes matching prefixes back for new requests."""
        assert self.paged, "enable_tiering requires enable_paging first"
        self.tier = KVTierManager(self.pool, self.pages, self.cache.streamer,
                                  hw=self.hw, cfg=tier_cfg,
                                  name=f"w{self.wid}")

    @property
    def paged(self) -> bool:
        return self.pool is not None

    def prefill_paged(self, seq: int, x_or_tokens, token_ids=None):
        """Stage prefill for ONE request (batch 1); KV lands in pool blocks.
        `token_ids` enables prefix-sharing of full prompt blocks."""
        self._check()
        x, ks, vs = self._stage(self._prefill, x_or_tokens)
        s = ks.shape[2]
        self.check_table_fits(s)
        _, fresh = self.pool.allocate(seq, s, token_ids=token_ids)
        # shared blocks already hold identical data (same prefix, same
        # weights); rewriting them is a no-op value-wise, so write the window
        # once instead of per-fresh-block bookkeeping
        table, t0, lens = self._tables([seq], [0], [s])
        # the prompt's slots, block by block (`block_window` from token 0)
        pad = window_blocks(s, self.pool.block_size) * self.pool.block_size
        with tracing.span("kvpool.write_window", wid=self.wid, n=1):
            self.pages.write_window(table, t0, {
                leaf: jnp.pad(a, ((0, 0), (0, 0), (0, pad - s), (0, 0),
                                  (0, 0)))
                for leaf, a in (("k", ks), ("v", vs))}, lens)
        self.paged_dirty[seq] = {j for j, _, _, _ in self.pool.block_span(seq)}
        return x, len(fresh)

    def ensure_prefill_table(self, seq: int, plen: int, token_ids=None) -> None:
        """Size `seq`'s block table for the WHOLE prompt before chunked
        prefill: a cold prompt allocates fresh (with `token_ids`, full blocks
        whose prefix hash is live are ref-shared, like `prefill_paged` — but
        fresh blocks are NOT published until their pages are written, see
        `publish_prefix_hashes`); an adopted-prefix table (block-aligned,
        from `adopt_prefix`) is appended out to the full prompt length.
        Raises PoolExhausted (or ValueError past the table width) before
        mutating."""
        self._check()
        self.check_table_fits(plen)
        if seq not in self.pool.tables:
            self.pool.allocate(seq, plen, token_ids=token_ids, publish=False)
            self.paged_dirty.setdefault(seq, set())
            return
        have = self.pool.seq_lens[seq]
        if plen > have:
            cow = self.pool.append(seq, plen - have)
            self.pages.apply_cow(cow)

    def publish_prefix_hashes(self, seq: int, hashes, upto_tokens: int) -> None:
        """Publish the prefix hashes of the prompt blocks whose pages are
        fully WRITTEN (the chunked-prefill cursor has passed them).  Blocks
        beyond the cursor stay unpublished so a concurrent allocate/adopt —
        or an abort-time demotion into the tier prefix cache — can never
        touch unwritten pages."""
        n = min(len(hashes), upto_tokens // self.pool.block_size)
        if n > 0:
            self.pool.publish_hashes(seq, hashes[:n])

    def prefill_chunk_paged(self, seq: int, x_or_tokens, pos0: int):
        """One chunk [pos0, pos0+C) of a paged prefill: the chunk-set pass
        with one sequence.  Requires `ensure_prefill_table` first."""
        return self.prefill_chunk_paged_batch([seq], x_or_tokens, [pos0],
                                              [int(x_or_tokens.shape[1])])

    def decode_paged(self, seq: int, x_or_token, pos: int):
        """One decode step for one sequence: the fused decode pass with one
        sequence."""
        return self.decode_paged_batch([seq], x_or_token, [pos])

    def _append_slots(self, seqs) -> None:
        """One new slot per sequence (CoW where its tail block is shared)."""
        for seq in seqs:
            self.check_table_fits(self.pool.seq_lens[seq] + 1)
        with tracing.span("kvpool.append", wid=self.wid, n=len(seqs)):
            cow = [c for seq in seqs for c in self.pool.append(seq)]
            self.pages.apply_cow(cow)

    def _tables(self, seqs, *per_seq):
        """The pass's block tables ``[B, table_width]`` and its per-sequence
        int32 vectors (positions, chunk lengths), placed on this stage's
        device: the only bytes a paged pass uploads."""
        with tracing.span("worker.gather", wid=self.wid, n=len(seqs),
                          width=self.table_width):
            arrays = (self.pool.padded_tables(seqs, self.table_width),
                      *(np.asarray(v, np.int32) for v in per_seq))
            with tracing.span("worker.upload", wid=self.wid) as sp:
                out = self.put(arrays)
                if sp is not None:
                    sp["bytes"] = sum(a.nbytes for a in arrays)
        return out

    def _write_back(self, seqs, table, pos, k_slots, v_slots, lens, dirty):
        """The blocks the pass wrote into, back into the pages on the
        device; `dirty[i]` lists the logical blocks sequence i wrote."""
        with tracing.span("kvpool.write_window", wid=self.wid, n=len(seqs)):
            self.pages.write_window(table, pos, {"k": k_slots, "v": v_slots},
                                    lens)
            for seq, js in zip(seqs, dirty):
                self.paged_dirty.setdefault(seq, set()).update(js)

    def decode_paged_batch(self, seqs, x_or_tokens, poses):
        """ONE fused pipeline pass: every sequence in `seqs` decodes one step
        at its OWN position.  Appends a slot per sequence (CoW where shared),
        runs the paged stage program over the block tables (it gathers each
        layer's cache from the pages on the device), and writes each
        sequence's new token back into its block.  The cluster pre-flights
        pool capacity for the WHOLE batch first, so the per-sequence appends
        here cannot run out mid-batch."""
        self._check()
        bs = self.pool.block_size
        self._append_slots(seqs)
        table, pos = self._tables(seqs, poses)
        x, k, v = self._stage(self._decode_batch_paged, x_or_tokens,
                              self.pages.k, self.pages.v, table, pos)
        self._write_back(seqs, table, pos, k, v, None,
                         [[p // bs] for p in poses])
        return x

    def prefill_chunk_paged_batch(self, seqs, x_or_tokens, pos0s, q_lens):
        """One fused chunk-set pass: one prefill chunk of EACH sequence runs
        in a single pipeline pass (`stage_prefill_chunk_batch_paged`),
        sequence i's chunk holding ``q_lens[i]`` valid tokens at positions
        ``pos0s[i]..`` and attending over its own resident prefix plus
        itself.  Each sequence's valid rows are written into its own pages;
        padding rows touch no block.  Requires `ensure_prefill_table` for
        every sequence first."""
        self._check()
        bs = self.pool.block_size
        table, pos, lens = self._tables(seqs, pos0s, q_lens)
        x, k, v = self._stage(self._prefill_chunk_batch_paged, x_or_tokens,
                              self.pages.k, self.pages.v, table, pos, lens)
        self._write_back(seqs, table, pos, k, v, lens,
                         [range(p // bs, -(-(p + c) // bs))
                          for p, c in zip(pos0s, q_lens)])
        return x

    def read_blocks(self, want) -> list:
        """Blocks of several sequences off the device in one read: `want`
        is ``[(seq, pos | None), ...]`` — the block holding token `pos`, or
        every live block.  Returns, per item, ``{logical_idx: arrays}``."""
        spans = [[(j, bid, t1 - t0)
                  for j, bid, t0, t1 in self.pool.block_span(seq)
                  if pos is None or j == pos // self.pool.block_size]
                 for seq, pos in want]
        flat = iter(self.pages.read_blocks([(bid, w) for items in spans
                                            for _, bid, w in items]))
        return [{j: next(flat) for j, _, _ in items} for items in spans]

    def live_blocks(self, seq: int) -> Dict[int, Dict[str, np.ndarray]]:
        return self.read_blocks([(seq, None)])[0]

    def install_blocks(self, seq: int, length: int,
                       blocks: Dict[int, Dict[str, np.ndarray]],
                       hashes=None) -> None:
        """(Re)build a sequence's pool entry from streamed blocks (recovery /
        swap-in / disaggregated prompt-KV landing).  With `hashes` (the
        sequence's prompt prefix chain) full prompt blocks already live in
        the pool are ref-shared instead of re-installed, so a recovered pool
        fits everything the failed one held."""
        if seq in self.pool.tables:
            self.pool.free_seq(seq)
        table, fresh = self.pool.allocate(seq, length, hashes=hashes)
        fresh_set = set(fresh)
        self.pages.install_blocks([(bid, blocks[j])
                                   for j, bid in enumerate(table)
                                   if j in blocks and j in fresh_set])
        # shared blocks hold live data too: they must survive an offload
        self.paged_dirty[seq] = set(blocks) | (set(range(len(table)))
                                               - fresh_set)

    def paged_offload(self, seq: int) -> None:
        """Swap a sequence out: only dirty blocks cross the host link, then
        its pool blocks are freed (this is what admits more work).  With
        tiering enabled, the blocks enter the HBM→host→SSD hierarchy as
        write-behind instead of a plain host put."""
        if seq not in self.pool.tables:
            return
        dirty = self.paged_dirty.get(seq, set())
        blocks = {j: arrs for j, arrs in self.live_blocks(seq).items()
                  if j in dirty}
        if self.tier is not None:
            self.tier.swap_out_blocks(seq, blocks)
        else:
            self.cache.swap_out_blocks(seq, blocks)
        self.paged_swapped[seq] = self.pool.seq_lens[seq]
        self.pool.free_seq(seq)
        self.paged_dirty[seq] = set()

    def paged_restore(self, seq: int) -> None:
        if seq in self.pool.tables or seq not in self.paged_swapped:
            return
        length = self.paged_swapped[seq]
        # capacity check BEFORE any state mutation, so a failed restore is
        # retryable (the engine preempts a victim and calls again)
        if self.pool.num_free() < blocks_for(length, self.pool.block_size):
            raise PoolExhausted(
                f"worker {self.wid}: cannot restore seq {seq} "
                f"({blocks_for(length, self.pool.block_size)} blocks needed, "
                f"{self.pool.num_free()} free)")
        del self.paged_swapped[seq]
        blocks = (self.tier.swap_in_blocks(seq) if self.tier is not None
                  else self.cache.swap_in_blocks(seq))
        # clip: the held copy may extend past a rolled-back length
        keep = blocks_for(length, self.pool.block_size)
        self.install_blocks(seq, length,
                            {j: a for j, a in blocks.items() if j < keep})
        self.paged_dirty[seq] = set()

    def free_paged_seq(self, seq: int) -> None:
        """Retire a sequence.  With tiering, its hashed full prompt blocks
        are demoted into the prefix cache (write-behind) before the pool
        frees them — the seed of cross-request prefix reuse."""
        if self.pool is not None and seq in self.pool.tables:
            if self.tier is not None:
                self._demote_prefix_blocks(seq)
            self.pool.free_seq(seq)
        self.paged_swapped.pop(seq, None)
        self.paged_dirty.pop(seq, None)
        if self.tier is not None:
            self.tier.drop_seq(seq)
        self.cache.drop_seq_swap(seq)

    def _demote_prefix_blocks(self, seq: int) -> None:
        bs = self.pool.block_size
        hashed = [(self.pool.blocks[bid].hash, bid)
                  for _, bid, _, _ in self.pool.block_span(seq)]
        hashed = [(h, bid) for h, bid in hashed
                  if h is not None and not self.tier.has_prefix(h)]
        arrays = self.pages.read_blocks([(bid, bs) for _, bid in hashed])
        for (h, _), arr in zip(hashed, arrays):
            self.tier.cache_prefix_block(h, arr)

    # --- cross-request prefix reuse ------------------------------------
    def adoptable_prefix_len(self, hashes) -> int:
        """Longest leading run of prefix-chain hashes this stage can serve
        without prefill compute: live shared pool blocks OR any tier."""
        n = 0
        for h in hashes:
            if self.pool.has_hash(h) or \
                    (self.tier is not None and self.tier.has_prefix(h)):
                n += 1
            else:
                break
        return n

    def pool_prefix_hits(self, hashes) -> int:
        """Leading run servable by ref-sharing live pool blocks alone (these
        cost no free blocks — admission control's headroom discount)."""
        n = 0
        for h in hashes:
            if not self.pool.has_hash(h):
                break
            n += 1
        return n

    def adopt_prefix(self, seq: int, hashes, length: int) -> int:
        """Build `seq`'s prompt prefix from cached blocks: co-resident pool
        blocks are ref-shared; the rest are promoted out of the tier
        hierarchy.  Returns the number of tier-promoted blocks."""
        self._check()
        missing = [h for h in hashes if not self.pool.has_hash(h)]
        if len(missing) > self.pool.num_free():
            raise PoolExhausted(
                f"worker {self.wid}: adopting prefix for seq {seq} needs "
                f"{len(missing)} blocks, {self.pool.num_free()} free")
        fetched = (self.tier.fetch_prefix_chain(missing)
                   if missing and self.tier is not None else {})
        _, fills = self.pool.adopt_prefix(seq, hashes, length)
        self.pages.install_blocks([(bid, fetched[h]) for h, bid in fills])
        # adopted blocks count as dirty: the first offload must persist them
        # for this sequence (tier copies are keyed by hash, not by seq)
        self.paged_dirty[seq] = {j for j in range(len(hashes))}
        return len(fills)
