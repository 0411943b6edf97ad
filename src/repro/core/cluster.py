"""In-process DéjàVu cluster: real pipeline-parallel serving with prompt/token
disaggregation, microbatch swapping, ring replication, failure recovery,
straggler migration, and elastic repartitioning.

Workers are real objects holding real arrays; every byte between them moves
through DéjàVuLib primitives over modeled transports, so tests assert on
actual tokens while benchmarks read the modeled transfer timelines.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import costmodel as cm
from repro.core import telemetry
from repro.core import tracing
from repro.core.controller import Controller
from repro.core.dejavulib import (NetworkTransport, PipelineTopo, StreamEngine,
                                  faults, stream_in, stream_in_blocks,
                                  stream_out, stream_out_blocks)
from repro.core.dejavulib.transport import DEFAULT_HW, HardwareModel
from repro.core.worker import StageWorker
from repro.kvcache.paged import BlockPool, PoolExhausted, blocks_for
from repro.kvcache.tiers import TierConfig


def _stage_ranges(num_layers: int, depth: int) -> List[Tuple[int, int]]:
    assert depth <= num_layers, f"pipeline depth {depth} > {num_layers} layers"
    splits = np.array_split(np.arange(num_layers), depth)
    return [(int(s[0]), int(s[-1]) + 1) for s in splits]


def fused_supported(cfg: ArchConfig) -> bool:
    """Whether the batched fused-round path is EXACT for this config — the
    narrow correctness gate behind `DejaVuCluster.fused_ok`.

    The batched mask/bias path carries full-causal, ALiBi, and
    sliding-window(+meta attention-sink) attention per sequence, so every
    dense/moe config qualifies.  What it cannot express is per-sequence
    state outside the KV cache: ssm/hybrid recurrent state, encdec
    cross-attention, and vlm patch slots (`num_patches` shifts every token's
    cache position by a per-request prefix the batched gather does not
    carry) — those families fall back to the per-sequence oracle path
    cleanly, fused knob or not.  Mirrored by
    `costmodel.fused_round_supported` so planner round terms degrade the
    same way."""
    return cfg.family in ("dense", "moe") and not cfg.num_patches


class DejaVuCluster:
    def __init__(self, cfg: ArchConfig, model, params, n_workers: int, *,
                 mode: str = "colocated", dp_split: Optional[Tuple[int, int]] = None,
                 swapping: bool = False, replication: bool = False,
                 compress_replicas: bool = False,
                 max_resident: int = 2, hw: HardwareModel = DEFAULT_HW,
                 paged: bool = False, kv_block_size: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 tiered: bool = False,
                 host_cache_blocks: Optional[int] = None,
                 ssd_cache_blocks: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 fused_rounds: Optional[bool] = None,
                 devices: Optional[Sequence[jax.Device]] = None):
        assert mode in ("colocated", "disaggregated")
        if mode == "disaggregated":
            assert dp_split is not None and sum(dp_split) == n_workers
        if tiered:
            assert paged, "tiered=True requires paged=True"
        self.cfg = cfg
        self.model = model
        # full weights: the checkpoint store, kept in host memory; each
        # stage holds only its own slice on its device
        self.params = jax.device_get(params)
        # worker `wid` runs on devices[wid % len(devices)]: one stage per
        # chip where there are enough, all on one chip when given one
        self.devices = list(devices) if devices else jax.local_devices()
        self.mode = mode
        self.swapping = swapping
        self.replication = replication
        self.compress_replicas = compress_replicas
        self.max_resident = max_resident
        self.hw = hw
        self.paged = paged
        self.tiered = tiered
        self.tier_cfg = TierConfig(host_capacity_blocks=host_cache_blocks,
                                   ssd_capacity_blocks=ssd_cache_blocks)
        self.kv_block_size = kv_block_size or cfg.kv_block_size
        self.kv_pool_blocks = kv_pool_blocks or cfg.kv_pool_blocks or 512
        self.prefill_chunk_tokens = (cfg.prefill_chunk_tokens
                                     if prefill_chunk_tokens is None
                                     else prefill_chunk_tokens)
        self.fused_rounds = (cfg.fused_rounds if fused_rounds is None
                             else fused_rounds)
        self.streamer = StreamEngine("cluster")
        self.controller = Controller()
        self.net = NetworkTransport(hw)

        if mode == "colocated":
            self.prompt_group = self.token_group = self._build_group(
                n_workers, role="both", wid0=0)
        else:
            dp, dt = dp_split
            self.prompt_group = self._build_group(dp, role="prompt", wid0=0)
            self.token_group = self._build_group(dt, role="token", wid0=dp)
        for w in set(self.prompt_group + self.token_group):
            self.controller.register(w)
            if paged:
                w.enable_paging(self.kv_pool_blocks, self.kv_block_size)
                if tiered:
                    w.enable_tiering(self.tier_cfg)
        self.mb_pos: Dict[int, int] = {}        # current KV length per microbatch
        self.mb_prompt_len: Dict[int, int] = {}
        self.mb_max_len: Dict[int, int] = {}
        self.mb_batch: Dict[int, int] = {}
        # paged (per-sequence) bookkeeping
        self.seq_len: Dict[int, int] = {}       # live tokens per sequence
        self.seq_prompt_len: Dict[int, int] = {}
        self.seq_hashes: Dict[int, List[int]] = {}   # prompt prefix chain
        self.kv_bytes_peak = 0
        # cross-request prefix-reuse accounting (tiered mode)
        self.prefill_tokens_total = 0
        self.prefill_tokens_saved = 0
        self.prefix_hit_blocks = 0
        # chunked-prefill accounting + in-flight (engine-interleaved) state
        self._pending_prefill: Dict[int, dict] = {}
        self.prefill_passes: Dict[int, int] = {}     # rid -> passes last prefill
        self.adoption_suffix_log: List[Tuple[int, int]] = []  # (suffix_toks, passes)
        self.round_prefill_model_s = 0.0   # modeled prefill s this round (engine resets)
        # telemetry clock marks of delivered kills; the engine closes each
        # into a `cluster.recovery_s` observation at the first token emitted
        # after the restore (paper: fail -> first post-restore token)
        self._recovery_marks: List[float] = []

    # ------------------------------------------------------------------
    def live_kv_bytes(self) -> int:
        """Device-resident decode-state bytes right now (dense slots + pages)."""
        total = 0
        for w in set(self.prompt_group + self.token_group):
            if w.paged:
                total += w.pages.used_bytes()
            for slot in w.kv.values():
                total += sum(int(np.prod(a.shape)) * a.dtype.itemsize
                             for a in slot.values())
        return total

    def _track_kv_peak(self) -> None:
        self.kv_bytes_peak = max(self.kv_bytes_peak, self.live_kv_bytes())

    # ------------------------------------------------------------------
    def _build_group(self, depth: int, role: str, wid0: int) -> List[StageWorker]:
        ranges = _stage_ranges(self.cfg.num_layers, depth)
        ws = []
        for i, (lo, hi) in enumerate(ranges):
            ws.append(StageWorker(wid0 + i, self.model, self.params, lo, hi,
                                  first=(i == 0), last=(i == len(ranges) - 1),
                                  role=role, hw=self.hw, streamer=self.streamer,
                                  compress_replicas=self.compress_replicas,
                                  device=self._device_for(wid0 + i)))
        return ws

    def _device_for(self, wid: int) -> jax.Device:
        return self.devices[wid % len(self.devices)]

    def _topo(self, group: List[StageWorker]) -> PipelineTopo:
        return PipelineTopo(depth=len(group), num_layers=self.cfg.num_layers,
                            microbatch=0)

    # ------------------------------------------------------------------
    # serving primitives
    # ------------------------------------------------------------------
    def prefill_mb(self, mb: int, tokens: jnp.ndarray, max_new: int) -> jnp.ndarray:
        """Prefill a microbatch through the prompt pipeline; in disaggregated
        mode, stream its prompt KV to the token pipeline (paper §4.2.1)."""
        b, plen = tokens.shape
        # cache length aligned to the kv_pack DMA token block (8)
        max_len = -(-(plen + max_new) // 8) * 8
        self.mb_batch[mb] = b
        self.mb_pos[mb] = plen
        self.mb_prompt_len[mb] = plen
        self.mb_max_len[mb] = max_len
        with telemetry.span("pass", kind="mb_prefill"), \
                tracing.span("pass", kind="mb_prefill", mb=mb, batch=b):
            x = tokens
            for w in self.prompt_group:
                x = w.prefill(mb, w.put(x), max_len)
            telemetry.advance(cm.stage_prompt_time(
                self.cfg, cm.WorkloadSpec(prompt_len=plen, new_tokens=1,
                                          microbatch=b),
                self.cfg.num_layers, 8, self.hw))
        logits = x
        if self.mode == "disaggregated":
            self._stream_prompt_kv(mb, plen)
        if self.replication:
            self._replicate(mb, (0, plen), step=0, group=self.token_group)
        if self.swapping:
            for w in self.token_group:
                if mb in w.kv:
                    w.offload(mb)           # full first offload to host
        self._track_kv_peak()
        return logits

    def _stream_prompt_kv(self, mb: int, plen: int) -> None:
        bsz = self.mb_batch[mb]
        topo_p = PipelineTopo(len(self.prompt_group), self.cfg.num_layers, bsz)
        topo_t = PipelineTopo(len(self.token_group), self.cfg.num_layers, bsz)
        dst_stores = {i: w.cache.host for i, w in enumerate(self.token_group)}
        for si, w in enumerate(self.prompt_group):
            kv = w.kv.pop(mb)
            state = {"kv": {k: np.asarray(v) for k, v in kv.items()}}
            mbk = f"{mb}"
            stream_out(state, si, topo_p, topo_t, dst_stores, self.net,
                       mb=mbk, token_range=(0, plen))
        # token side: merge chunks into local caches sized max_len
        b = None
        for di, w in enumerate(self.token_group):
            lo, hi = topo_t.layer_range(di)
            hkv, dh = self.cfg.num_kv_heads, self.cfg.resolved_head_dim
            # batch size from any incoming chunk
            some_key = next(k for k in w.cache.host.keys() if k.startswith(f"mb{mb}/kv/"))
            b = w.cache.host.get(some_key).shape[1]
            shapes = {"kv": {"k": ((hi - lo, b, self.mb_max_len[mb], hkv, dh), self.cfg.dtype),
                             "v": ((hi - lo, b, self.mb_max_len[mb], hkv, dh), self.cfg.dtype)}}
            local = stream_in(w.cache.host, di, topo_t, topo_p, shapes, self.net,
                              mb=f"{mb}", token_range=(0, plen))
            w.install_kv(mb, local["kv"])
            for key in [k for k in w.cache.host.keys() if k.startswith(f"mb{mb}/")]:
                w.cache.host.delete(key)

    def decode_mb(self, mb: int, token: jnp.ndarray, step: int) -> jnp.ndarray:
        """One decode step through the token pipeline.  Returns logits [B,V].
        `step` is 1-based (step i consumes token_{i-1})."""
        pos = self.mb_pos[mb]
        with telemetry.span("pass", kind="mb_decode"), \
                tracing.span("pass", kind="mb_decode", mb=mb, step=step):
            if self.swapping:
                for w in self.token_group:
                    w.restore(mb)
            x = token
            for w in self.token_group:
                x = w.decode(mb, w.put(x), pos)
            telemetry.advance(cm.stage_token_time(
                self.cfg, cm.WorkloadSpec(prompt_len=max(pos, 1), new_tokens=1,
                                          microbatch=self.mb_batch.get(mb, 1)),
                self.cfg.num_layers, 8, pos + 1, self.hw))
        self.mb_pos[mb] = pos + 1
        if self.replication:
            self._replicate(mb, (pos, pos + 1), step=step, group=self.token_group)
        if self.swapping:
            for w in self.token_group:
                w.offload(mb, token_range=(pos, pos + 1))
        for w in set(self.prompt_group + self.token_group):
            w.heartbeat()
        self._track_kv_peak()
        return x

    # ------------------------------------------------------------------
    # paged serving primitives (continuous batching; KV moves per BLOCK)
    # ------------------------------------------------------------------
    @property
    def fused_ok(self) -> bool:
        """Fused batched rounds run whenever the knob is on, the cluster is
        paged, and `fused_supported` says the batched mask/bias path is
        exact for the family — ALiBi (bloom) and sliding-window+meta (hymba
        -style dense mixes) included.  Unsupported families (ssm / hybrid /
        encdec / vlm) fall back to the per-sequence oracle path cleanly."""
        return self.fused_rounds and self.paged and fused_supported(self.cfg)

    def can_admit(self, prompt_len: int, n_active: int,
                  token_ids: Optional[np.ndarray] = None) -> bool:
        """Admission control: every token-side pool must fit the prompt plus
        one headroom block per already-running sequence (each may need a new
        block before this request finishes its first step).

        With tiering, `token_ids` lets admission count cached capacity: full
        prompt blocks whose prefix hash is live in a pool will be ref-shared,
        not allocated, so they need no free blocks — in BOTH serving modes
        (the prompt side adopts prefixes during prefill; the token side
        re-shares them when the streamed blocks install).  The chain is
        capped one block short of the prompt — at least one suffix token must
        run through compute — so a boundary-aligned prompt's last full block
        is never discounted.  Tier-backed blocks still promote INTO free
        blocks and are not discounted.  In disaggregated mode the prompt-side
        pools are checked too (they hold the prompt only until its blocks
        stream out, so no per-active headroom there)."""
        bs = self.kv_block_size
        need = blocks_for(prompt_len + 1, bs) + n_active
        hashes: List[int] = []
        if token_ids is not None and self.tiered:
            hashes = BlockPool.chain_hashes(
                [int(t) for t in token_ids], bs)[:(prompt_len - 1) // bs]

        def fits(w: StageWorker, want: int) -> bool:
            hits = w.pool_prefix_hits(hashes) if hashes else 0
            return w.pool.num_free() >= want - hits

        if not all(fits(w, need) for w in self.token_group):
            return False
        if self.mode == "disaggregated":
            pneed = blocks_for(prompt_len, bs)
            return all(fits(w, pneed) for w in self.prompt_group)
        return True

    def prefill_seq(self, rid: int, prompt: np.ndarray, max_new: int) -> jnp.ndarray:
        """Prefill ONE request through the prompt pipeline into pool blocks,
        running every pipeline pass back-to-back (the engine's interleaved
        scheduler calls `prefill_seq_begin`/`prefill_seq_step` itself so
        decode steps can run between chunks)."""
        self.prefill_seq_begin(rid, prompt, max_new)
        logits = None
        while logits is None:
            logits = self.prefill_seq_step(rid)
        return logits

    def _chunkable(self) -> bool:
        """Chunked prefill is exact only where the decode path is (same
        restriction as prefix adoption): dense/moe attention — the chunk
        mask carries windows, meta sinks, and ALiBi per sequence; only vlm
        patch slots (a per-request position prefix) are out."""
        return (self.prefill_chunk_tokens > 0
                and self.cfg.family in ("dense", "moe")
                and not self.cfg.num_patches)

    def check_context_fits(self, n_tokens: int) -> None:
        """Raise ValueError if a context of `n_tokens` text tokens would
        outgrow any stage's fixed block-table width."""
        for w in set(self.prompt_group + self.token_group):
            w.check_table_fits(n_tokens + self.cfg.context_overhead)

    def prefill_seq_begin(self, rid: int, prompt: np.ndarray,
                          max_new: int) -> None:
        """Stage a prefill for `prefill_seq_step` to advance pass by pass.
        With tiering, the prompt's prefix-chain hashes are first matched
        against live pool blocks AND the host/SSD tiers of every prompt-side
        stage; a matching prefix is adopted (streamed back up the hierarchy)
        and only the remaining suffix runs through compute — chunked,
        `prefill_chunk_tokens` Q tokens per pass (vs one pass per suffix
        token with the knob at 0, the oracle path property tests compare
        against).  Cold prompts longer than the chunk are split the same way
        so the scheduler can interleave decodes between passes."""
        assert self.paged, "prefill_seq requires paged=True"
        plen = int(prompt.shape[0])
        self.check_context_fits(plen)    # refuse before any state changes
        self.seq_prompt_len[rid] = plen
        self.seq_len[rid] = plen
        token_ids = [int(t) for t in prompt]
        self.seq_hashes[rid] = BlockPool.chain_hashes(token_ids,
                                                      self.kv_block_size)
        for w in self.prompt_group:      # re-prefill after rollback-to-0
            if rid in w.pool.tables:
                w.free_paged_seq(rid)
        self.prefill_tokens_total += plen
        ck = self.prefill_chunk_tokens
        khashes = self._adoptable_prefix(token_ids)
        if self.tiered:
            candidates = (plen - 1) // self.kv_block_size
            telemetry.count("tier.prefix_hit_blocks", len(khashes))
            telemetry.count("tier.prefix_miss_blocks",
                            candidates - len(khashes))
        st = {"prompt": np.asarray(prompt, np.int32), "plen": plen,
              "start": 0, "pos": 0, "passes": 0, "x": None}
        if khashes:
            start = len(khashes) * self.kv_block_size
            for w in self.prompt_group:
                w.adopt_prefix(rid, khashes, start)
            self.prefix_hit_blocks += len(khashes)
            self.prefill_tokens_saved += start
            st["start"] = st["pos"] = start
            if ck > 0:
                st["mode"] = "chunk"
                for w in self.prompt_group:
                    w.ensure_prefill_table(rid, plen)
            else:
                st["mode"] = "token"
        elif self._chunkable() and (plen > ck or self.fused_ok):
            # fused rounds force chunk mode even for short cold prompts so
            # every in-flight prefill can pack into the round's chunk-set pass
            st["mode"] = "chunk"
            for w in self.prompt_group:
                w.ensure_prefill_table(rid, plen, token_ids=token_ids)
        else:
            st["mode"] = "batch"
        self._pending_prefill[rid] = st

    def prefill_seq_step(self, rid: int) -> Optional[jnp.ndarray]:
        """Run ONE pipeline pass of a staged prefill: the whole prompt
        (batch mode), one `prefill_chunk_tokens` chunk attending over the
        pool-resident prefix, or one suffix token through the decode path.
        Returns the prefill logits once the prompt is fully processed (and
        the post-prefill block streaming/replication/swap have run), else
        None — the engine interleaves decode steps between calls."""
        st = self._pending_prefill[rid]
        plen, pos = st["plen"], st["pos"]
        with telemetry.span("pass", kind=f"prefill_{st['mode']}"), \
                tracing.span("pass", rid=rid, kind=f"prefill_{st['mode']}",
                             pos=pos, plen=plen):
            if st["mode"] == "batch":
                x = st["prompt"][None]
                for w in self.prompt_group:
                    x, _ = w.prefill_paged(
                        rid, w.put(x), token_ids=[int(t) for t in st["prompt"]])
                n_q = plen
            elif st["mode"] == "chunk":
                c = min(self.prefill_chunk_tokens, plen - pos)
                x = st["prompt"][None, pos:pos + c]
                for w in self.prompt_group:
                    x = w.prefill_chunk_paged(rid, w.put(x), pos)
                n_q = c
            else:                        # token-at-a-time oracle path
                x = st["prompt"][pos:pos + 1]
                for w in self.prompt_group:
                    x = w.decode_paged(rid, w.put(x), pos)
                n_q = 1
            st["x"] = x
            self._after_prefill_pass(rid, st, n_q)
        if st["pos"] < plen:
            return None
        return self._finish_prefill(rid)

    def _after_prefill_pass(self, rid: int, st: dict, n_q: int) -> None:
        """Per-pass bookkeeping shared by the per-sequence and fused chunk
        paths: advance the cursor, publish the prefix hashes of the blocks
        whose pages the cursor just completed (cold chunked prefills only —
        adopted suffixes and the batched path publish elsewhere, see
        `publish_prefix_hashes`), and charge the modeled pass time."""
        st["pos"] += n_q
        st["passes"] += 1
        if st["mode"] == "chunk" and st["start"] == 0:
            for w in self.prompt_group:
                w.publish_prefix_hashes(rid, self.seq_hashes[rid], st["pos"])
        t = cm.chunked_prefill_pass_time(
            self.cfg, n_q, st["pos"], self.cfg.num_layers, 8, self.hw)
        self.round_prefill_model_s += t
        telemetry.advance(t)

    def _finish_prefill(self, rid: int) -> jnp.ndarray:
        st = self._pending_prefill.pop(rid)
        plen, start = st["plen"], st["start"]
        self.prefill_passes[rid] = st["passes"]
        if start > 0:
            self.adoption_suffix_log.append((plen - start, st["passes"]))
            self._register_compute(plen - start, plen)
        if self.mode == "disaggregated":
            self._stream_prompt_blocks(rid, plen)
        if self.replication:
            self._replicate_seqs([(rid, 0, None)])
        if self.swapping:
            for w in self.token_group:
                w.paged_offload(rid)
        self._track_kv_peak()
        return st["x"]

    def prefill_pending(self, rid: int) -> bool:
        return rid in self._pending_prefill

    def prefill_mode(self, rid: int) -> Optional[str]:
        """'chunk' | 'batch' | 'token' for a staged prefill, else None —
        the engine packs only chunk-mode prefills into a fused pass."""
        st = self._pending_prefill.get(rid)
        return None if st is None else st["mode"]

    def abort_prefill(self, rid: int) -> None:
        """Drop an in-flight prefill (e.g. a worker died mid-chunk and took
        the partial tables with it); the engine re-begins from scratch."""
        self._pending_prefill.pop(rid, None)
        for w in self.prompt_group:
            if rid in w.pool.tables:
                w.free_paged_seq(rid)

    def _adoptable_prefix(self, token_ids: List[int]) -> List[int]:
        """Prefix-chain hashes (full blocks) every prompt-side stage can
        serve from cache.  Capped so at least one suffix token runs through
        compute (the prefill logits must come from somewhere)."""
        if not self.tiered or self.cfg.family not in ("dense", "moe") \
                or self.cfg.num_patches:
            return []
        bs = self.kv_block_size
        hashes = BlockPool.chain_hashes(token_ids, bs)
        hashes = hashes[:(len(token_ids) - 1) // bs]
        if not hashes:
            return []
        k = min(w.adoptable_prefix_len(hashes) for w in self.prompt_group)
        return hashes[:k]

    def _register_compute(self, n_tokens: int, ctx: int) -> None:
        """Report modeled compute time to the streamer so its overlap report
        can say how much tier write-behind was hidden behind it."""
        if not self.tiered or n_tokens <= 0:
            return
        wl = cm.WorkloadSpec(prompt_len=max(ctx, 1), new_tokens=1, microbatch=1)
        t = cm.stage_token_time(self.cfg, wl, self.cfg.num_layers, 8,
                                max(ctx, 1), self.hw)
        self.streamer.compute_span(t * n_tokens)

    def _stream_prompt_blocks(self, rid: int, plen: int) -> None:
        topo_p = PipelineTopo(len(self.prompt_group), self.cfg.num_layers, 1)
        topo_t = PipelineTopo(len(self.token_group), self.cfg.num_layers, 1)
        dst_stores = {i: w.cache.host for i, w in enumerate(self.token_group)}
        for si, w in enumerate(self.prompt_group):
            stream_out_blocks(w.live_blocks(rid), si, topo_p, topo_t,
                              dst_stores, self.net, seq=rid)
            w.free_paged_seq(rid)
        for di, w in enumerate(self.token_group):
            blocks = stream_in_blocks(w.cache.host, di, topo_t, topo_p,
                                      self.net, seq=rid)
            # re-share full prompt blocks already live in the token-side pool
            # (same cap as `can_admit`'s discount, which counts on this)
            w.install_blocks(rid, plen, blocks,
                             hashes=self.seq_hashes.get(rid, [])[
                                 :(plen - 1) // self.kv_block_size])

    def decode_seq(self, rid: int, token: jnp.ndarray, step: int) -> jnp.ndarray:
        """One decode step for one sequence through the token pipeline.
        Raises PoolExhausted BEFORE mutating any pool, so the engine can
        preempt a victim and retry (ValueError, as early, past the fixed
        table width)."""
        pos = self.seq_len[rid]
        with telemetry.span("pass", kind="perseq_decode"), \
                tracing.span("pass", rid=rid, seq=step, kind="perseq_decode"):
            if self.swapping:
                for w in self.token_group:
                    w.paged_restore(rid)
            for w in self.token_group:
                w.check_table_fits(w.pool.seq_lens[rid] + 1)
                if w.pool.append_needs_block(rid) and w.pool.num_free() == 0:
                    raise PoolExhausted(
                        f"worker {w.wid} pool full (seq {rid})")
            x = token
            for w in self.token_group:
                x = w.decode_paged(rid, w.put(x), pos)
            self.seq_len[rid] = pos + 1
            self._register_compute(1, pos + 1)
            telemetry.advance(cm.stage_token_time(
                self.cfg, cm.WorkloadSpec(prompt_len=max(pos, 1),
                                          new_tokens=1, microbatch=1),
                self.cfg.num_layers, 8, pos + 1, self.hw))
        if self.replication:
            self._replicate_seqs([(rid, step, pos)])
        if self.swapping:
            for w in self.token_group:
                w.paged_offload(rid)
        for w in set(self.prompt_group + self.token_group):
            w.heartbeat()
        self._track_kv_peak()
        return x

    def decode_batch(self, rids: List[int], tokens,
                     steps: List[int]) -> jnp.ndarray:
        """ONE pipeline pass that decodes EVERY sequence in `rids` one step
        (fused rounds) — ragged per-sequence lengths over per-sequence block
        tables, vs `decode_seq`'s one pass per sequence.

        Per-sequence semantics are preserved exactly: capacity and the fixed
        table width are pre-flighted across the WHOLE batch so PoolExhausted
        (the engine preempts a victim and retries) or ValueError (a context
        past the width) raises before any pool mutates; replication still
        pushes each sequence's touched block with its own step; swap restores
        / offloads every sequence around the pass; and a worker death
        mid-pass surfaces as RuntimeError for the engine's detect-and-recover,
        which rolls every sequence back exactly like the per-sequence path.

        tokens: [B] int32 (each sequence's last sampled token); steps:
        per-sequence 1-based decode step.  Returns logits [B,V]."""
        poses = [self.seq_len[rid] for rid in rids]
        # the trace's pass covers the pass's replication too, as the time
        # between the step and its sampling does
        with tracing.span("pass", kind="fused_decode", rids=rids):
            with telemetry.span("pass", kind="fused_decode"):
                if self.swapping:
                    for w in self.token_group:
                        for rid in rids:
                            w.paged_restore(rid)
                for w in self.token_group:
                    for rid in rids:
                        w.check_table_fits(w.pool.seq_lens[rid] + 1)
                    need = sum(1 for rid in rids
                               if w.pool.append_needs_block(rid))
                    if need > w.pool.num_free():
                        raise PoolExhausted(
                            f"worker {w.wid} pool cannot absorb a fused "
                            f"round of {len(rids)} appends ({need} needed, "
                            f"{w.pool.num_free()} free)")
                x = np.asarray(tokens, np.int32)
                for w in self.token_group:
                    x = w.decode_paged_batch(rids, w.put(x), poses)
                for rid, pos in zip(rids, poses):
                    self.seq_len[rid] = pos + 1
                    self._register_compute(1, pos + 1)
                ctx = max(1, (sum(poses) + len(poses)) // max(len(poses), 1))
                telemetry.advance(cm.decode_round_time(
                    self.cfg, len(rids), ctx, self.cfg.num_layers, 8,
                    self.hw, fused=True))
            if self.replication:
                self._replicate_seqs(list(zip(rids, steps, poses)))
            if self.swapping:
                for w in self.token_group:
                    for rid in rids:
                        w.paged_offload(rid)
            for w in set(self.prompt_group + self.token_group):
                w.heartbeat()
            self._track_kv_peak()
        return x

    def prefill_chunkset_pass(self, rids: List[int]
                              ) -> Dict[int, Optional[jnp.ndarray]]:
        """Advance the staged chunk-mode prefills of ALL `rids` by one chunk
        each in ONE pipeline pass through the prompt group — the fused
        analogue of calling `prefill_seq_step` once per sequence.  Ragged
        chunk lengths (a prompt's final chunk may be short) are padded to the
        set's longest and masked inside the pass.  Returns {rid:
        prefill_logits | None}; a completed prompt runs the same post-prefill
        streaming / replication / swap as the per-sequence path."""
        with telemetry.span("pass", kind="chunkset"), \
                tracing.span("pass", kind="chunkset", rids=rids):
            return self._prefill_chunkset_pass(rids)

    def _prefill_chunkset_pass(self, rids: List[int]
                               ) -> Dict[int, Optional[jnp.ndarray]]:
        sts = [self._pending_prefill[r] for r in rids]
        assert all(st["mode"] == "chunk" for st in sts), \
            "prefill_chunkset_pass packs chunk-mode prefills only"
        ck = self.prefill_chunk_tokens
        cs = [min(ck, st["plen"] - st["pos"]) for st in sts]
        cmax = max(cs)
        toks = np.zeros((len(rids), cmax), np.int32)
        for i, st in enumerate(sts):
            toks[i, :cs[i]] = st["prompt"][st["pos"]:st["pos"] + cs[i]]
        pos0s = [st["pos"] for st in sts]
        x = toks
        for w in self.prompt_group:
            x = w.prefill_chunk_paged_batch(rids, w.put(x), pos0s, cs)
        out: Dict[int, Optional[jnp.ndarray]] = {}
        for i, (rid, st) in enumerate(zip(rids, sts)):
            self._after_prefill_pass(rid, st, cs[i])
            if st["pos"] < st["plen"]:
                out[rid] = None
            else:
                st["x"] = x[i:i + 1]
                out[rid] = self._finish_prefill(rid)
        return out

    def _replicate_seqs(self, items: List[Tuple[int, int, Optional[int]]]
                        ) -> None:
        """Ring-replicate at BLOCK granularity, as one
        ``dejavulib.replicate`` span with the bytes pushed: for each (rid,
        step, pos), a prefill (pos None) pushes every live block, a decode
        step only the block it touched.  Each stage reads the blocks of
        every item off its device at once.  Waits for each item's
        streams."""
        group = self.token_group
        n = len(group)
        with tracing.span("dejavulib.replicate", n=len(items)) as sp:
            reads = []
            for w in group:
                live = [(rid, pos) for rid, _, pos in items
                        if rid in w.pool.tables]
                reads.append(dict(zip([rid for rid, _ in live],
                                      w.read_blocks(live))))
            pushed = 0
            for rid, step, _ in items:
                for i, w in enumerate(group):
                    if rid not in reads[i]:
                        continue
                    peer = group[(i + 1) % n]
                    for j, arrays in reads[i][rid].items():
                        pushed += w.cache.replicate_block_to(
                            peer.cache, rid, j, arrays, step,
                            self.controller.ack_replication)
                self.streamer.drain()
            if sp is not None:
                sp["bytes"] = pushed

    def preempt_seq(self, rid: int) -> None:
        """Swap a running sequence fully out (block-granular) to free pool
        space for another request; `resume_seq` brings it back.  Offload is
        a no-op on workers where the sequence is already swapped out."""
        for w in self.token_group:
            w.paged_offload(rid)

    def resident_blocks(self, rid: int) -> int:
        """Device-resident blocks a preemption of `rid` would free."""
        return sum(len(w.pool.tables.get(rid, ())) for w in self.token_group)

    def can_resume(self, rid: int, n_active: int) -> bool:
        need = blocks_for(self.seq_len[rid] + 1, self.kv_block_size) + n_active
        return all(w.pool.num_free() >= need for w in self.token_group)

    def resume_seq(self, rid: int) -> None:
        for w in self.token_group:
            w.paged_restore(rid)

    def free_seq(self, rid: int) -> None:
        """Retire a finished sequence: blocks return to the pool immediately
        (this is what lets the engine admit queued work every step)."""
        for w in set(self.prompt_group + self.token_group):
            w.free_paged_seq(rid)
            for key in [k for k in w.cache.replica.keys()
                        if f"/seq{rid}/" in k]:
                w.cache.replica.delete(key)
        self.seq_len.pop(rid, None)
        self.seq_prompt_len.pop(rid, None)
        self.seq_hashes.pop(rid, None)
        self._pending_prefill.pop(rid, None)

    def pool_stats(self) -> Dict[str, int]:
        used = max((w.pool.num_used() for w in self.token_group), default=0)
        peak = max((w.pool.peak_used_blocks for w in self.token_group), default=0)
        return {"used_blocks": used, "peak_blocks": peak,
                "peak_kv_bytes": self.kv_bytes_peak}

    def tier_stats(self) -> Dict[str, float]:
        """Aggregate the per-stage tier-manager counters plus the cluster's
        prefix-reuse tallies (empty unless tiered=True)."""
        agg: Dict[str, float] = {}
        for w in set(self.prompt_group + self.token_group):
            if getattr(w, "tier", None) is None:
                continue
            for k, v in w.tier.stats().items():
                agg[k] = agg.get(k, 0) + v
        if agg or self.tiered:
            agg["prefill_tokens_total"] = self.prefill_tokens_total
            agg["prefill_tokens_saved"] = self.prefill_tokens_saved
            agg["prefix_hit_blocks"] = self.prefix_hit_blocks
        return agg

    def _replicate(self, mb: int, token_range, step: int,
                   group: List[StageWorker]) -> None:
        n = len(group)
        for i, w in enumerate(group):
            if mb not in w.kv and not self.swapping:
                continue
            kv = w.kv.get(mb)
            if kv is None:      # swapped out: replicate from host copy
                kv = w.put({leaf: w.cache.host.get(f"swap/mb{mb}/{leaf}")
                            for leaf in ("k", "v")})
            peer = group[(i + 1) % n]
            w.cache.replicate_to(peer.cache, mb, kv, token_range, step,
                                 self.controller.ack_replication)
        self.streamer.drain()

    # ------------------------------------------------------------------
    # failure handling (paper §4.2.3) + straggler migration
    # ------------------------------------------------------------------
    def inject_failure(self, wid: int) -> None:
        # observability point only — lets a recorded trace (and fault_trace
        # assertions) show every delivered kill, whatever path requested it
        faults.fire("cluster.fail", tag=f"w{wid}")
        tracing.event("cluster.kill", wid=wid)
        t = telemetry.current()
        if t is not None:
            # mark the modeled clock; the engine closes the mark into a
            # `cluster.recovery_s` observation at the first post-restore token
            self._recovery_marks.append(t.clock_s)
            t.count("cluster.failures", 1)
        for w in set(self.prompt_group + self.token_group):
            if w.wid == wid:
                w.kill()
                self.controller.log_event("failure", wid=wid)
                return
        raise KeyError(wid)

    def take_recovery_marks(self) -> List[float]:
        """Drain the pending failure clock marks (see `inject_failure`)."""
        marks, self._recovery_marks = self._recovery_marks, []
        return marks

    def detect_and_recover(self, active_mbs: List[int]) -> Dict[int, int]:
        """Controller-driven recovery.  Returns {mb: resume_step} (empty if
        no failure)."""
        dead = self.controller.check_failures()
        resume: Dict[int, int] = {}
        for wid in dead:
            with tracing.span("recovery", wid=wid):
                resume.update(self._recover_worker(wid, active_mbs))
        return resume

    def _recover_worker(self, wid: int, active_mbs: List[int]) -> Dict[int, int]:
        if (self.mode == "disaggregated"
                and any(w.wid == wid for w in self.prompt_group)):
            # prompt workers hold no cross-microbatch state: rebuild in place
            idx = next(i for i, w in enumerate(self.prompt_group) if w.wid == wid)
            ranges = _stage_ranges(self.cfg.num_layers, len(self.prompt_group))
            lo, hi = ranges[idx]
            old = self.prompt_group[idx]
            neww = StageWorker(wid, self.model, self.params, lo, hi,
                               first=old.first, last=old.last, role=old.role,
                               hw=self.hw, streamer=self.streamer,
                               device=old.device)
            if self.paged:
                neww.enable_paging(self.kv_pool_blocks, self.kv_block_size)
                if self.tiered:
                    neww.enable_tiering(self.tier_cfg)
            self.prompt_group[idx] = neww
            self.controller.workers = [neww if w.wid == wid else w
                                       for w in self.controller.workers]
            self.controller.log_event("recovery", wid=wid, resume={})
            return {}
        group = self.token_group
        idx = next(i for i, w in enumerate(group) if w.wid == wid)
        n = len(group)
        old = group[idx]
        ranges = _stage_ranges(self.cfg.num_layers, n)
        lo, hi = ranges[idx]
        # fresh worker: weights re-sliced from the checkpointed full params
        neww = StageWorker(wid, self.model, self.params, lo, hi,
                           first=old.first, last=old.last, role=old.role,
                           hw=self.hw, streamer=self.streamer,
                           compress_replicas=self.compress_replicas,
                           device=old.device)
        group[idx] = neww
        self.controller.workers = [neww if w.wid == wid else w
                                   for w in self.controller.workers]
        succ = group[(idx + 1) % n]
        pred = group[(idx - 1) % n]
        if self.paged:
            return self._recover_worker_paged(wid, old, neww, succ, pred,
                                              active_mbs)
        # step 1: successor returns the failed worker's replica
        for mb in active_mbs:
            arrays = {}
            for leaf in ("k", "v"):
                key = f"w{wid}/mb{mb}/{leaf}"
                if key in succ.cache.replica:
                    arrays[leaf] = succ.cache.replica.get(key)
            if arrays:
                neww.install_kv(mb, arrays)
                if self.swapping:   # rebuild host copy too
                    neww.cache.swap_out(mb, neww.kv[mb])
        # step 2: predecessor re-replicates its own KV to the new worker
        for mb in active_mbs:
            kv = pred.kv.get(mb)
            if kv is None and pred.cache.host_has(mb):
                kv = pred.put({leaf: pred.cache.host.get(f"swap/mb{mb}/{leaf}")
                               for leaf in ("k", "v")})
            if kv is not None:
                pred.cache.replicate_to(neww.cache, mb, kv,
                                        (0, self.mb_pos[mb]),
                                        self.controller.replicated_step(pred.wid, mb),
                                        self.controller.ack_replication)
        self.streamer.drain()
        # step 3: resume point per microbatch
        resume = self.controller.resume_point(wid, active_mbs)
        # roll back cache positions; step i writes at prompt_len + i - 1
        for mb, r in resume.items():
            self.mb_pos[mb] = self.mb_prompt_len[mb] + max(r - 1, 0)
        self.controller.log_event("recovery", wid=wid, resume=dict(resume))
        return resume

    def _recover_worker_paged(self, wid: int, old: StageWorker,
                              neww: StageWorker,
                              succ: StageWorker, pred: StageWorker,
                              active: List[int]) -> Dict[int, int]:
        """Paged 4-step recovery: only LIVE blocks move.  Each sequence is
        restored from the LOWEST tier holding a replica: the dead worker's
        persistent SSD tier first (it survives the machine), else the
        successor's replica-ring blocks; the predecessor then re-streams its
        own blocks, and every sequence rolls back to its last fully
        replicated step."""
        neww.enable_paging(self.kv_pool_blocks, self.kv_block_size)
        if self.tiered:
            # the dead machine's disk outlives it: point the fresh worker's
            # tier manager at the same root and rebuild the index from the
            # self-describing keys (prefix cache + spilled swap blocks)
            root = old.tier.ssd.root if old.tier is not None else None
            neww.enable_tiering(dataclasses.replace(self.tier_cfg,
                                                    ssd_root=root))
            self.streamer.drain()         # pending write-behinds land first
            neww.tier.reattach()
        bs = self.kv_block_size
        # step 1: restore each sequence from the lowest tier holding it —
        # the reattached SSD tier, else the successor's replica blocks
        for rid in active:
            rep = self.controller.replicated_step(wid, rid)
            if rep < 0:
                continue            # nothing replicated: engine re-prefills
            avail = self.seq_prompt_len[rid] + max(rep, 0)
            keep = blocks_for(avail, bs)
            blocks = None
            # the SSD copy is only authoritative if the sequence really was
            # swapped out at (at least) the resume length — the peers'
            # symmetric swap state is the witness for the dead worker's
            if self.tiered and neww.tier is not None and \
                    pred.paged_swapped.get(rid, -1) >= avail:
                blocks = neww.tier.restore_swap_from_ssd(rid, keep)
            if blocks is None:
                blocks = {j: a
                          for j, a in succ.cache.replica_blocks(wid, rid).items()
                          if j < keep}
            # re-share fully-restored prompt blocks with co-resident
            # sequences — a pool that only fit its load through prefix
            # sharing must recover through prefix sharing too
            neww.install_blocks(rid, avail, blocks,
                                hashes=self.seq_hashes.get(rid, [])[:avail // bs])
            # a swapped/preempted sequence goes back to host on the fresh
            # worker too, so recovery leaves residency exactly as it found it
            if self.swapping or rid in pred.paged_swapped:
                neww.paged_offload(rid)
        # step 2: predecessor re-replicates its own live blocks; a swapped or
        # preempted sequence is brought back for the send, then re-offloaded
        # so pool occupancy is unchanged by recovery
        for rid in active:
            was_swapped = rid in pred.paged_swapped
            pred.paged_restore(rid)
            if rid not in pred.pool.tables:
                continue
            step = self.controller.replicated_step(pred.wid, rid)
            for j, arrays in pred.live_blocks(rid).items():
                pred.cache.replicate_block_to(neww.cache, rid, j, arrays, step,
                                              self.controller.ack_replication)
            if was_swapped:
                pred.paged_offload(rid)
        self.streamer.drain()
        # steps 3+4: resume point per sequence; roll every pool back to it
        resume = self.controller.resume_point(wid, active)
        for rid, r in resume.items():
            new_len = self.seq_prompt_len[rid] + max(r - 1, 0) if r > 0 else 0
            self.seq_len[rid] = new_len
            for w in self.token_group:
                if rid in w.pool.tables:
                    if new_len > 0:
                        w.pool.truncate(rid, new_len)
                    else:
                        w.free_paged_seq(rid)
                if rid in w.paged_swapped:
                    w.paged_swapped[rid] = min(w.paged_swapped[rid], new_len)
        self.controller.log_event("recovery", wid=wid, resume=dict(resume))
        return resume

    def migrate_worker(self, wid: int, active_mbs: List[int]) -> Dict[int, int]:
        """Straggler mitigation: proactively move a slow stage to a fresh
        worker using the replication ring (beyond-paper, same machinery)."""
        self.controller.log_event("migrate", wid=wid)
        self.inject_failure(wid)
        return self.detect_and_recover(active_mbs)

    # ------------------------------------------------------------------
    # elastic repartitioning (beyond-paper)
    # ------------------------------------------------------------------
    def repartition(self, new_depth: int, active_mbs: List[int]) -> None:
        """Re-split the token pipeline to `new_depth` stages, migrating all
        live KV through DéjàVuLib stream_out/stream_in."""
        old_group = self.token_group
        bsz = max(self.mb_batch.values()) if self.mb_batch else 1
        topo_old = PipelineTopo(len(old_group), self.cfg.num_layers, bsz)
        topo_new = PipelineTopo(new_depth, self.cfg.num_layers, bsz)
        ranges = _stage_ranges(self.cfg.num_layers, new_depth)
        wid0 = max(w.wid for w in set(self.prompt_group + self.token_group)) + 1
        new_group = []
        for i, (lo, hi) in enumerate(ranges):
            new_group.append(StageWorker(
                wid0 + i, self.model, self.params, lo, hi, first=(i == 0),
                last=(i == len(ranges) - 1),
                role=old_group[0].role, hw=self.hw, streamer=self.streamer,
                device=self._device_for(wid0 + i)))
        if self.paged:
            for w in new_group:
                w.enable_paging(self.kv_pool_blocks, self.kv_block_size)
                if self.tiered:
                    # fresh (cold) tiers: the per-stage layer slicing changed,
                    # so the old stages' cached blocks no longer match
                    w.enable_tiering(self.tier_cfg)
            dst_stores = {i: w.cache.host for i, w in enumerate(new_group)}
            for rid in active_mbs:
                for si, w in enumerate(old_group):
                    if self.swapping:
                        w.paged_restore(rid)
                    stream_out_blocks(w.live_blocks(rid), si, topo_old,
                                      topo_new, dst_stores, self.net, seq=rid)
                for di, w in enumerate(new_group):
                    blocks = stream_in_blocks(w.cache.host, di, topo_new,
                                              topo_old, self.net, seq=rid)
                    w.install_blocks(rid, self.seq_len[rid], blocks)
            self.token_group = new_group
            if self.mode == "colocated":
                self.prompt_group = new_group
            for w in new_group:
                self.controller.register(w)
            self.controller.log_event("repartition", depth=new_depth)
            return
        dst_stores = {i: w.cache.host for i, w in enumerate(new_group)}
        for mb in active_mbs:
            cur = self.mb_pos[mb]
            for si, w in enumerate(old_group):
                if self.swapping:
                    w.restore(mb)
                kv = w.kv.get(mb)
                state = {"kv": {k: np.asarray(v) for k, v in kv.items()}}
                stream_out(state, si, topo_old, topo_new, dst_stores, self.net,
                           mb=f"{mb}", token_range=(0, cur))
            for di, w in enumerate(new_group):
                lo, hi = topo_new.layer_range(di)
                hkv, dh = self.cfg.num_kv_heads, self.cfg.resolved_head_dim
                b = np.asarray(old_group[0].kv[list(old_group[0].kv)[0]]["k"]).shape[1] \
                    if old_group[0].kv else None
                shapes = {"kv": {"k": ((hi - lo, b, self.mb_max_len[mb], hkv, dh), self.cfg.dtype),
                                 "v": ((hi - lo, b, self.mb_max_len[mb], hkv, dh), self.cfg.dtype)}}
                local = stream_in(w.cache.host, di, topo_new, topo_old, shapes,
                                  self.net, mb=f"{mb}", token_range=(0, cur))
                w.install_kv(mb, local["kv"])
        self.token_group = new_group
        if self.mode == "colocated":
            self.prompt_group = new_group
        for w in new_group:
            self.controller.register(w)
        self.controller.log_event("repartition", depth=new_depth)
