"""Decoder-only transformer LM (dense / MoE / VLM families).

Layers are a single stacked pytree scanned with ``jax.lax.scan`` so HLO size
(and compile time) is O(1) in depth; the KV cache is threaded through the scan
as per-layer xs/ys.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kvcache.paged import block_window, gather_layer
from repro.models import attention as attn
from repro.models.common import (alibi_slopes, embed_init, logical_constraint,
                                 norm_apply, norm_init, split_keys)
from repro.models.losses import causal_lm_loss
from repro.models.mlp import mlp_apply, mlp_init
from repro.models.moe import moe_apply, moe_init


class DecoderLM:
    """Families: dense, moe, vlm (backbone + stub patch embeddings)."""

    def __init__(self, cfg: ArchConfig, backend: str = "xla", remat: bool = False):
        self.cfg = cfg
        self.backend = backend
        self.remat = remat
        self._alibi = (jnp.asarray(alibi_slopes(cfg.num_heads))
                       if cfg.pos_emb == "alibi" else None)
        # per-layer sliding window (0 = full attention), threaded through
        # every layer scan as xs so full-attn-layer mixes stay O(1)-HLO;
        # all-zeros for windowless configs (`_layer` then keeps the static
        # no-window mask path, and the array is dead-code-eliminated)
        self._layer_window = jnp.asarray(
            [0 if i in cfg.full_attn_layers else cfg.sliding_window
             for i in range(cfg.num_layers)], jnp.int32)
        self._stage_jits: Dict[tuple, Dict[str, Callable]] = {}

    # ------------------------------------------------------------------
    def init(self, key) -> Dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        kE, kP, kL, kH, kV = split_keys(key, 5)
        p: Dict = {"embed": embed_init(kE, (cfg.vocab_size, cfg.d_model), dtype)}
        if cfg.pos_emb == "learned":
            p["pos_table"] = embed_init(kP, (cfg.max_seq_len, cfg.d_model), dtype)
        if cfg.family == "vlm":
            p["patch_proj"] = embed_init(kV, (cfg.d_model, cfg.d_model), dtype)

        def one_layer(k):
            k1, k2, k3 = split_keys(k, 3)
            lp = {"ln1": norm_init(cfg.norm, cfg.d_model, dtype),
                  "attn": attn.attn_init(k1, cfg, dtype),
                  "ln2": norm_init(cfg.norm, cfg.d_model, dtype)}
            if cfg.is_moe:
                lp["moe"] = moe_init(k2, cfg, dtype)
            else:
                lp["mlp"] = mlp_init(k2, cfg, dtype)
            return lp

        keys = split_keys(kL, cfg.num_layers)
        p["layers"] = jax.tree.map(lambda *xs: jnp.stack(xs), *[one_layer(k) for k in keys])
        p["final_norm"] = norm_init(cfg.norm, cfg.d_model, dtype)
        if not cfg.tie_embeddings:
            p["lm_head"] = embed_init(kH, (cfg.d_model, cfg.vocab_size), dtype)
        return p

    # ------------------------------------------------------------------
    def _embed(self, params, tokens, patch_embeds=None):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.family == "vlm":
            assert patch_embeds is not None, "vlm needs patch_embeds"
            patches = patch_embeds.astype(x.dtype) @ params["patch_proj"]
            x = jnp.concatenate([patches, x], axis=1)
        if cfg.pos_emb == "learned":
            s = x.shape[1]
            x = x + params["pos_table"][None, :s]
        return x

    def _unembed(self, params, x):
        head = (params["embed"].T if self.cfg.tie_embeddings else params["lm_head"])
        logits = x @ head
        return logical_constraint(logits, "batch", None, "vocab")

    def _layer(self, x, lp, *, mode, positions=None, kc=None, vc=None,
               kv_positions=None, pos=None, q_lens=None, window=0,
               collect_aux=False):
        cfg = self.cfg
        if cfg.sliding_window == 0:
            window = 0        # static: windowless configs keep the plain mask
        num_meta = cfg.num_meta_tokens
        x = logical_constraint(x, "batch", "seq", None)   # residual stream
        h = norm_apply(cfg.norm, x, lp["ln1"])
        rope = cfg.pos_emb == "rope"
        if mode == "prefill":
            a, k, v = attn.attention_prefill(h, lp["attn"], cfg, positions,
                                             window=window, num_meta=num_meta,
                                             rope=rope, alibi=self._alibi,
                                             backend=self.backend)
            extra = (k, v)
        elif mode == "decode_batch":
            a, kc, vc = attn.attention_decode_batch(h, lp["attn"], cfg, kc, vc,
                                                    kv_positions, pos,
                                                    q_lens=q_lens,
                                                    window=window,
                                                    num_meta=num_meta,
                                                    rope=rope,
                                                    alibi=self._alibi,
                                                    backend=self.backend)
            extra = (kc, vc)
        else:
            a, kc, vc = attn.attention_decode(h, lp["attn"], cfg, kc, vc,
                                              kv_positions, pos,
                                              window=window, num_meta=num_meta,
                                              rope=rope,
                                              alibi=self._alibi, backend=self.backend)
            extra = (kc, vc)
        x = x + a
        h = norm_apply(cfg.norm, x, lp["ln2"])
        aux = jnp.float32(0.0)
        if cfg.is_moe:
            b, s, d = h.shape
            flat = h.reshape(b * s, d)
            if collect_aux:
                # training: capacity-bounded dispatch + load-balancing aux
                out, aux = moe_apply(flat, lp["moe"], cfg, return_aux=True)
            else:
                # inference: lossless dispatch — capacity depends on the
                # pass's token count, so dropping would make a token's
                # output vary with how the scheduler packed the pass
                out = moe_apply(flat, lp["moe"], cfg, drop=False)
            out = out.reshape(b, s, d)
        else:
            out = mlp_apply(h, lp["mlp"], cfg)
        return x + out, extra, aux

    # ------------------------------------------------------------------
    def loss(self, params, batch):
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens, batch.get("patch_embeds"))
        s_total = x.shape[1]
        positions = jnp.arange(s_total, dtype=jnp.int32)

        def body(x, xs):
            lp, w = xs
            x, _, aux = self._layer(x, lp, mode="prefill", positions=positions,
                                    window=w, collect_aux=cfg.is_moe)
            return x, aux

        if self.remat:
            body = jax.checkpoint(body)
        x, auxs = jax.lax.scan(body, x, (params["layers"], self._layer_window))
        x = norm_apply(cfg.norm, x, params["final_norm"])
        if cfg.family == "vlm":  # drop patch positions before the LM head
            x = x[:, cfg.num_patches:]
        logits = self._unembed(params, x)
        loss = causal_lm_loss(logits, batch["targets"], batch["loss_mask"])
        if cfg.is_moe:
            loss = loss + 0.01 * jnp.mean(auxs)
        return loss

    # ------------------------------------------------------------------
    def prefill(self, params, batch, max_len: Optional[int] = None):
        """Returns (last_token_logits [B,V], decode_state, next_pos)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        x = self._embed(params, tokens, batch.get("patch_embeds"))
        b, s_total, _ = x.shape
        max_len = max(max_len or s_total, s_total)  # total context incl. patches
        positions = jnp.arange(s_total, dtype=jnp.int32)

        def body(x, xs):
            lp, w = xs
            x, (k, v), _ = self._layer(x, lp, mode="prefill",
                                       positions=positions, window=w)
            return x, (k, v)

        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"],
                                             self._layer_window))
        x = norm_apply(cfg.norm, x, params["final_norm"])
        logits = self._unembed(params, x[:, -1:, :])[:, 0]
        hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
        kcache = jnp.zeros((cfg.num_layers, b, max_len, hkv, dh), ks.dtype)
        vcache = jnp.zeros_like(kcache)
        kcache = jax.lax.dynamic_update_slice_in_dim(kcache, ks, 0, axis=2)
        vcache = jax.lax.dynamic_update_slice_in_dim(vcache, vs, 0, axis=2)
        state = {"kv": {"k": kcache, "v": vcache}}
        return logits, state, jnp.int32(s_total)

    # ------------------------------------------------------------------
    # Stage-wise API for pipeline-parallel workers (DéjàVu cluster).
    # A stage owns a contiguous layer slice; stage 0 also embeds, the last
    # stage also applies the final norm + LM head.
    # ------------------------------------------------------------------

    def slice_params(self, params, lo: int, hi: int, *, first: bool, last: bool):
        sp = {"layers": jax.tree.map(lambda a: a[lo:hi], params["layers"]),
              "layer_window": self._layer_window[lo:hi]}
        if first:
            for k in ("embed", "pos_table", "patch_proj"):
                if k in params:
                    sp[k] = params[k]
        if last:
            sp["final_norm"] = params["final_norm"]
            if self.cfg.tie_embeddings:
                sp["embed"] = params["embed"]
            elif "lm_head" in params:
                sp["lm_head"] = params["lm_head"]
        return sp

    def stage_jits(self, *, first: bool, last: bool) -> Dict[str, Callable]:
        """The jitted stage functions of one pipeline position, built once
        per model and shared by every worker at that position: a worker that
        replaces a failed one, or a new cluster over the same model, reuses
        the compiled executables instead of tracing and compiling its own
        (a stage on another device reuses the trace and compiles only for
        that device).  Stage 0 takes tokens where the others take
        activations.  Each program is named after its stage function
        (``jit_stage_decode_batch_paged``), so a device trace tells them
        apart."""
        key = (first, last)
        if key not in self._stage_jits:
            def stage(fn, token_kw):
                if first:
                    def call(sp, tokens, *a):
                        return fn(sp, None, *a, first=True, last=last,
                                  **{token_kw: tokens})
                else:
                    def call(sp, x, *a):
                        return fn(sp, x, *a, first=False, last=last)
                call.__name__ = call.__qualname__ = fn.__name__
                return jax.jit(call)

            self._stage_jits[key] = {
                "prefill": stage(self.stage_prefill, "tokens"),
                "decode": stage(self.stage_decode, "token"),
                "decode_batch_paged": stage(self.stage_decode_batch_paged,
                                            "token"),
                "prefill_chunk_batch_paged": stage(
                    self.stage_prefill_chunk_batch_paged, "tokens"),
            }
        return self._stage_jits[key]

    def stage_prefill(self, sp, x, *, first: bool, last: bool,
                      tokens=None, patch_embeds=None):
        """Run one stage over a full prompt.  Stage 0 passes tokens instead
        of x.  Returns (x_out_or_logits, ks, vs) with ks/vs [Lstage,B,S,..]."""
        cfg = self.cfg
        if first:
            x = self._embed(sp, tokens, patch_embeds)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)

        def body(x, xs):
            lp, w = xs
            x, (k, v), _ = self._layer(x, lp, mode="prefill",
                                       positions=positions, window=w)
            return x, (k, v)

        x, (ks, vs) = jax.lax.scan(body, x, (sp["layers"],
                                             sp["layer_window"]))
        if last:
            x = norm_apply(cfg.norm, x, sp["final_norm"])
            x = self._unembed(sp, x[:, -1:, :])[:, 0]
        return x, ks, vs

    def _paged_layers(self, sp, x, kp, vp, table, pos, q_lens, kv_positions):
        """The stage's layer scan over pool pages ``[Lstage, N, bs, H*D]``:
        each layer gathers its dense ``[B, T*bs, H, D]`` cache by block table
        ``[B, T]`` (`gather_layer`), runs the fused-round attention on it,
        and keeps only the blocks the pass wrote into, from the one holding
        ``pos[b]`` on (`block_window`).  Returns (x, k_slots, v_slots),
        slots ``[Lstage, B, nb*bs, H, D]`` for `PagedKVCache.write_window`."""
        c = x.shape[1]
        hkv, dh = self.cfg.num_kv_heads, self.cfg.resolved_head_dim
        bs = kp.shape[2]

        def body(x, xs):
            lp, layer, w = xs
            k1 = gather_layer(kp, table, layer, hkv, dh)
            v1 = gather_layer(vp, table, layer, hkv, dh)
            x, (k1, v1), _ = self._layer(x, lp, mode="decode_batch", kc=k1,
                                         vc=v1, kv_positions=kv_positions,
                                         pos=pos, q_lens=q_lens, window=w)
            return x, (block_window(k1, pos, c, bs),
                       block_window(v1, pos, c, bs))

        layers = jnp.arange(kp.shape[0], dtype=jnp.int32)
        x, (k_slots, v_slots) = jax.lax.scan(body, x, (sp["layers"], layers,
                                                       sp["layer_window"]))
        return x, k_slots, v_slots

    def stage_decode_batch_paged(self, sp, x, kp, vp, table, pos, *,
                                 first: bool, last: bool, token=None):
        """Fused-round decode for one stage: B sequences each advance ONE
        step in a single pipeline pass, sequence b's new token sitting at its
        OWN position ``pos[b]`` (ragged lengths), its context read from the
        pool pages by its row of the block table.  kp/vp: [Lstage,N,bs,H*D];
        table: [B,T]; pos: [B].  Returns (x, k_slots, v_slots): the block
        each sequence's new token went into, [Lstage,B,bs,H,D]."""
        cfg = self.cfg
        if first:
            x = jnp.take(sp["embed"], token[:, None], axis=0)
            if cfg.pos_emb == "learned":
                x = x + jnp.take(sp["pos_table"], pos, axis=0)[:, None]
        s_cache = table.shape[1] * kp.shape[2]
        slots = jnp.arange(s_cache, dtype=jnp.int32)[None, :]
        kv_positions = jnp.where(slots <= pos[:, None], slots, -1)   # [B,S]
        x, k_slots, v_slots = self._paged_layers(sp, x, kp, vp, table, pos,
                                                 None, kv_positions)
        if last:
            x = norm_apply(cfg.norm, x, sp["final_norm"])
            x = self._unembed(sp, x)[:, 0]
        return x, k_slots, v_slots

    def stage_prefill_chunk_batch_paged(self, sp, x, kp, vp, table, pos,
                                        q_lens, *, first: bool, last: bool,
                                        tokens=None):
        """Fused chunk-set pass: one prefill chunk of EACH of B in-flight
        sequences runs in a single pipeline pass.  Sequence b's chunk holds
        ``q_lens[b]`` valid tokens at absolute positions ``pos[b] ..
        pos[b]+q_lens[b]-1`` (rows past q_lens[b] are padding); each chunk
        attends causally over its own prefix [0, pos[b]), read from the pool
        pages by block table, plus itself.  Stage 0 passes `tokens` [B,Cmax];
        the last stage returns each chunk's final-valid-token logits [B,V]
        (only sequences whose prefill just completed read theirs).
        kp/vp: [Lstage,N,bs,H*D]; table: [B,T]; pos/q_lens: [B].  Returns
        (x, k_slots, v_slots): the blocks each chunk went into,
        [Lstage,B,nb*bs,H,D] (`block_window`)."""
        cfg = self.cfg
        if first:
            x = jnp.take(sp["embed"], tokens, axis=0)
            if cfg.pos_emb == "learned":
                c = tokens.shape[1]
                posm = pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
                x = x + jnp.take(sp["pos_table"],
                                 jnp.clip(posm, 0, sp["pos_table"].shape[0] - 1),
                                 axis=0)
        c = x.shape[1]
        s_cache = table.shape[1] * kp.shape[2]
        slots = jnp.arange(s_cache, dtype=jnp.int32)[None, :]
        kv_positions = jnp.where(slots < (pos + q_lens)[:, None], slots, -1)
        x, k_slots, v_slots = self._paged_layers(sp, x, kp, vp, table, pos,
                                                 q_lens, kv_positions)
        if last:
            x = norm_apply(cfg.norm, x, sp["final_norm"])
            # per-sequence final valid token (ragged chunks): row q_lens[b]-1
            sel = (jnp.arange(c, dtype=jnp.int32)[None, :]
                   == (q_lens - 1)[:, None]).astype(x.dtype)       # [B,C]
            x = jnp.einsum("bc,bcd->bd", sel, x)
            x = self._unembed(sp, x[:, None])[:, 0]
        return x, k_slots, v_slots

    def stage_decode(self, sp, x, kc, vc, pos, *, first: bool, last: bool,
                     token=None):
        """One decode step for one stage.  kc/vc: [Lstage,B,S,H,D]."""
        cfg = self.cfg
        if first:
            x = jnp.take(sp["embed"], token[:, None], axis=0)
            if cfg.pos_emb == "learned":
                x = x + jax.lax.dynamic_slice_in_dim(sp["pos_table"], pos, 1, axis=0)[None]
        s_cache = kc.shape[2]
        kv_positions = jnp.arange(s_cache, dtype=jnp.int32)
        kv_positions = jnp.where(kv_positions <= pos, kv_positions, -1)

        def body(x, xs):
            lp, k1, v1, w = xs
            x, (k1, v1), _ = self._layer(x, lp, mode="decode", kc=k1, vc=v1,
                                         kv_positions=kv_positions, pos=pos,
                                         window=w)
            return x, (k1, v1)

        x, (kc, vc) = jax.lax.scan(body, x, (sp["layers"], kc, vc,
                                             sp["layer_window"]))
        if last:
            x = norm_apply(cfg.norm, x, sp["final_norm"])
            x = self._unembed(sp, x)[:, 0]
        return x, kc, vc

    # ------------------------------------------------------------------
    def decode_step(self, params, state, token, pos):
        """token: [B] int32; pos: scalar int32 (position of the new token).

        Returns (logits [B,V], new_state)."""
        cfg = self.cfg
        x = jnp.take(params["embed"], token[:, None], axis=0)
        if cfg.pos_emb == "learned":
            x = x + jax.lax.dynamic_slice_in_dim(params["pos_table"], pos, 1, axis=0)[None]
        s_cache = state["kv"]["k"].shape[2]
        kv_positions = jnp.arange(s_cache, dtype=jnp.int32)
        kv_positions = jnp.where(kv_positions <= pos, kv_positions, -1)

        def body(x, xs):
            lp, kc, vc, w = xs
            x, (kc, vc), _ = self._layer(x, lp, mode="decode", kc=kc, vc=vc,
                                         kv_positions=kv_positions, pos=pos,
                                         window=w)
            return x, (kc, vc)

        x, (kcs, vcs) = jax.lax.scan(body, x, (params["layers"],
                                               state["kv"]["k"],
                                               state["kv"]["v"],
                                               self._layer_window))
        x = norm_apply(cfg.norm, x, params["final_norm"])
        logits = self._unembed(params, x)[:, 0]
        return logits, {"kv": {"k": kcs, "v": vcs}}
