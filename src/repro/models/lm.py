"""Unified decoder-only LM covering dense / MoE / SSM / hybrid / VLM families.

Layer execution is ``lax.scan`` over *groups*: the repeating pattern unit of
the architecture (1 layer for uniform stacks, 6 for gemma3's 5 local + 1
global, 8 for a Jamba block).  Group params are stacked along a leading axis,
so HLO size is O(pattern), not O(layers).  Non-divisible remainder layers are
unrolled as a tail.

Modes:
  train   — full-seq, no cache, remat-able
  prefill — full-seq, emits per-layer cache (KV / ring-KV / SSM state)
  decode  — one token per row at per-row positions, consumes+produces cache
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro import kernels
from repro.configs.base import ModelConfig
from repro.configs.perf import BASELINE, PerfConfig
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import params as P

f32 = jnp.float32


def _group_period(cfg: ModelConfig) -> int:
    p = 1
    if cfg.attn_every:
        p = math.lcm(p, cfg.attn_every)
    if cfg.local_ratio:
        p = math.lcm(p, cfg.local_ratio + 1)
    if cfg.num_experts:
        p = math.lcm(p, cfg.moe_every)
    return p


def block_specs(cfg: ModelConfig, kind: str, is_moe: bool) -> dict:
    d: dict[str, Any] = {"ln1": L.rmsnorm_specs(cfg.d_model)}
    d["mixer"] = M.ssd_specs(cfg) if kind == "ssm" else L.attention_specs(cfg)
    d["ln2"] = L.rmsnorm_specs(cfg.d_model)
    d["mlp"] = L.moe_specs(cfg) if is_moe else L.mlp_specs(cfg)
    return d


class LM:
    def __init__(self, cfg: ModelConfig, perf: PerfConfig = BASELINE):
        self.cfg = cfg
        self.perf = perf
        p = _group_period(cfg)
        self.period = p
        self.groups = cfg.num_layers // p
        self.tail_layers = list(range(self.groups * p, cfg.num_layers))
        self.kinds = [cfg.layer_kind(j) for j in range(p)]
        self.moes = [cfg.layer_is_moe(j) for j in range(p)]
        for i in range(cfg.num_layers):
            if i < self.groups * p:
                assert cfg.layer_kind(i) == self.kinds[i % p], (i, cfg.name)
                assert cfg.layer_is_moe(i) == self.moes[i % p], (i, cfg.name)

    # ------------------------------------------------------------- specs
    def param_specs(self) -> dict:
        cfg = self.cfg
        group = {f"m{j}": block_specs(cfg, self.kinds[j], self.moes[j])
                 for j in range(self.period)}
        specs = {
            "embed": L.embed_specs(cfg),
            "final_norm": L.rmsnorm_specs(cfg.d_model),
            "blocks": P.stack(group, self.groups),
        }
        if self.tail_layers:
            specs["tail"] = {
                f"t{i}": block_specs(cfg, cfg.layer_kind(i), cfg.layer_is_moe(i))
                for i in self.tail_layers
            }
        return specs

    def _entry_specs(self, kind: str, batch: int, max_len: int) -> dict:
        cfg = self.cfg
        if kind == "ssm":
            return M.ssm_cache_specs(cfg, batch)
        w = cfg.window_for(kind)
        ring = w > 0
        length = min(w, max_len) if ring else max_len
        return L.kv_cache_specs(cfg, batch, length, ring=ring)

    def cache_specs(self, batch: int, max_len: int) -> dict:
        group = {f"m{j}": self._entry_specs(self.kinds[j], batch, max_len)
                 for j in range(self.period)}
        specs = {"blocks": P.stack(group, self.groups)}
        if self.tail_layers:
            specs["tail"] = {
                f"t{i}": self._entry_specs(self.cfg.layer_kind(i), batch, max_len)
                for i in self.tail_layers
            }
        return specs

    # ------------------------------------------------------------- blocks
    def _block(self, p, x, kind, is_moe, *, mode, positions, cache, pos,
               prefix_len, max_len, shd, true_len=None, block_table=None,
               live=None):
        cfg, perf = self.cfg, self.perf
        # kernels serve inference only: pallas_call has no reverse-mode rule
        kmode = "ref" if mode == "train" else kernels.kernel_mode()
        h = L.rmsnorm(p["ln1"], x, cfg.norm_eps)
        new_cache = None
        if kind == "ssm":
            if mode == "decode":
                mix, new_cache = M.ssd_apply_decode(p["mixer"], h, cache, cfg, shd)
            elif mode == "chunk":
                mix, new_cache = M.ssd_apply_chunk(p["mixer"], h, cache, cfg, shd,
                                                   true_len=true_len)
            else:
                mix, new_cache = M.ssd_apply_full(
                    p["mixer"], h, cfg, shd, want_state=(mode == "prefill"),
                    true_len=true_len if mode == "prefill" else None,
                    kernel=kmode)
        else:
            window = cfg.window_for(kind)
            theta = cfg.rope_theta_local if kind == "attn_local" else cfg.rope_theta
            q, k, v = L._project_qkv(p["mixer"], h, cfg, positions, theta)
            q = shd(q, ("batch", "act_seq", "heads", "qkv"))
            if mode == "decode":
                new_cache = L.cache_write_decode(cache, k, v, pos, ring=window > 0)
                mask = L.cache_valid_mask(new_cache, pos, ring=window > 0, window=window)
                ctx = L.attention_decode(q, new_cache["k"].astype(q.dtype),
                                         new_cache["v"].astype(q.dtype), mask)
            elif mode == "paged_decode":
                # cache = per-layer paged pools; one write DMA + decode
                # attention driven by the shared per-row block table.  The
                # Pallas kernel is the accelerator path; the jnp path gathers
                # the pools into the dense slot layout and reuses
                # attention_decode bit-for-bit, so a paged engine is
                # token-identical to a dense one on CPU.
                from repro.kernels.paged_attention.ops import paged_decode_attention
                from repro.serving.kv_cache import paged_gather, paged_write
                kp, vp = paged_write(cache["k"], cache["v"], block_table, pos,
                                     k[:, 0], v[:, 0], live=live)
                if kmode != "ref":
                    ctx_len = pos + 1
                    if live is not None:
                        ctx_len = jnp.where(live, ctx_len, 0)
                    ctx = paged_decode_attention(
                        q[:, 0], kp, vp, block_table, ctx_len,
                        interpret=kmode == "interpret")[:, None]
                else:
                    S_ctx = block_table.shape[1] * cache["k"].shape[1]
                    gk = paged_gather(kp, block_table, S_ctx)
                    gv = paged_gather(vp, block_table, S_ctx)
                    mask = (jnp.arange(S_ctx, dtype=jnp.int32)[None, :]
                            <= pos[:, None])
                    if live is not None:
                        mask = jnp.logical_and(mask, live[:, None])
                    ctx = L.attention_decode(q, gk.astype(q.dtype),
                                             gv.astype(q.dtype), mask)
                new_cache = {"k": kp, "v": vp}
            elif mode == "paged_chunk":
                # attend previously-written blocks (positions < pos0) through
                # a gathered contiguous view, then append this chunk's k/v
                # into allocator-extended blocks
                from repro.serving.kv_cache import paged_gather, paged_write_chunk
                S_ctx = block_table.shape[1] * cache["k"].shape[1]
                gk = paged_gather(cache["k"], block_table, S_ctx).astype(q.dtype)
                gv = paged_gather(cache["v"], block_table, S_ctx).astype(q.dtype)
                ctx = L.attention_chunk(q, k, v, {"k": gk, "v": gv}, pos,
                                        window=0, ring=False)
                kp, vp = paged_write_chunk(cache["k"], cache["v"], block_table,
                                           pos, true_len, k, v)
                new_cache = {"k": kp, "v": vp}
            elif mode == "chunk":
                # attend the pre-write cache + this chunk's own k/v, then
                # append the chunk (pos = chunk start, true_len = valid count)
                ctx = L.attention_chunk(q, k, v, cache, pos,
                                        window=window, ring=window > 0)
                new_cache = L.cache_write_chunk(cache, k, v, pos, true_len,
                                                ring=window > 0)
            else:
                if kmode != "ref" and prefix_len == 0:
                    from repro.kernels.flash_attention.ops import attention as FA
                    ctx = FA(q, k, v, causal=True, window=window,
                             bq=min(128, q.shape[1]), bk=min(128, k.shape[1]),
                             interpret=kmode == "interpret")
                else:
                    ctx = L.attention_full(
                        q, k, v, causal=True, window=window, prefix_len=prefix_len,
                        q_chunk=perf.q_chunk, impl=perf.attn_impl)
                if mode == "prefill":
                    empty = self._empty_cache_entry(kind, x.shape[0], max_len, x.dtype)
                    new_cache = L.cache_write_prefill(empty, k, v, ring=window > 0,
                                                      window=window, true_len=true_len)
            mix = L.attn_out(p["mixer"], ctx)
        x = x + mix
        h2 = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if is_moe:
            y, aux = L.moe_apply(p["mlp"], h2, cfg, shd)
        else:
            y, aux = L.mlp_apply(p["mlp"], h2, cfg, shd), jnp.zeros((), f32)
        return x + y, new_cache, aux

    def _empty_cache_entry(self, kind: str, batch: int, max_len: int, dtype):
        specs = self._entry_specs(kind, batch, max_len)
        kv_dtype = jnp.dtype(self.perf.kv_dtype) if kind != "ssm" else None

        def mk(s: P.ParamSpec):
            dt = s.dtype
            if kv_dtype is not None and s.dtype == jnp.bfloat16:
                dt = kv_dtype
            if s.init == "const":
                return jnp.full(s.shape, s.scale, dt)
            return jnp.zeros(s.shape, dt)

        return P.tree_map_specs(mk, specs)

    # ------------------------------------------------------------- trunk
    def _trunk(self, params, x, *, mode, positions, caches, pos, prefix_len,
               max_len, shd, true_len=None, block_table=None, live=None):
        """Run all layers; returns (x, new_caches, aux_total)."""
        cfg, perf = self.cfg, self.perf
        cached_modes = ("decode", "chunk", "paged_decode", "paged_chunk")

        def group_body(carry, xs):
            x, aux = carry
            gparams = xs[0]
            gcache = xs[1] if mode in cached_modes else None
            new_entries = {}
            for j in range(self.period):
                c = gcache[f"m{j}"] if gcache is not None else None
                x, nc, a = self._block(
                    gparams[f"m{j}"], x, self.kinds[j], self.moes[j],
                    mode=mode, positions=positions, cache=c, pos=pos,
                    prefix_len=prefix_len, max_len=max_len, shd=shd,
                    true_len=true_len, block_table=block_table, live=live)
                aux = aux + a
                if nc is not None:
                    new_entries[f"m{j}"] = nc
            ys = new_entries if (mode != "train") else None
            return (x, aux), ys

        body = group_body
        if mode == "train" and perf.remat != "none":
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if perf.remat == "dots" else None)
            body = jax.checkpoint(group_body, policy=policy)

        if mode == "decode" and perf.decode_unroll:
            aux = jnp.zeros((), f32)
            new_groups = []
            for g in range(self.groups):
                gparams = jax.tree.map(lambda a: a[g], params["blocks"])
                gcache = jax.tree.map(lambda a: a[g], caches["blocks"])
                new_entries = {}
                for j in range(self.period):
                    x, nc, a = self._block(
                        gparams[f"m{j}"], x, self.kinds[j], self.moes[j],
                        mode=mode, positions=positions, cache=gcache[f"m{j}"],
                        pos=pos, prefix_len=prefix_len, max_len=max_len,
                        shd=shd, true_len=true_len)
                    aux = aux + a
                    new_entries[f"m{j}"] = nc
                new_groups.append(new_entries)
            group_caches = jax.tree.map(lambda *xs_: jnp.stack(xs_), *new_groups)
        else:
            xs = (params["blocks"],)
            if mode in cached_modes:
                xs = (params["blocks"], caches["blocks"])
            (x, aux), group_caches = jax.lax.scan(body, (x, jnp.zeros((), f32)), xs)

        tail_caches = {}
        for i in self.tail_layers:
            tp = params["tail"][f"t{i}"]
            c = caches["tail"][f"t{i}"] if mode in cached_modes else None
            x, nc, a = self._block(
                tp, x, cfg.layer_kind(i), cfg.layer_is_moe(i),
                mode=mode, positions=positions, cache=c, pos=pos,
                prefix_len=prefix_len, max_len=max_len, shd=shd,
                true_len=true_len, block_table=block_table, live=live)
            aux = aux + a
            if nc is not None:
                tail_caches[f"t{i}"] = nc

        new_caches = None
        if mode != "train":
            new_caches = {"blocks": group_caches}
            if self.tail_layers:
                new_caches["tail"] = tail_caches
        return x, new_caches, aux

    # ------------------------------------------------------------- inputs
    def _embed_inputs(self, params, batch, shd):
        """tokens (+ optional vlm patches) -> (x, positions, prefix_len)."""
        cfg = self.cfg
        x = L.embed_apply(params["embed"], batch["tokens"], cfg)
        prefix = 0
        if cfg.num_vision_tokens:
            patches = batch["patches"].astype(x.dtype)
            if cfg.scale_embed:
                patches = patches * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
            x = jnp.concatenate([patches, x], axis=1)
            prefix = cfg.num_vision_tokens
        x = shd(x, ("batch", "act_seq", "embed"))
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)[None, :]
        return x, positions, prefix

    # ------------------------------------------------------------- public
    def loss(self, params, batch, shd=L._noop_shd):
        """batch: tokens (B,S[,text]) int32, labels (B,S_text) int32 (-1 pad)."""
        cfg = self.cfg
        x, positions, prefix = self._embed_inputs(params, batch, shd)
        x, _, aux = self._trunk(params, x, mode="train", positions=positions,
                                caches=None, pos=None, prefix_len=prefix,
                                max_len=0, shd=shd)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if prefix:
            x = x[:, prefix - 1:-1]  # hidden states predicting each text token
            labels = batch["labels"]
        else:
            x = x[:, :-1]
            labels = batch["labels"][:, 1:]
        nll, cnt = L.chunked_xent(params["embed"], x, labels, cfg, shd,
                                  chunk=self.perf.xent_chunk)
        loss = nll / jnp.maximum(cnt.astype(f32), 1.0)
        if cfg.num_experts:
            loss = loss + cfg.aux_loss_weight * aux / max(cfg.num_layers, 1)
        return loss, {"nll": nll, "tokens": cnt, "aux": aux}

    def prefill(self, params, batch, max_len: int, shd=L._noop_shd, true_len=None):
        """Full-sequence prefill.  Returns (last-token logits (B,V) f32, cache).

        ``true_len`` (B,) int32: number of valid *text* tokens per row for
        right-padded (bucketed) prompts; logits come from the last valid
        position and ring/SSM caches exclude pad positions.  The absolute
        sequence length includes any vision prefix."""
        cfg = self.cfg
        x, positions, prefix = self._embed_inputs(params, batch, shd)
        abs_len = None if true_len is None else true_len + prefix
        x, caches, _ = self._trunk(params, x, mode="prefill", positions=positions,
                                   caches=None, pos=None, prefix_len=prefix,
                                   max_len=max_len, shd=shd, true_len=abs_len)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if true_len is None:
            x_last = x[:, -1:]
        else:
            li = (abs_len - 1)[:, None, None]
            x_last = jnp.take_along_axis(x, jnp.maximum(li, 0), axis=1)
        logits = L.unembed_logits(params["embed"], x_last, cfg)[:, 0]
        return logits, caches

    def prefill_chunk(self, params, tokens, pos0, n_valid, caches, shd=L._noop_shd):
        """One bucket-sized chunk of a long prompt, batched over cache rows.

        tokens (B,C) int32 right-padded chunk; pos0 (B,) int32 absolute start
        positions; n_valid (B,) int32 valid tokens per row — 0 marks an idle
        row whose cache is returned bit-identical (the engine runs its whole
        decode pool through one program regardless of how many rows are mid-
        prefill).  ``caches`` is the full pool cache tree; the chunk's K/V
        (or SSM state) is appended in place of raising on long prompts.

        Returns (logits (B,V) f32 at each row's last valid chunk position,
        new caches).  Text-only decoders — no vision prefix or encoder; the
        engine gates admission accordingly.

        Exactness: attention / ring / SSM chunked prefill matches full-seq
        prefill (fp rounding aside).  MoE capacity dispatch is per-call, so
        its token-drop pattern under router skew may differ from a single
        full-sequence prefill — inherent to capacity-based MoE (decode, with
        one slot per expert per token, never drops either way).
        """
        cfg = self.cfg
        x = L.embed_apply(params["embed"], tokens, cfg)
        x = shd(x, ("batch", "act_seq", "embed"))
        C = tokens.shape[1]
        positions = pos0[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        x, caches, _ = self._trunk(params, x, mode="chunk", positions=positions,
                                   caches=caches, pos=pos0, prefix_len=0,
                                   max_len=0, shd=shd, true_len=n_valid)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        li = jnp.maximum(n_valid - 1, 0)[:, None, None]
        x_last = jnp.take_along_axis(x, li, axis=1)
        logits = L.unembed_logits(params["embed"], x_last, cfg)[:, 0]
        return logits, caches

    # ------------------------------------------------------------- paged
    def supports_paged(self) -> bool:
        """Paged KV serving covers pure decoders whose every layer is global
        attention: SSM/conv state is per-row (nothing to page), ring layers
        keep their own slot-position bookkeeping, and vision/encoder
        prefixes pin absolute layout.  The engine falls back to the dense
        RowPool backend for those families."""
        cfg = self.cfg
        kinds = set(self.kinds) | {cfg.layer_kind(i) for i in self.tail_layers}
        return (not cfg.is_encoder_decoder and not cfg.num_vision_tokens
                and kinds == {"attn"} and cfg.window_for("attn") == 0)

    def paged_cache_specs(self, num_blocks: int, block_size: int) -> dict:
        """Per-layer paged pool specs, mirroring :meth:`cache_specs`'s tree
        structure so the same scan-over-groups trunk consumes them.  Every
        layer indexes its pool through one shared per-row block table."""
        assert self.supports_paged(), f"{self.cfg.name}: not paged-servable"
        cfg = self.cfg
        kv_dtype = jnp.dtype(self.perf.kv_dtype)

        def entry():
            shape = (num_blocks, block_size, cfg.num_kv_heads, cfg.head_dim)
            axes = ("kv_blocks", "kv_slot", "kv_heads", "qkv")
            return {"k": P.ParamSpec(shape, axes, dtype=kv_dtype, init="zeros"),
                    "v": P.ParamSpec(shape, axes, dtype=kv_dtype, init="zeros")}

        specs = {"blocks": P.stack({f"m{j}": entry()
                                    for j in range(self.period)}, self.groups)}
        if self.tail_layers:
            specs["tail"] = {f"t{i}": entry() for i in self.tail_layers}
        return specs

    def decode_step_paged(self, params, tokens, pos, pools, block_table,
                          live=None, shd=L._noop_shd):
        """Decode step against paged KV pools.

        tokens (B,1) int32; pos (B,) int32 absolute positions; pools: the
        paged cache tree (:meth:`paged_cache_specs`); block_table
        (B, max_blk) int32, -1 = unmapped; live (B,) bool — False rows
        (empty or mid-prefill) neither write their token nor count context.
        Returns (logits (B,V) f32, new pools)."""
        cfg = self.cfg
        x = L.embed_apply(params["embed"], tokens, cfg)
        positions = pos[:, None]
        x, pools, _ = self._trunk(params, x, mode="paged_decode",
                                  positions=positions, caches=pools, pos=pos,
                                  prefix_len=0, max_len=0, shd=shd,
                                  block_table=block_table, live=live)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = L.unembed_logits(params["embed"], x, cfg)[:, 0]
        return logits, pools

    def prefill_chunk_paged(self, params, tokens, pos0, n_valid, pools,
                            block_table, shd=L._noop_shd):
        """Chunked prefill appending into paged pools (the paged counterpart
        of :meth:`prefill_chunk`).  A prefix-cache hit simply starts the
        first chunk at pos0 = n_cached: the shared blocks already hold those
        positions' KV, so the skipped tokens are never embedded or attended.
        The batch is any set of rows (the engine passes the rows that
        advance, a fixed group at a time, with their block-table rows): the
        pools are reached only through ``block_table``.  Rows with
        n_valid == 0 and an unmapped table are exact no-ops."""
        cfg = self.cfg
        x = L.embed_apply(params["embed"], tokens, cfg)
        x = shd(x, ("batch", "act_seq", "embed"))
        C = tokens.shape[1]
        positions = pos0[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        x, pools, _ = self._trunk(params, x, mode="paged_chunk",
                                  positions=positions, caches=pools, pos=pos0,
                                  prefix_len=0, max_len=0, shd=shd,
                                  true_len=n_valid, block_table=block_table)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        li = jnp.maximum(n_valid - 1, 0)[:, None, None]
        x_last = jnp.take_along_axis(x, li, axis=1)
        logits = L.unembed_logits(params["embed"], x_last, cfg)[:, 0]
        return logits, pools

    def decode_step(self, params, tokens, pos, caches, shd=L._noop_shd):
        """tokens (B,1) int32, pos (B,) int32 absolute positions in the full
        (prefix + text) sequence.  Returns (logits (B,V) f32, new caches)."""
        cfg = self.cfg
        x = L.embed_apply(params["embed"], tokens, cfg)
        positions = pos[:, None]
        x, caches, _ = self._trunk(params, x, mode="decode", positions=positions,
                                   caches=caches, pos=pos, prefix_len=0,
                                   max_len=0, shd=shd)
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        logits = L.unembed_logits(params["embed"], x, cfg)[:, 0]
        return logits, caches


def make_model(cfg: ModelConfig, perf: PerfConfig = BASELINE):
    if cfg.is_encoder_decoder:
        from repro.models.whisper import EncDec
        return EncDec(cfg, perf)
    return LM(cfg, perf)
