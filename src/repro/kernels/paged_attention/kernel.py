"""Paged decode attention for TPU (PagedAttention adapted to VMEM tiling).

The GPU original gathers KV blocks with per-warp address arithmetic; the TPU
adaptation streams whole pages HBM->VMEM through a block-table-driven index
map (scalar-prefetch grid spec: the table must be resident before the DMA
for grid step j can be issued).  Grid (B, KV, nblk) with the page axis
innermost; online-softmax state for all ``rep`` query heads of one kv head
sits in VMEM scratch across page iterations.

Dead pages (table == -1 or fully past context_len) skip their compute via
``pl.when``; their DMA is redirected to page 0 by the index map (clamped),
so no out-of-bounds traffic is issued.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32
NEG = -1e30


def _kernel(tbl_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale, bs, nblk, rep, d):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    blk = tbl_ref[b, j]
    ctx = ctx_ref[b]
    live = jnp.logical_and(blk >= 0, j * bs < ctx)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(f32) * scale              # (rep, d)
        k = k_ref[0, 0].astype(f32)                      # (bs, d)
        v = v_ref[0, 0].astype(f32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)   # (rep, bs)
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rep, bs), 1)
        s = jnp.where(pos < ctx, s, NEG)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        m_safe = jnp.maximum(m_new, -1e29)
        p = jnp.exp(s - m_safe[:, None])
        alpha = jnp.exp(jnp.maximum(m_prev, -1e29) - m_safe)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=f32)
        m_scr[...] = m_new

    @pl.when(j == nblk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, block_table, context_len, *,
                    scale: float | None = None, interpret: bool = False):
    """q (B,H,d); pools (num_blocks, bs, KV, d); block_table (B, max_blk);
    context_len (B,) -> (B,H,d)."""
    B, H, d = q.shape
    nb, bs, KV, _ = k_pages.shape
    max_blk = block_table.shape[1]
    rep = H // KV
    scale = d ** -0.5 if scale is None else scale

    # (B, KV, rep, d) query layout: one grid cell owns one kv head's group
    qg = q.reshape(B, KV, rep, d)
    # pools to (num_blocks, KV, bs, d) so one (page, kv head) is a VMEM tile
    kp = k_pages.transpose(0, 2, 1, 3)
    vp = v_pages.transpose(0, 2, 1, 3)

    kern = functools.partial(_kernel, scale=scale, bs=bs, nblk=max_blk,
                             rep=rep, d=d)

    def page_map(b, g, j, tbl):
        return (jnp.maximum(tbl[b, j], 0), g, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                      # block_table, context_len
        grid=(B, KV, max_blk),
        in_specs=[
            pl.BlockSpec((1, 1, rep, d), lambda b, g, j, tbl, ctx: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda b, g, j, tbl, ctx: (jnp.maximum(tbl[b, j], 0), g, 0, 0)),
            pl.BlockSpec((1, 1, bs, d),
                         lambda b, g, j, tbl, ctx: (jnp.maximum(tbl[b, j], 0), g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, d),
                               lambda b, g, j, tbl, ctx: (b, g, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rep,), f32), pltpu.VMEM((rep,), f32),
                        pltpu.VMEM((rep, d), f32)],
    )
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, rep, d), q.dtype),
        interpret=interpret,
    )(block_table, context_len, qg, kp, vp)
    return out.reshape(B, H, d)
