"""FA2-style blocked attention for TPU (pl.pallas_call + BlockSpec).

Layout (B,H,S,d); grid (B, H, nq, nk) with the kv axis innermost — TPU grids
execute sequentially, so the online-softmax running state (m, l, acc) lives
in VMEM scratch that persists across the nk iterations of one (b,h,q) tile.
Block shapes are MXU-aligned: q/k tiles of 128/256 rows, head_dim lanes.

GQA is handled in the k/v index maps (kv head = h // rep), sliding windows
by masking whole tiles out via ``pl.when`` (a skipped tile costs one grid
step, no memory traffic: its DMA loads the same block as the previous step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

f32 = jnp.float32
NEG = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                 scale, causal, window, bq, bk, nk, sq, skv):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # tile coordinates (kv may be longer than q: right-aligned q positions)
    off = skv - sq
    q0 = qi * bq + off          # absolute position of the q tile start
    k0 = ki * bk

    # whole-tile skip tests
    live = jnp.bool_(True)
    if causal:
        live &= k0 <= q0 + bq - 1
    if window:
        live &= (k0 + bk - 1) > (q0 - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(f32) * scale             # (bq, d)
        k = k_ref[0, 0].astype(f32)                     # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32)  # (bq, bk)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        m_safe = jnp.maximum(m_new, -1e29)
        p = jnp.exp(s - m_safe[:, None])                # (bq, bk)
        alpha = jnp.exp(jnp.maximum(m_prev, -1e29) - m_safe)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1)
        v = v_ref[0, 0].astype(f32)                     # (bk, d)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=f32)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + pv
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0, :, :] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, bq: int = 128, bk: int = 128,
                    interpret: bool = False):
    """q: (B,H,Sq,d); k,v: (B,KV,Skv,d) -> (B,H,Sq,d).

    Sq/Skv must be multiples of bq/bk (ops.py pads).  ``interpret=True`` runs
    the kernel body on CPU for validation.
    """
    B, H, Sq, d = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    rep = H // KV
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    nq, nk = Sq // bq, Skv // bk
    scale = d ** -0.5 if scale is None else scale

    kern = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, nk=nk, sq=Sq, skv=Skv)

    return pl.pallas_call(
        kern,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, i, j: (b, h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype),
        scratch_shapes=_scratch(bq, d),
        interpret=interpret,
    )(q, k, v)


def _scratch(bq: int, d: int):
    from jax.experimental.pallas import tpu as pltpu
    return [pltpu.VMEM((bq,), f32), pltpu.VMEM((bq,), f32),
            pltpu.VMEM((bq, d), f32)]
