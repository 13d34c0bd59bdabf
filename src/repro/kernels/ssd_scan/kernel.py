"""Mamba-2 SSD chunked scan for TPU (pl.pallas_call + BlockSpec).

Grid (b, H, nc) with the chunk axis innermost: the inter-chunk state h
(P x N, fp32) persists in VMEM scratch across the sequential chunk
iterations while the intra-chunk quadratic term runs on the MXU:

  M   = (C B^T) * L        -- (Q,Q) masked decay kernel
  y   = M (x*dt) + (C h) * exp(cum)
  h'  = exp(cum_Q) h + (B * wt)^T (x*dt)

Chunk Q and head dim P are MXU-aligned (Q=128/256, P=64/128); one grid cell
holds Q x max(P, N) fp32 tiles comfortably in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

f32 = jnp.float32


def _kernel(x_ref, b_ref, c_ref, dt_ref, da_ref, y_ref, hout_ref, h_scr, *,
            Q, P, N, nc):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(f32)          # (Q, P)
    Bm = b_ref[0, 0].astype(f32)         # (Q, N)
    Cm = c_ref[0, 0].astype(f32)         # (Q, N)
    dt = dt_ref[0, 0].astype(f32)        # (1, Q)
    da = da_ref[0, 0].astype(f32)        # (1, Q)

    # prefix sums as triangular matmuls (Mosaic has no cumsum), kept 2-D:
    # cum_r[0, q] and cum_c[q, 0] both hold sum_{t<=q} da[t]
    qi = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    ti = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = ti <= qi
    tri = causal.astype(f32)                                 # [q, t]
    eye = (ti == qi).astype(f32)
    cum_r = _dot(da, tri, 1, 1)                              # (1, Q)
    cum_c = _dot(tri, da, 1, 1)                              # (Q, 1)
    dt_c = _dot(eye, dt, 1, 1)                               # (Q, 1)
    total = jnp.sum(da, axis=1, keepdims=True)               # (1, 1)

    L = jnp.where(causal, jnp.exp(cum_c - cum_r), 0.0)       # (q, t)
    M = _dot(Cm, Bm, 1, 1) * L                               # (q, t)
    y_in = _dot(M * dt, x, 1, 0)                             # (Q, P)
    h = h_scr[...]                                           # (P, N)
    y_off = _dot(Cm, h, 1, 1) * jnp.exp(cum_c)               # (Q, P)
    y_ref[0, 0] = (y_in + y_off).astype(y_ref.dtype)

    wt = dt_c * jnp.exp(total - cum_c)                       # (Q, 1)
    dh = _dot(x, Bm * wt, 0, 0)                              # (P, N)
    h_scr[...] = h * jnp.exp(total) + dh

    @pl.when(ci == nc - 1)
    def _emit_state():
        hout_ref[0, 0] = h_scr[...]


def _dot(a, b, ca: int, cb: int):
    """2-D f32 matmul contracting a's dim ``ca`` with b's dim ``cb``."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=f32)


def ssd_scan(x, B, C, dt, da, *, chunk: int = 128, interpret: bool = False):
    """x (b,S,H,P); B,C (b,S,H,N) group-expanded; dt,da (b,S,H) f32.
    Returns (y (b,S,H,P) f32, h_last (b,H,P,N) f32)."""
    b, S, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0, (S, Q)
    nc = S // Q

    # kernel layout: head-major so one grid cell streams (Q,P)/(Q,N) tiles
    xt = x.transpose(0, 2, 1, 3)          # (b,H,S,P)
    Bt = B.transpose(0, 2, 1, 3)          # (b,H,S,N)
    Ct = C.transpose(0, 2, 1, 3)
    # per-token scalars as (b,H,1,S): a block's last two dims (1, Q) are
    # then legal TPU tiles (a (1,1,Q) block over (b,H,S) puts a 1 over H)
    dtt = dt.transpose(0, 2, 1)[:, :, None, :]
    dat = da.transpose(0, 2, 1)[:, :, None, :]

    kern = functools.partial(_kernel, Q=Q, P=P, N=N, nc=nc)
    y, h_last = pl.pallas_call(
        kern,
        grid=(b, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda i, h, c: (i, h, 0, c)),
            pl.BlockSpec((1, 1, 1, Q), lambda i, h, c: (i, h, 0, c)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda i, h, c: (i, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, H, S, P), f32),
            jax.ShapeDtypeStruct((b, H, P, N), f32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), f32)],
        interpret=interpret,
    )(xt, Bt, Ct, dtt, dat)
    return y.transpose(0, 2, 1, 3), h_last
