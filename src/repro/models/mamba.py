"""Mamba-2 / SSD (state-space duality) layer [arXiv:2405.21060].

Chunked SSD for train/prefill (intra-chunk quadratic term + inter-chunk
state recurrence via lax.scan), exact recurrent step for decode.  States are
fp32; matmuls bf16 with fp32 accumulation.  Heads shard over the model axis.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import _noop_shd
from repro.models.params import ParamSpec

f32 = jnp.float32


def ssd_specs(cfg: ModelConfig) -> dict:
    D = cfg.d_model
    H, P, G, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_ngroups, cfg.ssm_state
    K = cfg.ssm_conv
    return {
        "w_z": ParamSpec((D, H, P), ("embed", "heads", "qkv")),
        "w_x": ParamSpec((D, H, P), ("embed", "heads", "qkv")),
        "w_B": ParamSpec((D, G, N), ("embed", "groups", "state")),
        "w_C": ParamSpec((D, G, N), ("embed", "groups", "state")),
        "w_dt": ParamSpec((D, H), ("embed", "heads")),
        "conv_x": ParamSpec((H, P, K), ("heads", "qkv", "conv"), init="normal", scale=0.5),
        "conv_B": ParamSpec((G, N, K), ("groups", "state", "conv"), init="normal", scale=0.5),
        "conv_C": ParamSpec((G, N, K), ("groups", "state", "conv"), init="normal", scale=0.5),
        "A_log": ParamSpec((H,), ("heads",), dtype=f32, init="zeros"),
        "dt_bias": ParamSpec((H,), ("heads",), dtype=f32, init="zeros"),
        "D_skip": ParamSpec((H,), ("heads",), dtype=f32, init="ones"),
        "norm": {"scale": ParamSpec((H, P), ("heads", "qkv"), dtype=f32, init="zeros")},
        "w_out": ParamSpec((H, P, D), ("heads", "qkv", "embed")),
    }


def ssm_cache_specs(cfg: ModelConfig, batch: int) -> dict:
    H, P, G, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_ngroups, cfg.ssm_state
    K = cfg.ssm_conv
    return {
        "h": ParamSpec((batch, H, P, N), ("batch", "heads", "qkv", "state"), dtype=f32, init="zeros"),
        "conv_x": ParamSpec((batch, K - 1, H, P), ("batch", "conv", "heads", "qkv"), init="zeros"),
        "conv_B": ParamSpec((batch, K - 1, G, N), ("batch", "conv", "groups", "state"), init="zeros"),
        "conv_C": ParamSpec((batch, K - 1, G, N), ("batch", "conv", "groups", "state"), init="zeros"),
    }


def _causal_conv(x, w):
    """Depthwise causal conv along seq.  x: (B,S,...chan), w: (...chan,K)."""
    K = w.shape[-1]
    pad = [(0, 0)] * x.ndim
    pad[1] = (K - 1, 0)
    xp = jnp.pad(x, pad)
    out = sum(xp[:, j:j + x.shape[1]] * w[..., j] for j in range(K))
    return out


def _conv_step(state, xt, w):
    """state: (B,K-1,...), xt: (B,...) -> (y (B,...), new_state)."""
    K = w.shape[-1]
    full = jnp.concatenate([state, xt[:, None]], axis=1)  # (B,K,...)
    y = sum(full[:, j] * w[..., j] for j in range(K))
    return y, full[:, 1:]


def _gated_norm(p_norm, y, z, eps):
    y = y * jax.nn.silu(z.astype(f32))
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)  # over P, per head
    y = y * jax.lax.rsqrt(var + eps)
    return y * (p_norm["scale"] + 1.0)


def _project(p, x, cfg):
    z = jnp.einsum("bsd,dhp->bshp", x, p["w_z"])
    xr = jnp.einsum("bsd,dhp->bshp", x, p["w_x"])
    Br = jnp.einsum("bsd,dgn->bsgn", x, p["w_B"])
    Cr = jnp.einsum("bsd,dgn->bsgn", x, p["w_C"])
    dt = jnp.einsum("bsd,dh->bsh", x, p["w_dt"]).astype(f32)
    return z, xr, Br, Cr, dt


def _expand_heads(t, H):
    """(B,...,G,N) -> (B,...,H,N) repeating each group H//G times."""
    G = t.shape[-2]
    rep = H // G
    return jnp.repeat(t, rep, axis=-2) if rep > 1 else t


def _ssd_scan_chunks(xc, Bc, Cc, da, dt, h0, H: int, Q: int):
    """Chunked SSD scan over conv-activated projections.

    xc: (B,S,H,P), Bc/Cc: (B,S,G,N), da/dt: (B,S,H), h0: (B,H,P,N) initial
    state (zeros for a fresh sequence).  S must be a multiple of Q.
    Returns (h_last, y (B,S,H,P) f32).
    """
    B, S = xc.shape[:2]
    nc = S // Q

    def chunkify(t):  # (B,S,...) -> (nc,B,Q,...)
        return t.reshape(B, nc, Q, *t.shape[2:]).swapaxes(0, 1)

    xq, Bq, Cq, daq, dtq = map(chunkify, (xc, Bc, Cc, da, dt))

    def body(h, inp):
        xk, Bk, Ck, dak, dtk = inp  # (B,Q,H,P) (B,Q,G,N) (B,Q,G,N) (B,Q,H) (B,Q,H)
        cum = jnp.cumsum(dak, axis=1)  # (B,Q,H)
        seg = cum[:, :, None, :] - cum[:, None, :, :]  # (B,q,t,H) = cum_q - cum_t
        tri = jnp.tril(jnp.ones((Q, Q), bool))
        L = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)  # (B,q,t,H)
        CB = jnp.einsum("bqgn,btgn->bqtg", Ck, Bk, preferred_element_type=f32)
        M = _expand_heads(CB, H) * L
        xdt = (xk.astype(f32) * dtk[..., None])
        y_in = jnp.einsum("bqth,bthp->bqhp", M.astype(xk.dtype), xdt.astype(xk.dtype),
                          preferred_element_type=f32)
        Ch = _expand_heads(Ck, H)  # (B,Q,H,N)
        y_off = jnp.einsum("bqhn,bhpn->bqhp", Ch.astype(xk.dtype), h.astype(xk.dtype),
                           preferred_element_type=f32)
        y_off = y_off * jnp.exp(cum)[..., None]  # decay from chunk start to q
        # state update
        wt = jnp.exp(cum[:, -1:, :] - cum)  # (B,Q,H)
        Bh = _expand_heads(Bk, H)           # (B,Q,H,N)
        h_new = h * jnp.exp(cum[:, -1, :])[:, :, None, None] + jnp.einsum(
            "bthn,bthp->bhpn", (Bh.astype(f32) * wt[..., None]).astype(xk.dtype),
            xdt.astype(xk.dtype), preferred_element_type=f32)
        return h_new, (y_in + y_off)

    h_last, ys = jax.lax.scan(body, h0, (xq, Bq, Cq, daq, dtq))
    return h_last, ys.swapaxes(0, 1).reshape(B, S, *ys.shape[3:])


def ssd_apply_full(p, x, cfg: ModelConfig, shd=_noop_shd, *, want_state: bool = False,
                   true_len=None, kernel: str = "ref"):
    """Full-sequence SSD.  x: (B,S,D) -> (y, cache|None).

    Non-divisible S is front-padded with zeros to a chunk multiple: leading
    zero tokens are exact no-ops for the causal conv (matches zero left-pad)
    and contribute nothing to the state (x=0 after silu(conv(0))=0), so both
    the sliced outputs and the final state are unchanged.

    ``true_len`` (B,) int32 supports right-padded prompts: pad positions get
    dt=0 and x=0, making them exact no-ops for the state recurrence; the conv
    tail cache is gathered at per-row valid positions.

    ``kernel`` (see :func:`repro.kernels.kernel_mode`): ``"ref"`` scans in
    jnp, ``"pallas"``/``"interpret"`` run the SSD scan kernel.
    """
    B, S_in, D = x.shape
    Q = min(cfg.ssm_chunk, S_in)
    lead = (-S_in) % Q
    if lead:
        x = jnp.pad(x, ((0, 0), (lead, 0), (0, 0)))
    B, S, D = x.shape
    H, P, G, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_ngroups, cfg.ssm_state

    z, xr, Br, Cr, dt = _project(p, x, cfg)
    xc = jax.nn.silu(_causal_conv(xr, p["conv_x"]))
    Bc = jax.nn.silu(_causal_conv(Br, p["conv_B"]))
    Cc = jax.nn.silu(_causal_conv(Cr, p["conv_C"]))
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (B,S,H) f32
    if true_len is not None:
        seq_idx = jnp.arange(S, dtype=jnp.int32)[None, :] - lead  # (1,S)
        valid = seq_idx < true_len[:, None]                       # (B,S)
        dt = jnp.where(valid[..., None], dt, 0.0)
        xc = jnp.where(valid[..., None, None], xc, 0.0)
    a = -jnp.exp(p["A_log"].astype(f32))     # (H,)
    da = dt * a                              # (B,S,H) <= 0

    if kernel != "ref":
        from repro.kernels.ssd_scan.ops import ssd_chunked_scan
        Bh = _expand_heads(Bc, H)
        Ch = _expand_heads(Cc, H)
        y, h_last = ssd_chunked_scan(xc, Bh, Ch, dt, da, chunk=Q,
                                     interpret=kernel == "interpret")
    else:
        h0 = jnp.zeros((B, H, P, N), f32)
        h_last, y = _ssd_scan_chunks(xc, Bc, Cc, da, dt, h0, H, Q)
    y = y + p["D_skip"][:, None] * xc.astype(f32)
    y = _gated_norm(p["norm"], y, z, cfg.norm_eps)
    y = shd(y.astype(x.dtype), ("batch", "act_seq", "heads", "qkv"))
    out = jnp.einsum("bshp,hpd->bsd", y, p["w_out"])
    if lead:
        out = out[:, lead:]
    if not want_state:
        return out, None
    K = cfg.ssm_conv
    assert S >= K - 1, "prefill shorter than conv receptive field"
    if true_len is None:
        def tail(t):
            return t[:, S - (K - 1):]
    else:
        # per-row last K-1 *valid* raw projections (pre-conv) for the decode
        # conv state; rows assumed to have true_len >= K-1
        idx = lead + true_len[:, None] - (K - 1) + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        idx = jnp.maximum(idx, 0)

        def tail(t):
            ix = idx.reshape(B, K - 1, *([1] * (t.ndim - 2)))
            return jnp.take_along_axis(t, ix, axis=1)
    cache = {
        "h": h_last,
        "conv_x": tail(xr).astype(x.dtype),
        "conv_B": tail(Br).astype(x.dtype),
        "conv_C": tail(Cr).astype(x.dtype),
    }
    return out, cache


def ssd_apply_chunk(p, x, cache, cfg: ModelConfig, shd=_noop_shd, *, true_len):
    """One chunked-prefill step with carried state.

    x: (B,C,D) right-padded chunk of a longer prompt; ``cache`` holds the SSM
    state after the previous chunks (zeros for the first chunk); ``true_len``
    (B,) int32 counts the valid tokens of this chunk (0 == row is a no-op:
    its returned cache row equals the input row).  Matches ssd_apply_full on
    the concatenated sequence: pad positions get dt=0 / x=0 (exact state
    no-ops) and the causal conv reads the cached last K-1 raw projections
    instead of zero left-padding.  Returns (y (B,C,D), new cache).
    """
    B, C, D = x.shape
    H, P, G, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_ngroups, cfg.ssm_state
    K = cfg.ssm_conv
    z, xr, Br, Cr, dt = _project(p, x, cfg)
    # conv over [cached raw tail (K-1) | chunk]; outputs at concat positions
    # >= K-1 see the true history, so slicing [K-1:] is exact for the chunk
    xcat = jnp.concatenate([cache["conv_x"].astype(xr.dtype), xr], axis=1)
    Bcat = jnp.concatenate([cache["conv_B"].astype(Br.dtype), Br], axis=1)
    Ccat = jnp.concatenate([cache["conv_C"].astype(Cr.dtype), Cr], axis=1)
    xc = jax.nn.silu(_causal_conv(xcat, p["conv_x"])[:, K - 1:])
    Bc = jax.nn.silu(_causal_conv(Bcat, p["conv_B"])[:, K - 1:])
    Cc = jax.nn.silu(_causal_conv(Ccat, p["conv_C"])[:, K - 1:])
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (B,C,H) f32
    valid = jnp.arange(C, dtype=jnp.int32)[None, :] < true_len[:, None]
    dt = jnp.where(valid[..., None], dt, 0.0)
    xc = jnp.where(valid[..., None, None], xc, 0.0)
    a = -jnp.exp(p["A_log"].astype(f32))
    da = dt * a

    Q = min(cfg.ssm_chunk, C)
    lead = (-C) % Q
    if lead:  # zero front-pad to a chunk multiple: dt=0/x=0 state no-ops
        def pad(t):
            return jnp.pad(t, ((0, 0), (lead, 0)) + ((0, 0),) * (t.ndim - 2))
        xc_p, Bc_p, Cc_p, da_p, dt_p, z_p = map(pad, (xc, Bc, Cc, da, dt, z))
    else:
        xc_p, Bc_p, Cc_p, da_p, dt_p, z_p = xc, Bc, Cc, da, dt, z
    h_last, y = _ssd_scan_chunks(xc_p, Bc_p, Cc_p, da_p, dt_p,
                                 cache["h"].astype(f32), H, Q)
    y = y + p["D_skip"][:, None] * xc_p.astype(f32)
    y = _gated_norm(p["norm"], y, z_p, cfg.norm_eps)
    y = shd(y.astype(x.dtype), ("batch", "act_seq", "heads", "qkv"))
    out = jnp.einsum("bshp,hpd->bsd", y, p["w_out"])
    if lead:
        out = out[:, lead:]

    # new conv tail: last K-1 raw projections ending at the last valid token.
    # xcat index of the last valid token is (K-1) + true_len - 1, so the tail
    # is xcat[true_len : true_len + K-1]; true_len == 0 reproduces the old
    # cached tail exactly (the no-op row guarantee).
    idx = true_len[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]

    def tail(t):
        ix = idx.reshape(B, K - 1, *([1] * (t.ndim - 2)))
        return jnp.take_along_axis(t, ix, axis=1)

    new_cache = {
        "h": h_last,
        "conv_x": tail(xcat).astype(cache["conv_x"].dtype),
        "conv_B": tail(Bcat).astype(cache["conv_B"].dtype),
        "conv_C": tail(Ccat).astype(cache["conv_C"].dtype),
    }
    return out, new_cache


def ssd_apply_decode(p, x, cache, cfg: ModelConfig, shd=_noop_shd):
    """One-token recurrent step.  x: (B,1,D) -> (y (B,1,D), new cache)."""
    H, P, G, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_ngroups, cfg.ssm_state
    z, xr, Br, Cr, dt = _project(p, x, cfg)
    xt, nconv_x = _conv_step(cache["conv_x"], xr[:, 0], p["conv_x"])
    Bt, nconv_B = _conv_step(cache["conv_B"], Br[:, 0], p["conv_B"])
    Ct, nconv_C = _conv_step(cache["conv_C"], Cr[:, 0], p["conv_C"])
    xt, Bt, Ct = jax.nn.silu(xt), jax.nn.silu(Bt), jax.nn.silu(Ct)
    dt = jax.nn.softplus(dt[:, 0] + p["dt_bias"])  # (B,H)
    a = -jnp.exp(p["A_log"].astype(f32))
    da = jnp.exp(dt * a)  # (B,H)
    Bh = _expand_heads(Bt, H).astype(f32)  # (B,H,N)
    Ch = _expand_heads(Ct, H).astype(f32)
    xdt = xt.astype(f32) * dt[..., None]   # (B,H,P)
    h = cache["h"] * da[:, :, None, None] + jnp.einsum("bhn,bhp->bhpn", Bh, xdt)
    y = jnp.einsum("bhn,bhpn->bhp", Ch, h) + p["D_skip"][:, None] * xt.astype(f32)
    y = _gated_norm(p["norm"], y, z[:, 0], cfg.norm_eps)
    out = jnp.einsum("bhp,hpd->bd", y.astype(x.dtype), p["w_out"])[:, None]
    new_cache = {"h": h, "conv_x": nconv_x.astype(x.dtype), "conv_B": nconv_B.astype(x.dtype),
                 "conv_C": nconv_C.astype(x.dtype)}
    return out, new_cache
