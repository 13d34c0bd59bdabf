"""Plain float32 reference of a Mamba-2 (SSD) language model: embedding,
pre-norm blocks of one Mamba-2 mixer each, a final RMSNorm and a tied
unembedding.  The recurrence runs token by token (no chunking), so it shares
no algorithm with the chunked scan it checks.

It imports nothing of the system under test.  ``init`` makes the weights
from a key, in the types they are served in; ``to_program`` lays the same
arrays out as the serving program's parameter tree; ``logits`` runs one
sequence through the whole model in float32 at the highest matmul precision
(``fp8=True``: every matmul input rounded to float8 e4m3, the control).

Departures from the published Mamba-2, which the serving program makes and
this reference therefore makes too: the input projections are separate
matrices (z, x, B, C, dt) rather than one fused in_proj; the depthwise
causal convolutions over x, B and C have no bias; the gated RMSNorm before
the output projection normalises each head's 64 channels rather than all
3072; RMSNorm weights are stored as offsets (weight = 1 + offset).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from fp8 import mm, q8

f32, bf16 = jnp.float32, jnp.bfloat16


def _sizes(m):
    H = m["ssm_expand"] * m["d_model"] // m["ssm_headdim"]
    return (m["num_layers"], m["d_model"], H, m["ssm_headdim"],
            m["ssm_ngroups"], m["ssm_state"], m["ssm_conv"], m["vocab_size"])


def init(key, m: dict) -> dict:
    L, D, H, P, G, N, K, V = _sizes(m)
    ks = iter(jax.random.split(key, 24))

    def normal(shape, std, dtype=bf16):
        return (jax.random.normal(next(ks), shape, f32) * std).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, f32, lo, hi)

    dt0 = jnp.exp(uniform((L, H), jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "embed": normal((V, D), D ** -0.5),
        "final_norm": normal((D,), 0.1, f32),
        "ln1": normal((L, D), 0.1, f32),
        "w_z": normal((L, D, H, P), D ** -0.5),
        "w_x": normal((L, D, H, P), D ** -0.5),
        "w_B": normal((L, D, G, N), D ** -0.5),
        "w_C": normal((L, D, G, N), D ** -0.5),
        "w_dt": normal((L, D, H), D ** -0.5),
        "conv_x": normal((L, H, P, K), 0.5),
        "conv_B": normal((L, G, N, K), 0.5),
        "conv_C": normal((L, G, N, K), 0.5),
        "A_log": jnp.log(uniform((L, H), 1.0, 16.0)),
        "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),       # softplus^-1(dt0)
        "D_skip": 1.0 + normal((L, H), 0.1, f32),
        "norm": normal((L, H, P), 0.1, f32),
        "w_out": normal((L, H, P, D), (H * P) ** -0.5),
    }


def to_program(w: dict) -> dict:
    L, D = w["ln1"].shape
    mixer = {k: w[k] for k in ("w_z", "w_x", "w_B", "w_C", "w_dt", "conv_x",
                               "conv_B", "conv_C", "A_log", "dt_bias",
                               "D_skip", "w_out")}
    mixer["norm"] = {"scale": w["norm"]}
    # the program's block carries an MLP of width 0 after a second norm
    empty = jnp.zeros((L, D, 0), bf16)
    return {
        "embed": {"embedding": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "blocks": {"m0": {
            "ln1": {"scale": w["ln1"]},
            "mixer": mixer,
            "ln2": {"scale": jnp.zeros((L, D), f32)},
            "mlp": {"w_gate": empty, "w_up": empty,
                    "w_down": jnp.zeros((L, 0, D), bf16)},
        }},
    }


def _rms(x, offset, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + offset)


def _conv(x, w):
    """Depthwise causal convolution along the first axis: x (S, ...chan),
    w (...chan, K); y[t] = sum_j x[t - K + 1 + j] w[j]."""
    K = w.shape[-1]
    xp = jnp.concatenate([jnp.zeros((K - 1, *x.shape[1:]), x.dtype), x])
    return sum(xp[j:j + x.shape[0]] * w[..., j] for j in range(K))


def logits(w: dict, m: dict, tokens, at, *, fp8: bool = False):
    """tokens (S,) int32: one sequence; at (K,) int32: the positions whose
    next-token logits are wanted.  Returns (K, V) float32."""
    L, D, H, P, G, N, K, V = _sizes(m)
    eps = m["norm_eps"]
    x = w["embed"][tokens].astype(f32)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(f32), lw)
        h = _rms(x, lw["ln1"], eps)
        z = mm("sd,dhp->shp", h, lw["w_z"], fp8)
        xs = jax.nn.silu(_conv(mm("sd,dhp->shp", h, lw["w_x"], fp8), lw["conv_x"]))
        Bs = jax.nn.silu(_conv(mm("sd,dgn->sgn", h, lw["w_B"], fp8), lw["conv_B"]))
        Cs = jax.nn.silu(_conv(mm("sd,dgn->sgn", h, lw["w_C"], fp8), lw["conv_C"]))
        dt = jax.nn.softplus(mm("sd,dh->sh", h, lw["w_dt"], fp8) + lw["dt_bias"])
        A = -jnp.exp(lw["A_log"])
        Bh = jnp.repeat(Bs, H // G, axis=1)                  # (S, H, N)
        Ch = jnp.repeat(Cs, H // G, axis=1)

        def step(state, inp):                                # state (H, P, N)
            xt, Bt, Ct, dtt = inp
            state = (state * jnp.exp(dtt * A)[:, None, None]
                     + (dtt[:, None] * xt)[:, :, None] * Bt[:, None, :])
            return state, jnp.einsum("hpn,hn->hp", state, Ct,
                                     precision=jax.lax.Precision.HIGHEST)

        _, y = jax.lax.scan(step, jnp.zeros((H, P, N), f32), (xs, Bh, Ch, dt))
        y = (y + lw["D_skip"][:, None] * xs) * jax.nn.silu(z)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps) * (1.0 + lw["norm"])
        return x + mm("shp,hpd->sd", y, lw["w_out"], fp8), None

    layers = {k: w[k] for k in ("ln1", "w_z", "w_x", "w_B", "w_C", "w_dt",
                                "conv_x", "conv_B", "conv_C", "A_log",
                                "dt_bias", "D_skip", "norm", "w_out")}
    x, _ = jax.lax.scan(layer, x, layers)
    h = _rms(x[at], w["final_norm"], eps)
    emb = w["embed"].astype(f32)
    if fp8:
        emb = q8(emb)
    return mm("kd,vd->kv", h, emb, fp8)
