"""Plain float32 reference of a dense decoder (Qwen2 family): embedding,
pre-norm blocks of grouped-query attention with QKV bias and rotary
positions, a SwiGLU MLP, a final RMSNorm and a tied unembedding.

It imports nothing of the system under test.  ``init`` makes the weights
from a key, in the types they are served in; ``to_program`` lays the same
arrays out as the serving program's parameter tree; ``logits`` runs one
sequence through the whole model in float32 at the highest matmul precision
(``fp8=True``: every matmul input rounded to float8 e4m3, the control).

Departures from the published description: none in function.  RMSNorm
weights are stored as offsets (weight = 1 + offset), which is how the
serving program stores them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from fp8 import mm, q8

f32, bf16 = jnp.float32, jnp.bfloat16


def init(key, m: dict) -> dict:
    L, D, H, KV, hd, F, V = (m["num_layers"], m["d_model"], m["num_heads"],
                             m["num_kv_heads"], m["head_dim"], m["d_ff"],
                             m["vocab_size"])
    ks = iter(jax.random.split(key, 16))

    def normal(shape, std, dtype=bf16):
        return (jax.random.normal(next(ks), shape, f32) * std).astype(dtype)

    return {
        "embed": normal((V, D), D ** -0.5),
        "final_norm": normal((D,), 0.1, f32),
        "ln1": normal((L, D), 0.1, f32),
        "ln2": normal((L, D), 0.1, f32),
        "wq": normal((L, D, H, hd), D ** -0.5),
        "wk": normal((L, D, KV, hd), D ** -0.5),
        "wv": normal((L, D, KV, hd), D ** -0.5),
        "bq": normal((L, H, hd), 0.1),
        "bk": normal((L, KV, hd), 0.1),
        "bv": normal((L, KV, hd), 0.1),
        "wo": normal((L, H, hd, D), (H * hd) ** -0.5),
        "w_gate": normal((L, D, F), D ** -0.5),
        "w_up": normal((L, D, F), D ** -0.5),
        "w_down": normal((L, F, D), F ** -0.5),
    }


def to_program(w: dict) -> dict:
    return {
        "embed": {"embedding": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "blocks": {"m0": {
            "ln1": {"scale": w["ln1"]},
            "mixer": {k: w[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")},
            "ln2": {"scale": w["ln2"]},
            "mlp": {k: w[k] for k in ("w_gate", "w_up", "w_down")},
        }},
    }


def _rms(x, offset, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + offset)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=f32) * 2.0 / x.shape[-1])
    ang = pos[:, None].astype(f32) * inv                   # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def logits(w: dict, m: dict, tokens, at, *, fp8: bool = False):
    """tokens (S,) int32: one sequence; at (K,) int32: the positions whose
    next-token logits are wanted.  Returns (K, V) float32."""
    H, KV, hd, eps = m["num_heads"], m["num_kv_heads"], m["head_dim"], m["norm_eps"]
    S = tokens.shape[0]
    pos = jnp.arange(S)
    causal = pos[:, None] >= pos[None, :]
    x = w["embed"][tokens].astype(f32)

    def layer(x, lw):
        lw = jax.tree.map(lambda a: a.astype(f32), lw)
        h = _rms(x, lw["ln1"], eps)
        q = mm("sd,dhk->shk", h, lw["wq"], fp8) + lw["bq"]
        k = mm("sd,dhk->shk", h, lw["wk"], fp8) + lw["bk"]
        v = mm("sd,dhk->shk", h, lw["wv"], fp8) + lw["bv"]
        q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
        q = q.reshape(S, KV, H // KV, hd)
        s = mm("sgrk,tgk->grst", q, k, fp8) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = mm("grst,tgk->sgrk", p, v, fp8).reshape(S, H, hd)
        x = x + mm("shk,hkd->sd", o, lw["wo"], fp8)
        h = _rms(x, lw["ln2"], eps)
        g = jax.nn.silu(mm("sd,df->sf", h, lw["w_gate"], fp8))
        u = mm("sd,df->sf", h, lw["w_up"], fp8)
        return x + mm("sf,fd->sd", g * u, lw["w_down"], fp8), None

    layers = {k: w[k] for k in ("ln1", "ln2", "wq", "wk", "wv", "bq", "bk",
                                "bv", "wo", "w_gate", "w_up", "w_down")}
    x, _ = jax.lax.scan(layer, x, layers)
    h = _rms(x[at], w["final_norm"], eps)
    emb = w["embed"].astype(f32)
    if fp8:
        emb = q8(emb)
    return mm("kd,vd->kv", h, emb, fp8)
