"""Matmuls of the plain references: float32 at the highest precision, or,
for the control, with both inputs rounded to float8 e4m3 (per-tensor scale
for weights, per-row scale for activations) and the products summed in
float32 — the next precision below the bfloat16 the models are served in."""
from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0


def q8(x, axis=None):
    """Round to float8 e4m3 under a scale that maps the largest magnitude
    (per tensor, or along ``axis``) to the format's largest value."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(spec: str, a, b, fp8: bool):
    if fp8:
        a, b = q8(a, axis=-1), q8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
