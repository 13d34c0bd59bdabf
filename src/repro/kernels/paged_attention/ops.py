"""jit'd wrapper for paged decode attention."""
from __future__ import annotations

import functools

import jax

from repro.kernels.paged_attention.kernel import paged_attention


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, block_table, context_len, *,
                           interpret: bool = False):
    return paged_attention(q, k_pages, v_pages, block_table, context_len,
                           interpret=interpret)
