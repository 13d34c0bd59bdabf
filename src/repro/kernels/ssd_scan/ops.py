"""jit'd wrapper for the SSD scan kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.ssd_scan.kernel import ssd_scan


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunked_scan(x, B, C, dt, da, *, chunk: int = 128,
                     interpret: bool = False):
    return ssd_scan(x, B, C, dt, da, chunk=chunk, interpret=interpret)
