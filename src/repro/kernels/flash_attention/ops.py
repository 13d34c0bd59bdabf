"""jit'd wrapper: model layout <-> kernel layout, padding, dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), pad


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def attention(q, k, v, *, causal: bool = True, window: int = 0,
              bq: int = 128, bk: int = 128, interpret: bool = False):
    """Model layout: q (B,Sq,H,d); k,v (B,Skv,KV,d) -> (B,Sq,H,d).

    Pads Sq/Skv to block multiples; pad keys are masked out by the causal
    test (pad kpos > every real qpos) so results are exact after slicing.
    """
    B, Sq, H, d = q.shape
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    qt, pq = _pad_to(qt, 2, bq)
    kt, pk = _pad_to(kt, 2, bk)
    vt, _ = _pad_to(vt, 2, bk)
    # padded q rows sit at positions > every key => fully-masked rows under
    # causal; harmless garbage rows get sliced off.  padded k rows sit at
    # kpos > qpos of all real rows => masked.  (causal=False with padding is
    # rejected: encoder attention goes through the ref path.)
    assert causal or (pq == 0 and pk == 0), "non-causal padding unsupported"
    out = flash_attention(qt, kt, vt, causal=causal, window=window,
                          bq=bq, bk=bk, interpret=interpret)
    out = out[:, :, :Sq] if pq else out
    return out.transpose(0, 2, 1, 3)
