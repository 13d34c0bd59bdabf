"""Pallas TPU kernels for the serving data path's compute hot spots.

Each subpackage ships kernel.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), ops.py (jit'd wrapper: model layout, padding) and ref.py (pure-jnp
oracle).  The model runs the compiled kernels on TPU and its jnp paths
everywhere else; :func:`kernel_mode` makes that choice.  Tests drive the
kernels on CPU in interpret mode (tests/test_kernels.py), and
tests/test_tpu_compile.py compiles them for a described v5e chip.

flash_attention/   FA2-style blocked prefill attention (causal, GQA, SWA)
paged_attention/   decode attention over block-table paged KV
                   (scalar-prefetch grid: PagedAttention adapted to TPU DMA)
ssd_scan/          Mamba-2 SSD chunked scan (MXU intra-chunk + VMEM state)
"""
from __future__ import annotations

import jax


def kernel_mode() -> str:
    """How the model runs its kernel hot spots in this process: ``"pallas"``
    (compiled Pallas kernels) on TPU, ``"ref"`` (the jnp paths) on any other
    platform.  ``"interpret"`` (Pallas in interpret mode) is never chosen
    here — tests that check the kernels inside the model on CPU patch this
    function to return it."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"
