"""Production mesh construction.

Functions (never module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first init.

Target hardware: TPU v5e pods.  One pod = 16x16 = 256 chips; the multi-pod
configuration is 2 pods = 512 chips with the leading ``pod`` axis mapped to
DCN (inter-pod) links and ``data``/``model`` to intra-pod ICI.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

# v5e hardware constants used by the roofline analysis (benchmarks/roofline.py)
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link
HBM_BYTES = 16 * 2**30        # per chip


def make_production_mesh(*, multi_pod: bool = False):
    """Auto axes throughout: the sharding rules place activations with
    ``with_sharding_constraint``, which Explicit axes (``jax.make_mesh``'s
    default in the installed JAX) refuse."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    import math
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devs)} present; "
            "the dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import (launch/dryrun.py does)")
    return jax.make_mesh(shape, axes, devices=devs[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over whatever devices exist (CPU tests)."""
    n = len(jax.devices())
    assert data * model <= n, (data, model, n)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
