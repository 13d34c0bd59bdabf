"""Compile the serving kernels for a described TPU v5e chip at real widths.

Nothing runs: the TPU compiler lowers each kernel for a chip that is
described, not attached, and raises what the chip's compiler would raise
(illegal block shapes, VMEM overuse, unsupported ops).  Each compiled
program must contain the Pallas kernel (``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, so every test worker must
collect these tests and only the one that runs them touches it.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention.ops import attention
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.ssd_scan.ops import ssd_chunked_scan


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back from the persistent
    # cache without a chip; keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return fn.lower(*args).compile().as_text()


def test_paged_decode_compiles_at_qwen2_widths(one_chip):
    cfg = get_config("qwen2-0.5b")
    B, bs, max_len = 8, 16, 2048
    max_blk = max_len // bs
    pool = (B * max_blk, bs, cfg.num_kv_heads, cfg.head_dim)
    hlo = _compile(paged_decode_attention, [
        ((B, cfg.num_heads, cfg.head_dim), jnp.bfloat16),
        (pool, jnp.bfloat16), (pool, jnp.bfloat16),
        ((B, max_blk), jnp.int32), ((B,), jnp.int32)], one_chip)
    assert "tpu_custom_call" in hlo


def test_flash_prefill_compiles_at_qwen2_widths(one_chip):
    cfg = get_config("qwen2-0.5b")
    B, S = 4, 512
    q = ((B, S, cfg.num_heads, cfg.head_dim), jnp.bfloat16)
    kv = ((B, S, cfg.num_kv_heads, cfg.head_dim), jnp.bfloat16)
    hlo = _compile(attention, [q, kv, kv], one_chip)
    assert "tpu_custom_call" in hlo


def test_ssd_scan_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-780m")
    H, P, N = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    b, S = 1, 2 * cfg.ssm_chunk

    def scan(x, B, C, dt, da):
        return ssd_chunked_scan(x, B, C, dt, da, chunk=cfg.ssm_chunk)

    hlo = _compile(jax.jit(scan), [
        ((b, S, H, P), jnp.bfloat16), ((b, S, H, N), jnp.bfloat16),
        ((b, S, H, N), jnp.bfloat16), ((b, S, H), jnp.float32),
        ((b, S, H), jnp.float32)], one_chip)
    assert "tpu_custom_call" in hlo
