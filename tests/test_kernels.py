"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.ssd_scan.ops import ssd_chunked_scan
from repro.kernels.ssd_scan.ref import ssd_scan_ref
from repro.serving.kv_cache import PagedAllocator

pytestmark = pytest.mark.slow  # pallas interpret-mode kernel sweeps

RNG = np.random.default_rng(7)


# ------------------------------------------------------------ flash attn
@pytest.mark.parametrize("B,Sq,Skv,H,KV,d,window", [
    (2, 256, 256, 4, 2, 64, 0),
    (1, 128, 384, 4, 1, 64, 0),        # kv longer than q (right-aligned)
    (2, 256, 256, 8, 8, 32, 64),       # sliding window, MHA
    (1, 200, 200, 4, 2, 64, 0),        # non-block-multiple (padding path)
    (1, 128, 128, 6, 2, 128, 32),      # GQA 3x, window
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, Sq, Skv, H, KV, d, window, dtype):
    q = jnp.asarray(RNG.normal(size=(B, Sq, H, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Skv, KV, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Skv, KV, d)), dtype)
    out = attention(q, k, v, causal=True, window=window, interpret=True)
    ref = attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=True,
                        window=window).transpose(0, 2, 1, 3)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


# ------------------------------------------------------------ paged attn
@pytest.mark.parametrize("B,H,KV,d,nb,bs,maxb", [
    (3, 8, 2, 64, 16, 16, 6),
    (2, 4, 4, 32, 8, 8, 4),
    (1, 8, 1, 128, 32, 16, 8),
    (4, 2, 2, 64, 12, 32, 3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention(B, H, KV, d, nb, bs, maxb, dtype):
    alloc = PagedAllocator(nb, bs)
    ctx = RNG.integers(max(bs // 2, 1), maxb * bs, B)
    table = np.full((B, maxb), -1, np.int32)
    for b in range(B):
        blocks = alloc.allocate(b, int(ctx[b]))
        assert blocks is not None
        table[b, :len(blocks)] = blocks
    q = jnp.asarray(RNG.normal(size=(B, H, d)), dtype)
    kp = jnp.asarray(RNG.normal(size=(nb, bs, KV, d)), dtype)
    vp = jnp.asarray(RNG.normal(size=(nb, bs, KV, d)), dtype)
    tb, cl = jnp.asarray(table), jnp.asarray(ctx, jnp.int32)
    out = paged_decode_attention(q, kp, vp, tb, cl, interpret=True)
    ref = paged_attention_ref(q, kp, vp, tb, cl)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


# ------------------------------------------------------------ ssd scan
@pytest.mark.parametrize("b,S,H,P,N,Q", [
    (2, 128, 4, 32, 16, 32),
    (1, 256, 8, 64, 32, 64),
    (2, 64, 2, 16, 128, 64),
    (1, 512, 2, 64, 64, 128),
])
def test_ssd_scan(b, S, H, P, N, Q):
    x = jnp.asarray(RNG.normal(size=(b, S, H, P)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(b, S, H, N)), jnp.float32) * 0.5
    C = jnp.asarray(RNG.normal(size=(b, S, H, N)), jnp.float32) * 0.5
    dt = jnp.asarray(RNG.uniform(0.01, 0.2, size=(b, S, H)), jnp.float32)
    da = -dt * jnp.asarray(RNG.uniform(0.5, 2.0, size=(b, S, H)), jnp.float32)
    y, h = ssd_chunked_scan(x, B, C, dt, da, chunk=Q, interpret=True)
    yr, hr = ssd_scan_ref(x, B, C, dt, da, chunk=Q)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=2e-4)


def test_ssd_kernel_matches_sequential_recurrence():
    """The chunked kernel must equal the literal per-token recurrence."""
    b, S, H, P, N, Q = 1, 64, 2, 8, 4, 16
    x = jnp.asarray(RNG.normal(size=(b, S, H, P)), jnp.float32)
    B = jnp.asarray(RNG.normal(size=(b, S, H, N)), jnp.float32)
    C = jnp.asarray(RNG.normal(size=(b, S, H, N)), jnp.float32)
    dt = jnp.asarray(RNG.uniform(0.05, 0.3, size=(b, S, H)), jnp.float32)
    da = -dt
    y, h_last = ssd_chunked_scan(x, B, C, dt, da, chunk=Q, interpret=True)
    hs = np.zeros((b, H, P, N), np.float32)
    ys = np.zeros((b, S, H, P), np.float32)
    for t in range(S):
        decay = np.exp(np.asarray(da[:, t]))[..., None, None]
        outer = np.einsum("bhn,bhp->bhpn", np.asarray(B[:, t]),
                          np.asarray(x[:, t] * dt[:, t, :, None]))
        hs = hs * decay + outer
        ys[:, t] = np.einsum("bhn,bhpn->bhp", np.asarray(C[:, t]), hs)
    np.testing.assert_allclose(np.asarray(y), ys, atol=1e-3)
    np.testing.assert_allclose(np.asarray(h_last), hs, atol=1e-3)


# --------------------------------------------------- model-level pallas
@pytest.mark.parametrize("arch", ["qwen2-0.5b", "mamba2-780m", "mixtral-8x7b"])
def test_model_with_pallas_matches_ref(arch, monkeypatch):
    """The model's kernel path (forced to interpret mode through the one
    platform choice, kernels.kernel_mode) against its jnp path."""
    from repro import kernels
    from repro.configs import get_config
    from repro.models import params as P
    from repro.models.lm import make_model
    cfg = get_config(arch + "-smoke")
    model = make_model(cfg)
    params = P.init(jax.random.PRNGKey(0), model.param_specs())
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                              cfg.vocab_size).astype(jnp.int32)

    def prefill(mode):
        monkeypatch.setattr(kernels, "kernel_mode", lambda: mode)
        return jax.jit(lambda p, b: model.prefill(p, b, 48))(
            params, {"tokens": toks})[0]

    lr, lp = prefill("ref"), prefill("interpret")
    rel = float(jnp.max(jnp.abs(lr - lp))) / (float(jnp.max(jnp.abs(lr))) + 1e-9)
    # MoE archs: bf16 noise can flip router top-k, so tolerance is looser
    assert rel < (6e-2 if cfg.num_experts else 2e-2), (arch, rel)
