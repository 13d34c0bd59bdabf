"""Operations and bytes that the served work needs, from the model's sizes.

Every count is of what the work needs and never of what the program
happens to compute: idle pool rows, bucket and chunk padding, cached prefix
tokens and causal-masked halves are left out, so a share of a peak built
on these counts cannot pass 100% unless the time left out part of the work.
``m`` is a configuration's ``model`` dict (``configs/<config>.json``).
"""
from __future__ import annotations

BF16 = 2


def family(m: dict) -> str:
    return "ssd" if m.get("ssm_state") else "dense"


def _ssd_sizes(m: dict):
    H = m["ssm_expand"] * m["d_model"] // m["ssm_headdim"]
    return H, m["ssm_headdim"], m["ssm_ngroups"], m["ssm_state"], m["ssm_conv"]


def matmul_params(m: dict) -> int:
    """Weights a token passes through in the trunk's matrix multiplications
    (the embedding lookup and the unembedding excluded)."""
    D, L = m["d_model"], m["num_layers"]
    if family(m) == "ssd":
        H, P, G, N, _ = _ssd_sizes(m)
        return L * (D * (2 * H * P + 2 * G * N + H) + H * P * D)
    H, KV, hd, F = m["num_heads"], m["num_kv_heads"], m["head_dim"], m["d_ff"]
    return L * (D * (H + 2 * KV) * hd + H * hd * D + 3 * D * F)


def token_flops(m: dict, ctx: int) -> float:
    """FLOPs of one token through the trunk at a context of ``ctx``
    positions (itself included), without the unembedding."""
    f = 2.0 * matmul_params(m)
    L = m["num_layers"]
    if family(m) == "ssd":
        H, P, G, N, K = _ssd_sizes(m)
        # causal conv over x, B, C; state update and readout (2 FLOPs each)
        return f + L * (2 * K * (H * P + 2 * G * N) + 4 * H * P * N)
    return f + 4.0 * L * m["num_heads"] * m["head_dim"] * ctx


def prefill_flops(m: dict, start: int, stop: int) -> float:
    """Prompt positions [start, stop) prefilled (positions before ``start``
    come from the cache or an earlier chunk)."""
    n = stop - start
    if n <= 0:
        return 0.0
    f = n * token_flops(m, 0)
    if family(m) == "dense":    # sum of contexts start+1 .. stop
        ctx_sum = (start + 1 + stop) * n / 2
        f += 4.0 * m["num_layers"] * m["num_heads"] * m["head_dim"] * ctx_sum
    return f


def unembed_flops(m: dict) -> float:
    """One sampled token's logits over the vocabulary."""
    return 2.0 * m["d_model"] * m["vocab_size"]


def paged_decode_kernel(m: dict, ctx: int) -> tuple[float, float]:
    """(FLOPs, bytes) of decode attention for one row at ``ctx`` positions,
    over all layers: QK and PV, and reading the row's K and V once."""
    L, H, KV, hd = m["num_layers"], m["num_heads"], m["num_kv_heads"], m["head_dim"]
    return 4.0 * L * H * hd * ctx, 2.0 * L * ctx * KV * hd * BF16


def ssd_scan_kernel(m: dict, n: int, chunk: int) -> tuple[float, float]:
    """(FLOPs, bytes) of the chunked SSD scan over one row's ``n`` true
    prompt tokens, over all layers: the causal intra-chunk products
    (C.B and the weighted sum of x), the state's readout and update, and
    reading x, B, C (bfloat16) and dt, dA (float32) once per head."""
    H, P, _, N, _ = _ssd_sizes(m)
    L = m["num_layers"]
    full, rest = divmod(n, chunk)
    # sum over tokens of (offset in chunk + 1)
    tri = full * chunk * (chunk + 1) / 2 + rest * (rest + 1) / 2
    flops = L * H * (2.0 * (N + P) * tri + 4.0 * N * P * n)
    nbytes = L * H * n * ((P + 2 * N) * BF16 + 2 * 4)
    return flops, nbytes

