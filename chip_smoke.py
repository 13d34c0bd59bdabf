#!/usr/bin/env python3
"""Bring-up smoke on a TPU: qwen2-0.5b at its published widths, served
through the registry path, with the compiled Pallas kernels checked against
their jnp references on the chip.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four one-chip replicas, cross-chip migration

One chip: kernels vs references (paged decode and flash prefill at
qwen2-0.5b widths, the SSD scan at mamba2-780m widths), then 8 greedy
requests through ``CompletionsAPI`` -> ``EndpointRegistry`` ->
``Orchestrator`` -> ``InferenceEngine`` on the paged backend: synchronous
and streamed, one prompt longer than the largest bucket (chunked prefill),
two sharing a long prefix (prefix-cache hit), and the compiled decode and
prefill programs checked for the Pallas kernel.

``--chips 4``: four qwen2-0.5b replicas on four devices behind the
``directory`` router, a shared-prefix two-tenant batch, and a live paged
migration from one chip to another whose greedy output must equal the same
request served unmigrated.

Weights are random, made from a seed.  Every check raises on failure.  The
last line of standard output is one JSON object, printed only when every
phase passed: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Without a TPU the script exits non-zero before doing anything.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "qwen2-0.5b"
SSM_ARCH = "mamba2-780m"
SEED = 0
MAX_TOKENS = 32
ENGINE = dict(kv_backend="paged", capacity=8, max_len=2048, buckets=(64, 512),
              block_size=16)
# kernel vs reference: max |kernel - ref| <= TOL * max(1, max |ref|), a bf16
# tolerance (bf16 keeps 8 bits of mantissa: ~4e-3 relative per rounding)
TOL = 2e-2


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")
    print(f"  ok: {what}", flush=True)


def require_tpu(chips: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found platform "
                 f"{dev['platform']!r}")
    if dev["count"] < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"JAX found {dev['count']}")
    return dev


# --------------------------------------------------------------- kernels
def _close(name: str, out, ref) -> None:
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    err = float(np.max(np.abs(out - ref)))
    bound = TOL * max(1.0, float(np.max(np.abs(ref))))
    check(bool(np.isfinite(out).all()) and err <= bound,
          f"{name}: max |kernel - ref| {err:.3e} <= {bound:.3e}")


def kernels_phase() -> None:
    """Each compiled kernel against its jnp reference, on the chip.  The
    references run at full f32 matmul precision."""
    from repro.configs import get_config
    from repro.kernels.flash_attention.ops import attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.paged_attention.ops import paged_decode_attention
    from repro.kernels.paged_attention.ref import paged_attention_ref
    from repro.kernels.ssd_scan.ops import ssd_chunked_scan
    from repro.kernels.ssd_scan.ref import ssd_scan_ref

    print("phase kernels", flush=True)
    cfg = get_config(ARCH)
    H, KV, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    def normal(shape, dtype=jnp.bfloat16):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    # paged decode: the served engine's pool and table shapes
    B, bs, max_len = ENGINE["capacity"], ENGINE["block_size"], ENGINE["max_len"]
    max_blk = max_len // bs
    nb = B * max_blk
    rng = np.random.default_rng(SEED)
    ctx = rng.integers(1, max_len + 1, B).astype(np.int32)
    perm = rng.permutation(nb).astype(np.int32)
    table = np.full((B, max_blk), -1, np.int32)
    for b in range(B):
        n = -(-int(ctx[b]) // bs)
        table[b, :n] = perm[b * max_blk:b * max_blk + n]
    q, kp, vp = normal((B, H, d)), normal((nb, bs, KV, d)), normal((nb, bs, KV, d))
    args = (q, kp, vp, jnp.asarray(table), jnp.asarray(ctx))
    out = paged_decode_attention(*args)
    with jax.default_matmul_precision("highest"):
        ref = paged_attention_ref(*args)
    _close(f"paged decode ({ARCH} widths: B={B} H={H} KV={KV} d={d} "
           f"block={bs} ctx<={max_len})", out, ref)

    # flash prefill at the largest bucket
    S = ENGINE["buckets"][-1]
    q, k, v = normal((2, S, H, d)), normal((2, S, KV, d)), normal((2, S, KV, d))
    out = attention(q, k, v, causal=True)
    with jax.default_matmul_precision("highest"):
        ref = attention_ref(*(t.transpose(0, 2, 1, 3) for t in (q, k, v)),
                            causal=True).transpose(0, 2, 1, 3)
    _close(f"flash prefill ({ARCH} widths: S={S} H={H} KV={KV} d={d})",
           out, ref)

    # SSD scan at mamba2-780m widths
    scfg = get_config(SSM_ARCH)
    Hs, P, N, Q = scfg.ssm_nheads, scfg.ssm_headdim, scfg.ssm_state, scfg.ssm_chunk
    S = 2 * Q
    x = normal((1, S, Hs, P), jnp.float32)
    Bm = normal((1, S, Hs, N), jnp.float32) * 0.5
    Cm = normal((1, S, Hs, N), jnp.float32) * 0.5
    dt = jax.random.uniform(next(keys), (1, S, Hs), jnp.float32, 0.01, 0.2)
    da = -dt * jax.random.uniform(next(keys), (1, S, Hs), jnp.float32, 0.5, 2.0)
    y, h = ssd_chunked_scan(x, Bm, Cm, dt, da, chunk=Q)
    with jax.default_matmul_precision("highest"):
        yr, hr = ssd_scan_ref(x, Bm, Cm, dt, da, chunk=Q)
    what = f"({SSM_ARCH} widths: H={Hs} P={P} N={N} chunk={Q} S={S})"
    _close(f"ssd scan y {what}", y, yr)
    _close(f"ssd scan state {what}", h, hr)


# --------------------------------------------------------------- serving
def build_registry(replicas: int, lb_policy: str = "least"):
    """One endpoint of ``replicas`` replicas.  One replica is built by the
    endpoint's default factory, as ``launch/serve.py`` builds it; several
    load the first replica's weights, as replicas of one checkpoint do."""
    from repro.configs import get_config
    from repro.core.endpoints import EndpointRegistry, ModelEndpoint
    from repro.serving import InferenceEngine

    cfg = get_config(ARCH)
    spec = ModelEndpoint(
        name=ARCH, model=cfg, min_replicas=replicas, max_replicas=replicas,
        cold_start_steps=0, lb_policy=lb_policy, seed=SEED, **ENGINE)
    if replicas > 1:
        shared = {}

        def make():
            eng = InferenceEngine(cfg, params=shared.get("params"), seed=SEED,
                                  **ENGINE)
            shared.setdefault("params", eng.params)
            return eng
        spec.make_engine = make
    t0 = time.perf_counter()
    reg = EndpointRegistry([spec])
    orch = reg.resolve(ARCH)
    jax.block_until_ready([e.params for e in orch.engines])
    print(f"built {replicas} replica(s) of {ARCH} (layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}) in "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    check(all(e.paged for e in orch.engines), "replicas serve the paged backend")
    return reg, orch


def completion(prompt, **kw):
    from repro.serving import CompletionRequest
    return CompletionRequest(prompt=[int(t) for t in prompt], model=ARCH,
                             max_tokens=MAX_TOKENS, temperature=0.0, **kw)


def drain_streams(gens: dict) -> dict:
    """Round-robin the stream generators to their end; tokens and finish
    reason per key."""
    out = {k: ([], None) for k in gens}
    live = dict(gens)
    while live:
        for k, g in list(live.items()):
            chunk = next(g, None)
            if chunk is None:
                del live[k]
                continue
            choice = chunk.choices[0]
            out[k][0].extend(choice["tokens"])
            if choice["finish_reason"] is not None:
                out[k] = (out[k][0], choice["finish_reason"])
    return out


def check_served(name: str, tokens, finish) -> None:
    check(len(tokens) == MAX_TOKENS and finish == "length",
          f"{name}: {len(tokens)} tokens, finish={finish}")


def kernel_in(fn, *args) -> bool:
    return "tpu_custom_call" in fn.lower(*args).compile().as_text()


def serve_phase() -> None:
    from repro.serving import CompletionsAPI

    print("phase serve", flush=True)
    reg, orch = build_registry(1)
    eng = orch.engines[0]
    api = CompletionsAPI(reg)
    V = eng.cfg.vocab_size
    rng = np.random.default_rng(SEED)
    shared = rng.integers(0, V, 800)
    prompts = {
        "long": rng.integers(0, V, 1200),               # > largest bucket
        "prefix-a": np.concatenate([shared, rng.integers(0, V, 100)]),
        "prefix-b": np.concatenate([shared, rng.integers(0, V, 300)]),
        **{f"stream-{i}": rng.integers(0, V, n)
           for i, n in enumerate((150, 400, 700, 1500))},
    }
    check(len(prompts["long"]) > ENGINE["buckets"][-1],
          "one prompt takes the chunked path")

    served = 0
    t0 = time.perf_counter()
    r = api.create(completion(prompts["long"]))
    compile_s = time.perf_counter() - t0
    check_served("long (first request, compiles)", r.choices[0].tokens,
                 r.choices[0].finish_reason)
    served += 1
    t1 = time.perf_counter()
    hits = []
    for name in ("prefix-a", "prefix-b"):
        before = eng.prefix.hit_tokens
        r = api.create(completion(prompts[name]))
        check_served(name, r.choices[0].tokens, r.choices[0].finish_reason)
        served += 1
        hits.append(eng.prefix.hit_tokens - before)
    check(hits[1] > 0, f"prefix-b hit {hits[1]} cached prompt tokens")

    # streams, plus one synchronous copy of stream-0's prompt served while
    # stream-0 is still decoding (neither can hit the other's blocks, so
    # both run the same computation)
    gens = {k: api.stream(completion(p)) for k, p in prompts.items()
            if k.startswith("stream-")}
    firsts = {k: next(g) for k, g in gens.items()}
    sync = api.create(completion(prompts["stream-0"]))
    streamed = drain_streams(gens)
    for k, (toks, finish) in streamed.items():
        toks = firsts[k].choices[0]["tokens"] + toks
        check_served(k, toks, finish)
        streamed[k] = toks
        served += 1
    check_served("sync copy of stream-0", sync.choices[0].tokens,
                 sync.choices[0].finish_reason)
    served += 1
    check(sync.choices[0].tokens == streamed["stream-0"],
          "stream-0 equals its synchronous copy token for token")
    warm_s = time.perf_counter() - t1
    check(served == 8 and orch.pending() == 0,
          f"served {served}/8 requests on the paged backend")
    print(f"first request (compile included): {compile_s:.1f}s; the other "
          f"7 requests: {warm_s:.1f}s; generated tokens: {served * MAX_TOKENS}",
          flush=True)

    # the served programs run the kernels, not a silent jnp fallback
    live = np.ones((eng.capacity,), bool)
    check(kernel_in(eng._decode_paged, eng.params, eng.tokens,
                    eng._put(eng.pos), eng.caches,
                    eng._put(eng.block_tables), eng._put(live)),
          "compiled paged decode program contains the Pallas kernel")
    G, S = eng._group, ENGINE["buckets"][-1]
    check(kernel_in(eng._prefill_fn(S, G), eng.params,
                    {"tokens": eng._put(np.zeros((G, S), np.int32))},
                    eng._put(np.full((G,), S, np.int32))),
          f"compiled bucketed prefill program (S={S}) contains the Pallas "
          "kernel")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}", flush=True)


# --------------------------------------------------------------- 4 chips
def step_until(reg, done, limit: int = 1000) -> None:
    for _ in range(limit):
        if done():
            return
        reg.step()
    raise RuntimeError(f"not done after {limit} cluster steps")


def four_chip_phase() -> None:
    from repro.serving import CompletionsAPI, Request, SamplingParams, State

    print("phase four-chip", flush=True)
    reg, orch = build_registry(4, lb_policy="directory")
    devices = [e.device for e in orch.engines]
    param_devs = [next(iter(jax.tree.leaves(e.params)[0].devices()))
                  for e in orch.engines]
    check(len(set(param_devs)) == 4 and param_devs == devices,
          f"4 replicas' params on 4 distinct devices: "
          f"{[str(d) for d in param_devs]}")
    api = CompletionsAPI(reg)
    V = orch.engines[0].cfg.vocab_size
    rng = np.random.default_rng(SEED + 1)
    system = rng.integers(0, V, 512)

    # shared-prefix, two-tenant batch behind the directory router: one
    # request warms the system prefix, then eight interleaved streams
    t0 = time.perf_counter()
    r = api.create(completion(np.concatenate([system, rng.integers(0, V, 64)]),
                              tenant="tenant-a"))
    check_served("warm-up", r.choices[0].tokens, r.choices[0].finish_reason)
    gens = {i: api.stream(completion(
        np.concatenate([system, rng.integers(0, V, int(rng.integers(64, 300)))]),
        tenant=("tenant-a", "tenant-b")[i % 2])) for i in range(8)}
    for k, (toks, finish) in drain_streams(gens).items():
        check_served(f"batch stream {k}", toks, finish)
    hits = sum(e.prefix.hit_tokens for e in orch.engines)
    check(hits > 0, f"shared prefix hit {hits} cached prompt tokens")
    print(f"batch: 9 requests in {time.perf_counter() - t0:.1f}s; replicas "
          f"served {[len(e.finished) for e in orch.engines]}; migrations "
          f"during the batch: {len(orch.migrations.events)}", flush=True)

    # exactness: the same prompt served unmigrated on replica 0 and, on
    # replica 1, migrated mid-decode to replica 2 (no replica has seen it)
    prompt = [int(t) for t in rng.integers(0, V, 300)]
    reqs = [Request(rid=10_000 + i, model=ARCH, prompt=list(prompt),
                    sampling=SamplingParams(max_new_tokens=MAX_TOKENS))
            for i in range(2)]
    ref, mig = reqs
    orch.engines[0].submit(ref)
    orch.engines[1].submit(mig)
    step_until(reg, lambda: mig.state is State.DECODE and len(mig.output) >= 8)
    src, dst = orch.engines[1], orch.engines[2]
    ev = orch.migrations.migrate(src, dst, mig.rid, time.perf_counter(), 1, 2)
    check(ev is not None and any(q is mig for q in dst.row_req.values()),
          f"live paged migration {src.device} -> {dst.device} at token "
          f"{len(mig.output)} ({ev.bytes if ev else 0} KV bytes)")
    step_until(reg, lambda: ref.done() and mig.done())
    check(len(ref.output) == MAX_TOKENS and mig.output == ref.output,
          "migrated request's greedy output equals the unmigrated one "
          "token for token")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica phase")
    args = ap.parse_args(argv)
    dev = require_tpu(args.chips)
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chip_phase()
    else:
        kernels_phase()
        serve_phase()
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
