"""Continuous-batching engine microbenchmark (data-plane sanity numbers).

Reduced model on CPU: the *real* engine code path under a bursty arrival
trace mixing short (bucketed) and long (chunked) prompts.  Compares the
batched + chunked prefill pipeline (``max_prefill_per_step >= 2``) against
the one-prefill-per-step baseline: prefill throughput, decode latency,
tokens/s, TTFT.  Both engines are shape-warmed first so the timed section
measures steady-state serving, not XLA compiles.  These are CPU wall-clock
numbers — production performance projections come from the dry-run
roofline, not from here.
"""
from __future__ import annotations

import math
import time

import numpy as np

from repro.configs import get_config
from repro.serving import InferenceEngine, Request, SamplingParams
from repro.serving.scheduler import SchedulerConfig


def _burst_prompts(cfg, rng, n: int, long_every: int = 5) -> list[list[int]]:
    """Mostly short prompts with a long (> largest bucket) one mixed in."""
    prompts = []
    for i in range(n):
        if long_every and i % long_every == long_every - 1:
            ln = int(rng.integers(40, 72))       # chunked-prefill path
        else:
            ln = int(rng.integers(4, 28))        # bucketed path
        prompts.append([int(x) for x in rng.integers(0, cfg.vocab_size, ln)])
    return prompts


def _mk_engine(cfg, mpps: int, capacity: int, seed: int = 0) -> InferenceEngine:
    return InferenceEngine(
        cfg, capacity=capacity, max_len=96, buckets=(16, 32),
        sched=SchedulerConfig(max_prefill_per_step=mpps), seed=seed)


def _warm(eng, cfg) -> None:
    """Compile every shape the trace will hit: each bucket at the engine's
    group size, the chunk program, and the decode/sampler programs."""
    rng = np.random.default_rng(7)
    rid = 10_000
    for ln in (8, 24, 48):                       # bucket 16, bucket 32, chunked
        for _ in range(eng._group if ln <= 32 else 1):
            eng.submit(Request(rid=rid,
                               prompt=[int(x) for x in rng.integers(0, cfg.vocab_size, ln)],
                               sampling=SamplingParams(max_new_tokens=2,
                                                       temperature=0.7, top_k=32)))
            rid += 1
    eng.run(max_steps=300)
    assert not eng.pending()
    eng.finished.clear()
    eng.history.clear()


def _serve(eng, waves: list[list[list[int]]], max_new: int = 8) -> dict:
    """Waves of burst arrivals: each wave submits all its requests at once
    (worst case for prefill head-of-line blocking), runs until drained."""
    eng.finished = []
    eng.history.clear()
    rid = 0
    t0 = time.perf_counter()
    for wave in waves:
        for p in wave:
            eng.submit(Request(rid=rid, prompt=list(p),
                               sampling=SamplingParams(max_new_tokens=max_new,
                                                       temperature=0.7, top_k=32)))
            rid += 1
        eng.run(max_steps=3000)
    wall = time.perf_counter() - t0
    done = eng.finished
    toks = sum(len(r.output) for r in done)
    prompt_toks = sum(s.prefill_tokens for s in eng.history)
    prefill_s = sum(s.prefill_s for s in eng.history)
    decode_times = [s.decode_s for s in eng.history if s.decode_s > 0]
    occ = [s.occupancy for s in eng.history]
    return {
        "finished": len(done),
        "tokens": toks,
        "tokens_per_s": toks / wall,
        "prompt_tokens": prompt_toks,
        "prefill_tok_per_s": prompt_toks / max(prefill_s, 1e-9),
        "prefill_s_total": prefill_s,
        "decode_p50_ms": 1e3 * float(np.percentile(decode_times, 50)) if decode_times else 0,
        "max_occupancy": max(occ) if occ else 0,
        "mean_ttft_s": float(np.mean([r.ttft for r in done if r.ttft is not None])),
        "chunk_steps": sum(1 for s in eng.history if s.chunk_rows),
        "steps": len(eng.history),
        "wall_s": wall,
    }


def _shared_prefix_prompts(cfg, rng, n: int, prefix_len: int = 48) -> list[list[int]]:
    """Many tenants behind one agent/system template: every prompt shares a
    ``prefix_len``-token system prefix and differs only in a short user tail
    — the dominant multi-tenant serving scenario for prefix caching."""
    system = [int(x) for x in rng.integers(0, cfg.vocab_size, prefix_len)]
    prompts = []
    for _ in range(n):
        tail = [int(x) for x in rng.integers(0, cfg.vocab_size,
                                             int(rng.integers(4, 13)))]
        prompts.append(system + tail)
    return prompts


def run_paged(arch: str = "qwen2-0.5b-smoke", n_requests: int = 24,
              capacity: int = 8, block_size: int = 16,
              seed: int = 0, verbose: bool = True) -> dict:
    """Paged+prefix-cache backend vs. the dense RowPool backend on a
    shared-system-prompt trace: the paged engine must skip the cached prefix
    (hit rate > 0, fewer prompt tokens prefilled) and charge KV per block
    rather than per row."""
    cfg = get_config(arch)
    rng = np.random.default_rng(seed)
    prompts = _shared_prefix_prompts(cfg, rng, n_requests)
    waves = [prompts[i:i + 8] for i in range(0, len(prompts), 8)]

    engines = {
        "dense": _mk_engine(cfg, 4, capacity, seed),
        "paged": InferenceEngine(
            cfg, capacity=capacity, max_len=96, buckets=(16, 32),
            kv_backend="paged", block_size=block_size,
            sched=SchedulerConfig(max_prefill_per_step=4), seed=seed),
    }
    results: dict = {}
    for label, eng in engines.items():
        _warm(eng, cfg)
        if label == "paged":        # warm-trace pollution out of the stats
            eng.prefix.hit_tokens = eng.prefix.miss_tokens = 0
        results[label] = _serve(eng, waves)
        assert results[label]["finished"] == n_requests, \
            f"{label}: {results[label]['finished']}/{n_requests} served"
        hist = eng.history
        results[label]["prefill_tokens_true"] = sum(
            s.prefill_tokens_true for s in hist)
        results[label]["prefill_tokens_padded"] = sum(
            s.prefill_tokens_padded for s in hist)
        if label == "paged":
            occ_steps = [s for s in hist if s.kv_blocks_used]
            live_tok = sum((1.0 - s.kv_frag) * s.kv_blocks_used * block_size
                           for s in occ_steps)
            blocks = sum(s.kv_blocks_used for s in occ_steps)
            results[label].update({
                "prefix_hit_tokens": sum(s.prefix_hit_tokens for s in hist),
                "prefix_hit_rate": eng.prefix.hit_rate(),
                "blocks_per_token": blocks / max(live_tok, 1e-9),
                "kv_blocks_peak": max((s.kv_blocks_used for s in hist),
                                      default=0),
                "kv_util_peak": max((s.kv_util for s in hist), default=0.0),
                "cow_copies": eng.prefix.cow_copies,
            })
            # dense charges every occupied row its full max_len worth of
            # blocks; the paged peak is what was actually mapped
            dense_equiv = max(s.occupancy for s in hist) * eng.max_blk
            results[label]["dense_equiv_blocks"] = dense_equiv

    pg, dn = results["paged"], results["dense"]
    results["prefill_saved_frac"] = 1.0 - (pg["prefill_tokens_true"]
                                           / max(dn["prefill_tokens_true"], 1))
    if verbose:
        for label in ("dense", "paged"):
            print(f"--- {label} backend ---")
            for k, v in results[label].items():
                print(f"{k}: {v}")
        print(f"prefill tokens saved by prefix cache: "
              f"{100 * results['prefill_saved_frac']:.1f}%")
    assert pg["prefix_hit_rate"] > 0, "shared prefix never hit the cache"
    assert pg["prefill_tokens_true"] < dn["prefill_tokens_true"], \
        "prefix cache did not reduce prefilled tokens"
    assert pg["kv_blocks_peak"] < pg["dense_equiv_blocks"], \
        "paged backend charged no less KV than dense rows"
    return results


def run_migrate(arch: str = "qwen2-0.5b-smoke", n_requests: int = 20,
                capacity: int = 8, block_size: int = 16,
                seed: int = 0, verbose: bool = True) -> dict:
    """Paged scale-down drain: live block-table migration vs. attrition.

    Two paged replicas serve a decaying shared-prefix trace; once arrivals
    stop, replica B is the scale-down victim.  With migration its live
    requests hand their mapped blocks to A (destination-cached prefix
    blocks are skipped — A served the same system prompt); without, B must
    decode every request to completion before it can be reclaimed.  The
    bench reports steps-to-empty for both policies — migration must win,
    it moves O(blocks) bytes instead of running O(remaining tokens) of
    decode — plus the transferred/skipped byte telemetry."""
    from repro.core.migration import MigrationConfig, MigrationManager

    cfg = get_config(arch)
    results: dict = {}
    for policy in ("attrition", "migration"):
        rng = np.random.default_rng(seed)
        prompts = _shared_prefix_prompts(cfg, rng, n_requests)
        # decaying arrivals: big burst first, trailing off to nothing
        waves = []
        i, w = 0, max(n_requests // 2, 1)
        while i < n_requests:
            waves.append(prompts[i:i + w])
            i += w
            w = max(w // 2, 1)

        def mk():
            return InferenceEngine(
                cfg, capacity=capacity, max_len=96, buckets=(16, 32),
                kv_backend="paged", block_size=block_size,
                sched=SchedulerConfig(max_prefill_per_step=4), seed=seed)
        a, b = mk(), mk()
        b.params = a.params
        _warm(a, cfg)
        _warm(b, cfg)
        mgr = MigrationManager(MigrationConfig())
        rid = 0
        for wi, wave in enumerate(waves):        # B takes the decaying tail
            for j, p in enumerate(wave):
                eng = b if (wi + j) % 2 else a
                eng.submit(Request(rid=rid, prompt=list(p),
                                   sampling=SamplingParams(max_new_tokens=24)))
                rid += 1
            # load decays: later (smaller) waves arrive after the earlier
            # ones have mostly drained — the autoscaler's scale-down regime
            for _ in range(6):
                a.step()
                b.step()
        # arrivals over: B is the scale-down victim; hand its queue to A
        while b.scheduler.queue:
            a.submit(b.scheduler.queue.popleft())
        b_tokens_predrain = sum(len(r.output) for r in b.finished)
        drain_steps, t0 = 0, time.perf_counter()
        while (b.pool.used or b.scheduler.depth()) and drain_steps < 2000:
            if policy == "migration":
                # the orchestrator's drain: move everything the survivor
                # will admit, retry the rest next step
                for r in [q.rid for q in b.migratable_requests()]:
                    mgr.migrate(b, a, r, 0.0, 1, 0)
            a.step()
            b.step()
            drain_steps += 1
        drain_s = time.perf_counter() - t0
        a.run(max_steps=3000)                   # A finishes what it absorbed
        served = len(a.finished) + len(b.finished)
        assert served == n_requests, f"{policy}: {served}/{n_requests} served"
        res = {
            "drain_steps": drain_steps,
            "drain_s": drain_s,
            "b_decode_tokens_during_drain": sum(
                len(r.output) for r in b.finished) - b_tokens_predrain,
            "migrated": mgr.succeeded,
            "migration_failures": mgr.failed,
            "bytes_transferred": sum(e.bytes for e in mgr.events),
            "bytes_full": sum(e.bytes_full for e in mgr.events),
            "blocks_skipped": sum(e.blocks_skipped for e in mgr.events),
        }
        a.prefix.check_invariants()
        b.prefix.check_invariants()
        results[policy] = res
    mig, att = results["migration"], results["attrition"]
    results["drain_speedup_steps"] = att["drain_steps"] / max(
        mig["drain_steps"], 1)
    if verbose:
        for policy in ("attrition", "migration"):
            print(f"--- {policy} drain ---")
            for k, v in results[policy].items():
                print(f"{k}: {v}")
        print(f"drain speedup (attrition/migration steps): "
              f"{results['drain_speedup_steps']:.2f}x")
    assert mig["migrated"] > 0, "no request was live-migrated"
    assert mig["drain_steps"] < att["drain_steps"], \
        "live migration did not drain the victim faster than attrition"
    assert mig["bytes_transferred"] <= mig["bytes_full"], \
        "prefix skipping never reduced transfer bytes"
    return results


def _tenant_prompts(cfg, rng, n: int, n_tenants: int = 4,
                    block_size: int = 16,
                    tenant_len: int = 48) -> list[list[int]]:
    """Hierarchical multi-tenant trace: every prompt opens with the same
    one-block platform preamble, continues with one of ``n_tenants``
    tenant-specific agent templates, and ends in a short per-request user
    tail.  First-block affinity routing cannot tell tenants apart (the
    first block is identical for all of them); a cluster cache directory
    walking beyond the first block can."""
    preamble = [int(x) for x in rng.integers(0, cfg.vocab_size, block_size)]
    tenants = [preamble + [int(x) for x in
                           rng.integers(0, cfg.vocab_size, tenant_len)]
               for _ in range(n_tenants)]
    prompts = []
    for _ in range(n):
        t = int(rng.integers(0, n_tenants))
        tail = [int(x) for x in rng.integers(0, cfg.vocab_size,
                                             int(rng.integers(4, 13)))]
        prompts.append(tenants[t] + tail)
    return prompts


def run_directory(arch: str = "qwen2-0.5b-smoke", n_requests: int = 48,
                  capacity: int = 8, block_size: int = 16,
                  seed: int = 0, verbose: bool = True,
                  strict: bool = True) -> dict:
    """Cluster cache directory vs. first-block prefix affinity vs. p2c on a
    multi-tenant trace under autoscaling churn.

    All prompts share a one-block platform preamble; each tenant adds a
    two-block agent template.  The ``"prefix"`` policy keys on the first
    block only, so every tenant rendezvous-hashes to the *same* replica and
    the load guard scatters the overflow blindly; ``"directory"`` walks the
    cluster radix view across the whole prompt and routes each tenant to
    the replica that actually caches its template — including after
    scale-down moved those blocks via migration donation.  Time is the
    logical step clock, so routing, scaling, and the reported metrics are
    seed-deterministic (no wall-clock in the control path)."""
    from repro.core.autoscaler import HPAConfig
    from repro.core.orchestrator import Orchestrator, OrchestratorConfig

    cfg = get_config(arch)
    results: dict = {}
    for policy in ("p2c", "prefix", "directory"):
        rng = np.random.default_rng(seed)
        prompts = _tenant_prompts(cfg, rng, n_requests,
                                  block_size=block_size)

        def mk():
            return InferenceEngine(
                cfg, capacity=capacity, max_len=96, buckets=(16, 32),
                kv_backend="paged", block_size=block_size,
                sched=SchedulerConfig(max_prefill_per_step=4), seed=seed)

        ocfg = OrchestratorConfig(
            min_replicas=2, max_replicas=4, lb_policy=policy, lb_seed=seed,
            hpa=HPAConfig(metric="queue", target=2.0, min_replicas=2,
                          max_replicas=4, stabilization_s=8.0,
                          scale_down_cooldown_s=8.0),
            control_every_steps=2)
        orch = Orchestrator(mk, ocfg)

        # churn plan: (requests this burst, arrival rate per step, idle
        # steps after).  Sustained bursts push queue depth over the HPA
        # target (scale up) and keep every replica busy enough that the
        # load guard must spill — where tenant-aware spilling pays; the
        # long lulls drain to nothing (scale down -> drain migration
        # donates the victim's blocks); the second burst then probes
        # whether the policy can still find the surviving warm replicas.
        half = n_requests // 2
        plan = [(half, 6, 40), (n_requests - half, 6, 40)]
        t, rid = 0.0, 0
        for n_burst, rate, idle in plan:
            left = n_burst
            while left > 0:
                for _ in range(min(rate, left)):
                    orch.submit(Request(rid=rid,
                                        prompt=list(prompts[rid]),
                                        sampling=SamplingParams(
                                            max_new_tokens=8)),
                                now=t)
                    rid += 1
                left -= min(rate, left)
                orch.step(now=t)
                t += 1.0
            for _ in range(idle):
                orch.step(now=t)
                t += 1.0
        while orch.pending() and t < 5000.0:
            orch.step(now=t)
            t += 1.0
        done = list(orch.finished)
        for e in orch.engines:
            done.extend(e.finished)
            e.prefix.check_invariants()
        assert len(done) == n_requests, \
            f"{policy}: {len(done)}/{n_requests} served"
        hit = sum(r.prefix_hit_tokens for r in done)
        ptoks = sum(len(r.prompt) for r in done)
        replicas = [n for _, n in orch.scale_history]
        res = {
            "cluster_hit_rate": hit / max(ptoks, 1),
            "prefix_hit_tokens": hit,
            "prompt_tokens": ptoks,
            # what the cluster actually prefilled: prompt tokens minus the
            # ones served straight from replica prefix caches
            "prefill_tokens_true": ptoks - hit,
            "mean_ttft_steps": float(np.mean([r.ttft for r in done])),
            "p90_ttft_steps": float(np.percentile([r.ttft for r in done], 90)),
            "migrations": orch.migrations.succeeded,
            "scale_events": len(orch.scale_history),
            "replicas_peak": max(replicas, default=2),
            "replicas_final": len(orch.engines),
            "directory_entries_final": orch.directory.total_entries,
            "directory_stale_dropped": orch.directory.stats.stale_dropped,
            "steps": t,
        }
        results[policy] = res
    dirp, pref, p2c = (results[p] for p in ("directory", "prefix", "p2c"))
    results["hit_rate_gain_vs_prefix"] = (dirp["cluster_hit_rate"]
                                          - pref["cluster_hit_rate"])
    results["prefill_saved_vs_prefix"] = 1.0 - (
        dirp["prefill_tokens_true"] / max(pref["prefill_tokens_true"], 1))
    if verbose:
        for policy in ("p2c", "prefix", "directory"):
            print(f"--- {policy} routing ---")
            for k, v in results[policy].items():
                print(f"{k}: {v}")
        print(f"hit-rate gain (directory - prefix): "
              f"{results['hit_rate_gain_vs_prefix']:.3f}")
        print(f"prefill tokens saved vs prefix: "
              f"{100 * results['prefill_saved_vs_prefix']:.1f}%")
    # sanity checks are *collected*, not asserted mid-flight: __main__ must
    # still write the metrics JSON on a failing run (the regression gate's
    # re-baselining workflow needs the numbers to diagnose / re-commit)
    checks = [
        (dirp["replicas_peak"] > 2 and dirp["replicas_final"] <= 3,
         "the trace never exercised autoscaling churn"),
        (dirp["cluster_hit_rate"] > pref["cluster_hit_rate"],
         "directory routing did not beat first-block prefix affinity"),
        (dirp["prefill_tokens_true"] < pref["prefill_tokens_true"],
         "directory routing did not reduce prefilled tokens"),
        (dirp["cluster_hit_rate"] > p2c["cluster_hit_rate"],
         "directory routing did not beat p2c"),
    ]
    results["check_failures"] = [msg for ok, msg in checks if not ok]
    if strict and results["check_failures"]:
        raise AssertionError("; ".join(results["check_failures"]))
    return results


def run_transport(arch: str = "qwen2-0.5b-smoke", n_requests: int = 16,
                  capacity: int = 8, block_size: int = 16,
                  seed: int = 0, verbose: bool = True,
                  strict: bool = True) -> dict:
    """Both planes over the simulated cluster transport (core/transport.py).

    Part A — data plane: a scale-down drain on a bandwidth-limited link (one
    KV block per step).  ``stopcopy`` ships each migration as one synchronous
    whole-payload copy that stalls both endpoints for the copy's
    serialization steps; ``overlap`` streams block-granular chunks with
    ``migrate_async`` while *both* replicas keep stepping — the destination
    activates each row the step its last chunk lands.  Overlap must drain
    the victim in fewer steps: the transfer hides behind compute instead of
    adding to it.

    Part B — control plane: the cluster cache directory fed over the same
    fabric, lossless vs. injected faults (drop 30%, reorder 20%, duplicate
    10% on the unreliable delta class).  Directory routing runs on the stale
    *delivered* view; periodic anti-entropy reconciliation repairs the
    losses, so the lossy cluster hit rate must stay within 10% of lossless.

    Everything gated runs on the logical step clock with seeded RNGs (fault
    schedules included), so the metrics are bit-reproducible for a pinned
    ``--seed``."""
    from repro.core.autoscaler import HPAConfig
    from repro.core.migration import MigrationConfig, MigrationManager
    from repro.core.orchestrator import Orchestrator, OrchestratorConfig
    from repro.core.transport import FaultSpec, LinkSpec, Transport

    cfg = get_config(arch)
    results: dict = {}

    def mk():
        return InferenceEngine(
            cfg, capacity=capacity, max_len=96, buckets=(16, 32),
            kv_backend="paged", block_size=block_size,
            sched=SchedulerConfig(max_prefill_per_step=4), seed=seed)

    # --- Part A: drain a victim replica, stop-and-copy vs overlapped ------
    for policy in ("stopcopy", "overlap"):
        rng = np.random.default_rng(seed)
        prompts = _shared_prefix_prompts(cfg, rng, n_requests)
        a, b = mk(), mk()
        b.params = a.params
        _warm(a, cfg)
        _warm(b, cfg)
        bw = float(a.kv_per_block_bytes())    # link fits one block per step
        mgr = MigrationManager(MigrationConfig())
        tp = Transport(LinkSpec(latency_steps=1, bandwidth=bw,
                                max_in_flight=64))
        # the survivor takes a light share, the victim the heavy share: the
        # drain must move live KV, not just requeue cold prompts
        for rid, p in enumerate(prompts):
            eng = a if rid % 4 == 0 else b
            eng.submit(Request(rid=rid, prompt=list(p),
                               sampling=SamplingParams(max_new_tokens=24)))
        for _ in range(4):                    # land prefills -> migratable
            a.step()
            b.step()
        # victim's cold queue is control-plane traffic, not a KV transfer
        while b.scheduler.queue:
            a.submit(b.scheduler.queue.popleft())
        drain_steps, stall_steps = 0, 0
        while ((b.pool.used or b.scheduler.depth()
                or mgr.transfers_in_flight) and drain_steps < 2000):
            now = float(drain_steps)
            for rid2 in [q.rid for q in b.migratable_requests()]:
                if policy == "stopcopy":
                    n0 = len(mgr.events)
                    mgr.migrate(b, a, rid2, now, 1, 0)
                    for ev in mgr.events[n0:]:
                        # synchronous copy: both endpoints stall for the
                        # link-serialization steps of the bytes moved
                        stall = int(np.ceil(ev.bytes / bw))
                        drain_steps += stall
                        stall_steps += stall
                else:
                    mgr.migrate_async(b, a, rid2, now, tp, "nb", "na", 1, 0)
            a.step()
            b.step()
            if policy == "overlap":
                mgr.pump(now, tp)
                tp.step()
            drain_steps += 1
        a.run(max_steps=3000)
        b.run(max_steps=3000)
        served = len(a.finished) + len(b.finished)
        assert served == n_requests, f"{policy}: {served}/{n_requests} served"
        a.prefix.check_invariants()
        b.prefix.check_invariants()
        res = {
            "drain_steps": drain_steps,
            "stall_steps": stall_steps,
            "migrated": mgr.succeeded,
            "migration_failures": mgr.failed,
            "bytes_transferred": sum(e.bytes for e in mgr.events),
            "bytes_full": sum(e.bytes_full for e in mgr.events),
            "chunks": sum(e.chunks for e in mgr.events),
            "blocks_skipped": sum(e.blocks_skipped for e in mgr.events),
        }
        if policy == "overlap":
            res["transport_delivered"] = tp.counts["delivered"]
            res["transport_bytes"] = tp.bytes_delivered
        results[policy] = res
    results["overlap_speedup_steps"] = (
        results["stopcopy"]["drain_steps"]
        / max(results["overlap"]["drain_steps"], 1))

    # --- Part B: directory over a lossy fabric vs. lossless ---------------
    dir_res: dict = {}
    for label, faults in (
            ("lossless", FaultSpec()),
            ("lossy", FaultSpec(drop=0.3, reorder=0.2, duplicate=0.1,
                                seed=seed))):
        rng = np.random.default_rng(seed)
        prompts = _tenant_prompts(cfg, rng, 48, block_size=block_size)
        tp = Transport(LinkSpec(latency_steps=1, bandwidth=float("inf"),
                                max_in_flight=10_000), faults)
        ocfg = OrchestratorConfig(
            min_replicas=2, max_replicas=4, lb_policy="directory",
            lb_seed=seed,
            hpa=HPAConfig(metric="queue", target=2.0, min_replicas=2,
                          max_replicas=4, stabilization_s=8.0,
                          scale_down_cooldown_s=8.0),
            control_every_steps=2, transport=tp)
        orch = Orchestrator(mk, ocfg)
        plan = [(24, 6, 40), (24, 6, 40)]     # run_directory's churn plan
        t, rid = 0.0, 0
        for n_burst, rate, idle in plan:
            left = n_burst
            while left > 0:
                for _ in range(min(rate, left)):
                    orch.submit(Request(rid=rid, prompt=list(prompts[rid]),
                                        sampling=SamplingParams(
                                            max_new_tokens=8)),
                                now=t)
                    rid += 1
                left -= min(rate, left)
                orch.step(now=t)
                t += 1.0
            for _ in range(idle):
                orch.step(now=t)
                t += 1.0
        while orch.pending() and t < 5000.0:
            orch.step(now=t)
            t += 1.0
        done = list(orch.finished)
        for e in orch.engines:
            done.extend(e.finished)
            e.prefix.check_invariants()
        assert len(done) == rid, f"{label}: {len(done)}/{rid} served"
        hit = sum(r.prefix_hit_tokens for r in done)
        ptoks = sum(len(r.prompt) for r in done)
        dir_res[label] = {
            "cluster_hit_rate": hit / max(ptoks, 1),
            "prefix_hit_tokens": hit,
            "prompt_tokens": ptoks,
            "migrations": orch.migrations.succeeded,
            "transport_sent": tp.counts["sent"],
            "transport_delivered": tp.counts["delivered"],
            "transport_dropped": tp.counts["dropped"],
            "transport_duplicated": tp.counts["duplicated"],
            "transport_reordered": tp.counts["reordered"],
            "directory_stale_ignored": orch._dir_service.stale_ignored,
            "steps": t,
        }
    dir_res["hit_ratio"] = (
        dir_res["lossy"]["cluster_hit_rate"]
        / max(dir_res["lossless"]["cluster_hit_rate"], 1e-9))
    results["directory"] = dir_res

    if verbose:
        for policy in ("stopcopy", "overlap"):
            print(f"--- {policy} drain ---")
            for k, v in results[policy].items():
                print(f"{k}: {v}")
        print(f"overlap speedup (stopcopy/overlap steps): "
              f"{results['overlap_speedup_steps']:.2f}x")
        for label in ("lossless", "lossy"):
            print(f"--- directory over transport: {label} ---")
            for k, v in dir_res[label].items():
                print(f"{k}: {v}")
        print(f"lossy/lossless hit ratio: {dir_res['hit_ratio']:.3f}")
    ov, sc = results["overlap"], results["stopcopy"]
    checks = [
        (ov["migrated"] > 0, "no request streamed over the transport"),
        (ov["drain_steps"] < sc["drain_steps"],
         "overlapped streaming did not drain faster than stop-and-copy"),
        (ov["chunks"] >= ov["migrated"],
         "async transfers were not block-granular"),
        (dir_res["lossy"]["transport_dropped"] > 0,
         "the lossy run injected no loss — the fault schedule is dead"),
        (dir_res["hit_ratio"] >= 0.9,
         "directory hit rate under injected loss fell more than 10% "
         "below lossless"),
    ]
    results["check_failures"] = [msg for ok, msg in checks if not ok]
    if strict and results["check_failures"]:
        raise AssertionError("; ".join(results["check_failures"]))
    return results


def _poisson_trace(cfg, rng, n: int, qps: float,
                   interactive_frac: float = 0.7) -> list[dict]:
    """Open-loop arrival spec on the logical step clock: Poisson arrivals at
    ``qps`` requests per step, two SLO classes — interactive (short prompt,
    tight TTFT deadline) and batch (long chunked prompt, loose deadline)."""
    arrivals = np.cumsum(rng.exponential(1.0 / qps, n))
    spec = []
    for i in range(n):
        interactive = bool(rng.random() < interactive_frac)
        ln = int(rng.integers(4, 28)) if interactive \
            else int(rng.integers(40, 72))
        spec.append({
            "arrival": float(arrivals[i]),
            "prompt": [int(x) for x in rng.integers(0, cfg.vocab_size, ln)],
            "interactive": interactive,
        })
    return spec


def _mk_stream_reqs(spec: list[dict]) -> list:
    """Materialise fresh Request objects from a trace spec (requests are
    mutated by serving, so every engine run gets its own copies)."""
    reqs = []
    for i, s in enumerate(spec):
        tight = s["interactive"]
        r = Request(rid=i, prompt=list(s["prompt"]),
                    sampling=SamplingParams(
                        max_new_tokens=8 if tight else 16),
                    slo_ttft=12.0 if tight else 120.0,
                    slo_tpot=3.0 if tight else 6.0)
        r.arrival = s["arrival"]
        reqs.append(r)
    return reqs


def _stream_sweep(eng, reqs: list, n_total: int,
                  max_steps: float = 5000.0) -> dict:
    """Open-loop serve: submit each request at its arrival step, stream
    every token through the event demux, report per-request latency
    percentiles and SLO goodput.  All metrics live on the logical step
    clock, so a pinned seed makes them bit-reproducible (CI gates them)."""
    from repro.serving import FirstTokenEvent, State, StreamDemux

    eng.finished = []
    eng.history.clear()
    demux = StreamDemux()
    streamed: dict[int, list[int]] = {}
    first: dict[int, float] = {}
    i, t, qpeak, preempts = 0, 0.0, 0, 0
    while (i < len(reqs) or eng.pending()) and t < max_steps:
        while i < len(reqs) and reqs[i].arrival <= t:
            eng.submit(reqs[i], now=t)
            i += 1
        st = eng.step(now=t)
        preempts += st.preempted
        qpeak = max(qpeak, st.queue_depth)
        for ev in st.events:
            if isinstance(ev, FirstTokenEvent):
                first[ev.rid] = ev.t
        for tok in demux.feed(st.events):
            streamed.setdefault(tok.rid, []).append(tok.token)
        t += 1.0
    done = eng.finished
    rejected = [r for r in reqs if r.state is State.REJECTED]
    ttfts = sorted(first[r.rid] - r.arrival for r in done if r.rid in first)
    tpots = [r.tpot for r in done if r.tpot is not None]

    def pct(xs, p):
        return float(np.percentile(xs, p)) if xs else 0.0

    return {
        "served": len(done),
        "rejected": len(rejected),
        "tokens": sum(len(r.output) for r in done),
        "ttft_p50_steps": pct(ttfts, 50),
        "ttft_p90_steps": pct(ttfts, 90),
        "ttft_p99_steps": pct(ttfts, 99),
        "tpot_p50_steps": pct(tpots, 50),
        "tpot_p90_steps": pct(tpots, 90),
        "slo_goodput": sum(1 for r in done if r.slo_met()) / max(n_total, 1),
        "queue_peak": qpeak,
        "preemptions": preempts,
        "steps": t,
        "stream_equal": sum(1 for r in done
                            if streamed.get(r.rid, []) == r.output),
    }


def run_stream(arch: str = "qwen2-0.5b-smoke", n_requests: int = 32,
               capacity: int = 8, seed: int = 0, verbose: bool = True,
               strict: bool = True,
               qps_list: tuple[float, ...] = (0.5, 1.5, 3.0),
               trace: bool = False, trace_out: str | None = None,
               metrics_out: str | None = None) -> dict:
    """Open-loop streaming bench: Poisson arrivals swept to saturation.

    A mixed interactive/batch trace (70% short prompts with tight TTFT
    SLOs, 30% long chunked prompts with loose ones) arrives at increasing
    QPS on the logical step clock.  Every token is consumed through the
    typed event stream (the completions front-end's data path) and checked
    byte-identical against the final ``Request.output``.  The EDF scheduler
    (policy="slo", with the decode-pressure guard armed) is swept across
    all rates; FCFS serves the same top-rate trace for the goodput
    comparison — under overload, deadline ordering should keep more
    interactive requests inside their TTFT budget."""
    cfg = get_config(arch)
    results: dict = {}
    traces = {}
    for qps in qps_list:
        rng = np.random.default_rng([seed, int(round(qps * 10))])
        traces[qps] = _poisson_trace(cfg, rng, n_requests, qps)

    # tracing is observational only: the engines always carry a tracer, so
    # --trace merely shares one across sweeps and exports it — the gated
    # serving metrics are bit-identical with it on or off
    tracer = registry = None
    if trace or trace_out or metrics_out:
        from repro.core.metrics import MetricsRegistry
        from repro.core.tracing import Tracer, attribute_slo_misses
        tracer, registry = Tracer(), MetricsRegistry()

    def mk(policy):
        eng = InferenceEngine(
            cfg, capacity=capacity, max_len=96, buckets=(16, 32),
            sched=SchedulerConfig(policy=policy, max_prefill_per_step=4,
                                  slo_guard=(policy == "slo")),
            seed=seed, tracer=tracer, metrics=registry)
        if registry is not None:    # label the two engines apart
            eng.lb_id = {"slo": 0, "fcfs": 1}[policy]
            eng.set_metrics(registry)
        return eng

    # rids 0..n are reused by every sweep, so SLO-miss attribution must be
    # pulled from the live traces sweep-by-sweep, before the next sweep's
    # start_trace archives them
    attribution: list[dict] = []

    def _attribute(key, reqs_run):
        if tracer is None:
            return
        for row in attribute_slo_misses(tracer, reqs_run):
            row["sweep"] = key
            attribution.append(row)

    edf = mk("slo")
    _warm(edf, cfg)
    eq, total_served = 0, 0
    for qps in qps_list:
        key = f"qps_{qps}".replace(".", "p")
        reqs_run = _mk_stream_reqs(traces[qps])
        res = _stream_sweep(edf, reqs_run, n_requests)
        _attribute(key, reqs_run)
        eq += res["stream_equal"]
        total_served += res["served"]
        results[key] = res
    top = qps_list[-1]
    fcfs = mk("fcfs")
    _warm(fcfs, cfg)
    reqs_run = _mk_stream_reqs(traces[top])
    res = _stream_sweep(fcfs, reqs_run, n_requests)
    _attribute(f"fcfs_qps_{top}".replace(".", "p"), reqs_run)
    eq += res["stream_equal"]
    total_served += res["served"]
    results[f"fcfs_qps_{top}".replace(".", "p")] = res

    top_key = f"qps_{top}".replace(".", "p")
    results["stream_equal_frac"] = eq / max(total_served, 1)
    results["goodput_gain_vs_fcfs"] = (results[top_key]["slo_goodput"]
                                       - res["slo_goodput"])
    if verbose:
        for qps in qps_list:
            key = f"qps_{qps}".replace(".", "p")
            print(f"--- edf @ {qps} req/step ---")
            for k, v in results[key].items():
                print(f"{k}: {v}")
        print(f"--- fcfs @ {top} req/step ---")
        for k, v in res.items():
            print(f"{k}: {v}")
        print(f"stream == output for {eq}/{total_served} requests")
        print(f"goodput gain (edf - fcfs) at {top} req/step: "
              f"{results['goodput_gain_vs_fcfs']:.3f}")
    checks = [
        (results["stream_equal_frac"] == 1.0,
         "streamed tokens diverged from Request.output"),
        (all(results[f"qps_{q}".replace('.', 'p')]["served"]
             + results[f"qps_{q}".replace('.', 'p')]["rejected"]
             == n_requests for q in qps_list),
         "requests lost (served + rejected != submitted)"),
        (results["goodput_gain_vs_fcfs"] >= 0.0,
         "EDF scheduling lost goodput to FCFS under overload"),
    ]
    if tracer is not None:
        from repro.core.tracing import format_attribution
        results["slo_miss_attribution"] = attribution
        results["trace_errors"] = tracer.verify()
        checks.append((not results["trace_errors"],
                       "trace integrity violated: "
                       + "; ".join(results["trace_errors"][:3])))
        if verbose:
            print(format_attribution(attribution))
        if trace_out:
            tracer.write_chrome_trace(trace_out)
            print(f"wrote {trace_out} "
                  f"({sum(1 for _ in tracer.traces())} traces)")
        if metrics_out:
            with open(metrics_out, "w") as f:
                f.write(registry.render())
            print(f"wrote {metrics_out}")
    results["check_failures"] = [msg for ok, msg in checks if not ok]
    if strict and results["check_failures"]:
        raise AssertionError("; ".join(results["check_failures"]))
    return results


def _jain(xs: list[float]) -> float:
    """Jain's fairness index over per-tenant (weight-normalized) service:
    1.0 = perfectly weight-proportional shares, 1/n = one tenant hogging."""
    s, s2 = sum(xs), sum(x * x for x in xs)
    return (s * s) / (len(xs) * s2) if s2 > 0 else 1.0


def run_multimodel(arch: str = "qwen2-0.5b-smoke", n_requests: int = 36,
                   capacity: int = 8, seed: int = 0, verbose: bool = True,
                   strict: bool = True) -> dict:
    """Multi-model registry under a tenant-skewed trace: a base endpoint
    (weighted-fair two-tenant admission) plus a scale-to-zero draft
    endpoint that cold-starts twice — once mid-burst, once after idling
    back to zero.

    Entirely on the logical step clock, so served counts, cold-start
    steps, TTFTs, and the fairness index are seed-deterministic.  Tenant
    "alpha" submits 3x tenant "beta"'s volume and holds 3x its weight, so
    weighted-fair shares should match demand: the Jain index over
    weight-normalized mid-burst served tokens is ~1.0 when the wfq policy
    honors the weights (FCFS interleaving also lands near 1.0 here — the
    wfq-specific share test lives in tests/test_endpoints.py; the bench
    gates that fairness never *regresses*)."""
    from repro.core.autoscaler import HPAConfig
    from repro.core.endpoints import (EndpointRegistry, ModelEndpoint,
                                      TenantQuota)
    from repro.serving import State

    cfg = get_config(arch)
    rng = np.random.default_rng(seed)
    weights = {"alpha": 3.0, "beta": 1.0}
    reg = EndpointRegistry(
        [ModelEndpoint(
            name="base", model=cfg, capacity=capacity, max_len=96,
            buckets=(16, 32), priority=1, min_replicas=1, max_replicas=2,
            cold_start_steps=0, seed=seed,
            hpa=HPAConfig(metric="queue", target=6.0, max_replicas=2,
                          stabilization_s=8.0, scale_down_cooldown_s=8.0),
            sched=SchedulerConfig(policy="wfq", tenant_weights=weights,
                                  max_prefill_per_step=4)),
         ModelEndpoint(
            name="draft", model=cfg, capacity=4, max_len=96,
            buckets=(16, 32), priority=0, min_replicas=0, max_replicas=1,
            cold_start_steps=4, idle_ticks_to_zero=3,
            control_every_steps=2, seed=seed + 1)],
        tenants={t: TenantQuota(weight=w) for t, w in weights.items()})

    def _prompt():
        return [int(x) for x in rng.integers(0, cfg.vocab_size,
                                             int(rng.integers(8, 17)))]

    n_draft = 4
    n_base = n_requests - n_draft
    base_reqs: list[Request] = []
    draft_reqs: list[Request] = []
    rid = 0

    def _submit_draft(t: float, k: int) -> None:
        nonlocal rid
        for _ in range(k):
            r = Request(rid=rid, model="draft", tenant="alpha",
                        prompt=_prompt(),
                        sampling=SamplingParams(max_new_tokens=6),
                        slo_ttft=20.0, slo_tpot=4.0)
            rid += 1
            draft_reqs.append(r)
            reg.submit(r, now=t)

    # saturating burst on base: 4 submissions per step, 3 alpha : 1 beta;
    # the first draft pair lands mid-burst (cold start #1 overlaps load)
    t, submitted = 0.0, 0
    while submitted < n_base:
        for _ in range(min(4, n_base - submitted)):
            tenant = "beta" if submitted % 4 == 3 else "alpha"
            r = Request(rid=rid, model="base", tenant=tenant,
                        prompt=_prompt(),
                        sampling=SamplingParams(max_new_tokens=8),
                        slo_ttft=30.0, slo_tpot=4.0)
            rid += 1
            submitted += 1
            base_reqs.append(r)
            reg.submit(r, now=t)
        if t == 2.0:
            _submit_draft(t, 2)
        reg.step(t)
        t += 1.0
    while reg.pending() and t < 3000.0:
        reg.step(t)
        t += 1.0
    # idle: the draft endpoint must scale back to zero...
    for _ in range(20):
        reg.step(t)
        t += 1.0
    zero_after_burst = reg.state("draft") == "scaled_to_zero"
    # ...then cold-start again on the next request (wakeup #2)
    _submit_draft(t, 2)
    while reg.pending() and t < 3000.0:
        reg.step(t)
        t += 1.0
    for _ in range(20):
        reg.step(t)
        t += 1.0

    done = reg.finished()
    m = reg.metrics
    # mid-burst weighted fairness: tokens each tenant had streamed by the
    # median base token time, normalized by weight (both tenants are
    # backlogged there, so shares reflect admission policy, not demand)
    tok_times = sorted(tt for r in base_reqs for tt in r.token_times)
    t_cut = tok_times[len(tok_times) // 2] if tok_times else 0.0
    share = {tenant: sum(sum(1 for tt in r.token_times if tt <= t_cut)
                         for r in base_reqs if r.tenant == tenant)
             / weights[tenant] for tenant in weights}
    fairness = _jain(list(share.values()))

    def _ep_res(name: str, reqs: list) -> dict:
        served = [r for r in reqs if r.state is State.DONE]
        return {
            "served": len(served),
            "slo_goodput": (sum(1 for r in served if r.slo_met())
                            / max(len(served), 1)),
            "mean_ttft_steps": float(np.mean([r.ttft for r in served]))
            if served else 0.0,
            "replicas_final": len(reg.resolve(name).engines),
        }

    results: dict = {"base": _ep_res("base", base_reqs),
                     "draft": _ep_res("draft", draft_reqs)}
    results["draft"].update(
        cold_starts=m.get("endpoint_cold_starts_total").value(
            endpoint="draft"),
        cold_start_steps=m.get("endpoint_cold_start_steps").value(
            endpoint="draft"),
        zero_after_burst=zero_after_burst)
    results["tenant_fairness_jain"] = fairness
    results["tenant_share_per_weight"] = share
    results["steps"] = t
    if verbose:
        for name in ("base", "draft"):
            print(f"--- endpoint {name} ---")
            for k, v in results[name].items():
                print(f"{k}: {v}")
        print(f"tenant_fairness_jain: {fairness:.3f} (shares/weight {share})")
    checks = [
        (len(done) == n_requests,
         f"served {len(done)}/{n_requests}"),
        (results["draft"]["cold_starts"] == 2,
         "draft endpoint did not cold-start twice"),
        (zero_after_burst and results["draft"]["replicas_final"] == 0,
         "draft endpoint did not scale back to zero when idle"),
        (fairness >= 0.85,
         f"weighted tenant shares unfair (jain {fairness:.3f})"),
        (results["base"]["slo_goodput"] >= 0.5,
         "base endpoint goodput collapsed"),
    ]
    results["check_failures"] = [msg for ok, msg in checks if not ok]
    if strict and results["check_failures"]:
        raise AssertionError("; ".join(results["check_failures"]))
    return results


def _proactive_traces(cfg, seed: int) -> dict[str, list[list[tuple]]]:
    """Per-scenario arrival traces on the logical step clock.

    Each trace is a list of steps; each step is a list of
    ``(tenant, prompt, max_new)`` arrivals.  Generated once per scenario
    from a seeded rng and replayed *identically* under both policies, so
    reactive-vs-proactive differences are controller differences, nothing
    else."""
    rng = np.random.default_rng(seed)

    def _req(tenant=None):
        plen = int(rng.integers(8, 17))
        prompt = [int(x) for x in rng.integers(0, cfg.vocab_size, plen)]
        return (tenant, prompt, 8)

    def _trace(lams, tenant=None):
        return [[_req(tenant) for _ in range(int(rng.poisson(lam)))]
                for lam in lams]

    traces: dict[str, list[list[tuple]]] = {}
    # diurnal: two sinusoidal day/night cycles — the forecaster's trend
    # term should ride the upswings instead of waiting for queue build-up
    traces["diurnal"] = _trace(
        [0.15 + 1.0 * 0.5 * (1 + math.sin(2 * math.pi * s / 80 - math.pi / 2))
         for s in range(160)])
    # flash crowd: quiet floor, an 8-step linear ramp, a hot plateau that
    # needs ~max_replicas, then quiet again.  The ramp is the proactive
    # policy's whole case: extrapolate it and be warm when the plateau
    # lands, vs react to the queue the plateau causes
    quiet, hot = 0.1, 2.6
    traces["flash"] = _trace(
        [quiet] * 36
        + [quiet + (hot - quiet) * (i + 1) / 8 for i in range(8)]
        + [hot] * 48 + [quiet] * 28)
    # tenant hotspot: a steady background tenant plus one tenant spiking
    # mid-run — scaling must absorb the hot tenant without dragging the
    # steady tenant's SLOs down with it
    steady = _trace([0.5] * 160, tenant="steady")
    hotspot = _trace([0.0] * 56 + [1.8] * 48 + [0.0] * 56, tenant="hot")
    traces["hotspot"] = [a + b for a, b in zip(steady, hotspot)]
    # replay with churn: an on/off square wave (three bursts, long lulls)
    # that forces the autoscaler up and down repeatedly — the goodput
    # guard must not let scale-down eat the next burst's headroom
    wave = ([2.2] * 20 + [0.08] * 28) * 3
    traces["replay"] = _trace(wave)
    return traces


def _run_proactive_scenario(cfg, trace, policy: str, seed: int, *,
                            capacity: int = 4, cold_start_steps: int = 8,
                            control_every: int = 4,
                            max_replicas: int = 6) -> dict:
    """One scenario under one controller; logical clock throughout."""
    from repro.core.autoscaler import HPAConfig
    from repro.core.orchestrator import Orchestrator, OrchestratorConfig
    from repro.core.scaling_policy import ProactiveConfig
    from repro.serving import State

    def mk():
        return InferenceEngine(cfg, capacity=capacity, max_len=64,
                               buckets=(8, 16), seed=seed)

    ocfg = OrchestratorConfig(
        name="bench", min_replicas=1, max_replicas=max_replicas,
        hpa=HPAConfig(metric="queue", target=6.0, min_replicas=1,
                      max_replicas=max_replicas, stabilization_s=16.0,
                      scale_down_cooldown_s=16.0),
        scaling=ProactiveConfig() if policy == "proactive" else None,
        cold_start_steps=cold_start_steps, control_every_steps=control_every)
    orch = Orchestrator(mk, ocfg)

    reqs: list[Request] = []
    t, rid = 0.0, 0
    for arrivals in trace:
        for tenant, prompt, max_new in arrivals:
            r = Request(rid=rid, tenant=tenant, prompt=list(prompt),
                        sampling=SamplingParams(max_new_tokens=max_new),
                        slo_ttft=12.0, slo_tpot=3.0)
            rid += 1
            reqs.append(r)
            orch.submit(r, now=t)
        orch.step(now=t)
        t += 1.0
    while orch.pending() and t < 5000.0:
        orch.step(now=t)
        t += 1.0

    done = [r for r in reqs if r.state is State.DONE]
    assert len(done) == len(reqs), \
        f"{policy}: {len(done)}/{len(reqs)} served"
    ttfts = [r.ttft for r in done]
    ups = [tt for tt, c, nw, _ in orch.autoscaler.decisions if nw > c]
    replicas = [n for _, n in orch.scale_history]
    res = {
        "served": len(done),
        "slo_goodput": sum(1 for r in done if r.slo_met()) / len(done),
        "mean_ttft_steps": float(np.mean(ttfts)),
        "p95_ttft_steps": float(np.percentile(ttfts, 95)),
        "first_scaleup_step": ups[0] if ups else None,
        "scale_events": len(orch.scale_history),
        "replicas_peak": max(replicas, default=1),
        "replicas_final": len(orch.engines),
        "steps": t,
    }
    by_tenant = {r.tenant for r in done if r.tenant}
    if len(by_tenant) > 1:
        for tenant in sorted(by_tenant):
            sub = [r for r in done if r.tenant == tenant]
            res[f"goodput_{tenant}"] = \
                sum(1 for r in sub if r.slo_met()) / len(sub)
    return res


def run_proactive(arch: str = "qwen2-0.5b-smoke", n_requests: int = 0,
                  seed: int = 0, verbose: bool = True,
                  strict: bool = True) -> dict:
    """Proactive goodput-driven autoscaling vs the reactive HPA law across
    four scenarios — diurnal cycle, flash crowd, tenant hotspot, and a
    churn-heavy trace replay — each replayed from an identical seeded
    arrival trace under both controllers (``n_requests`` is ignored: the
    traces fix the workload).

    The headline number is the flash-crowd goodput gain: the proactive
    policy forecasts the ramp at the cold-start horizon and jumps straight
    to ``ceil(demand / learned_capacity)`` replicas, so they are warm when
    the plateau lands; the reactive law waits for queue depth to cross its
    target and then ratchets up one ratio step per control period, paying
    the cold start *inside* the spike.  Also includes the promoted
    deterministic ramp ablation that used to live in
    ``benchmarks/burst_proactive.py``.

    Entirely on the logical step clock: goodputs, TTFT steps, scale-up
    steps, and replica peaks are seed-deterministic and CI-gateable."""
    from burst_proactive import ramp_trigger_times

    cfg = get_config(arch)
    traces = _proactive_traces(cfg, seed)
    results: dict = {"scenarios": {}}
    for name, trace in traces.items():
        row: dict = {}
        for policy in ("reactive", "proactive"):
            row[policy] = _run_proactive_scenario(cfg, trace, policy, seed)
        row["goodput_gain"] = (row["proactive"]["slo_goodput"]
                               - row["reactive"]["slo_goodput"])
        r_up, p_up = (row["reactive"]["first_scaleup_step"],
                      row["proactive"]["first_scaleup_step"])
        row["scaleup_lead_steps"] = \
            (r_up - p_up) if (r_up is not None and p_up is not None) else None
        results["scenarios"][name] = row
    flash = results["scenarios"]["flash"]
    results["flash_goodput_gain"] = flash["goodput_gain"]
    results["flash_scaleup_lead_steps"] = flash["scaleup_lead_steps"]
    results["mean_goodput_gain"] = float(np.mean(
        [row["goodput_gain"] for row in results["scenarios"].values()]))
    # promoted unit ablation: reactive vs forecast trigger time on a clean
    # linear ramp (no queueing dynamics, pure controller lead)
    results["ramp"] = ramp_trigger_times()
    if verbose:
        for name, row in results["scenarios"].items():
            print(f"--- scenario {name} ---")
            for policy in ("reactive", "proactive"):
                r = row[policy]
                print(f"  {policy}: goodput={r['slo_goodput']:.3f} "
                      f"p95_ttft={r['p95_ttft_steps']:.0f} "
                      f"first_up={r['first_scaleup_step']} "
                      f"peak={r['replicas_peak']} "
                      f"events={r['scale_events']}")
            print(f"  goodput_gain={row['goodput_gain']:+.3f} "
                  f"scaleup_lead={row['scaleup_lead_steps']}")
        print(f"flash goodput gain: {results['flash_goodput_gain']:+.3f}; "
              f"ramp lead {results['ramp']['lead_s']:.0f}s")
    checks = [
        (flash["goodput_gain"] > 0,
         f"proactive did not beat reactive goodput on the flash crowd "
         f"({flash['proactive']['slo_goodput']:.3f} vs "
         f"{flash['reactive']['slo_goodput']:.3f})"),
        (flash["scaleup_lead_steps"] is not None
         and flash["scaleup_lead_steps"] > 0,
         "proactive scale-up did not lead reactive on the flash crowd"),
        (results["mean_goodput_gain"] > -0.01,
         "proactive lost goodput on average across the scenario suite"),
        (results["ramp"]["lead_s"] > 0,
         "forecast trigger did not lead reactive on the clean ramp"),
    ]
    results["check_failures"] = [msg for ok, msg in checks if not ok]
    if strict and results["check_failures"]:
        raise AssertionError("; ".join(results["check_failures"]))
    return results


def run(arch: str = "qwen2-0.5b-smoke", n_requests: int = 24,
        capacity: int = 8, seed: int = 0, verbose: bool = True) -> dict:
    cfg = get_config(arch)
    rng = np.random.default_rng(seed)
    prompts = _burst_prompts(cfg, rng, n_requests)
    waves = [prompts[i:i + 8] for i in range(0, len(prompts), 8)]

    engines = {}
    for label, mpps in (("single", 1), ("pipeline", 4)):
        engines[label] = _mk_engine(cfg, mpps, capacity, seed)
        _warm(engines[label], cfg)

    # single CPU wall-clock runs are noisy; re-measure (warm, no recompiles)
    # before concluding the pipeline lost to the baseline
    for attempt in range(3):
        results = {label: _serve(eng, waves) for label, eng in engines.items()}
        for label in engines:
            assert results[label]["finished"] == n_requests, \
                f"{label}: {results[label]['finished']}/{n_requests} served"
        ratio = (results["pipeline"]["prefill_tok_per_s"]
                 / max(results["single"]["prefill_tok_per_s"], 1e-9))
        if ratio >= 0.95:
            break
    results["prefill_speedup"] = ratio
    if verbose:
        for label in ("single", "pipeline"):
            print(f"--- {label} (max_prefill_per_step="
                  f"{1 if label == 'single' else 4}) ---")
            for k, v in results[label].items():
                print(f"{k}: {v}")
        print(f"prefill_speedup (pipeline/single): {ratio:.2f}x")
    assert ratio >= 0.95, \
        f"batched prefill slower than single-prefill baseline ({ratio:.2f}x)"
    return results


if __name__ == "__main__":
    import argparse
    import json
    import sys

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode",
                    choices=["pipeline", "paged", "migrate", "directory",
                             "stream", "transport", "multimodel",
                             "proactive"],
                    default="pipeline",
                    help="pipeline: batched/chunked prefill vs single-prefill; "
                         "paged: paged+prefix-cache backend vs dense rows; "
                         "migrate: paged scale-down drain, live block-table "
                         "migration vs attrition; directory: cluster "
                         "cache-directory routing vs prefix affinity vs p2c "
                         "under autoscaling churn; stream: open-loop Poisson "
                         "QPS sweep through the per-token event stream, "
                         "TTFT/TPOT percentiles and SLO goodput, EDF vs FCFS; "
                         "transport: both planes over the simulated cluster "
                         "fabric — overlapped block-granular drain vs "
                         "stop-and-copy, directory hit rate under injected "
                         "loss vs lossless; multimodel: two endpoints behind "
                         "one registry — wfq tenant fairness on the base "
                         "model, scale-to-zero cold starts on the draft "
                         "model, priority-aware replica budget; "
                         "proactive: goodput-driven forecast scaling vs "
                         "the reactive HPA law across diurnal / flash-"
                         "crowd / tenant-hotspot / churn-replay scenarios "
                         "on identical seeded traces")
    ap.add_argument("--n", type=int, default=None,
                    help="requests (default: per-mode)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed for trace generation and LB/engine "
                         "construction — runs with the same seed are "
                         "bit-reproducible (the CI regression gate pins it)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the result dict as JSON (CI artifact)")
    ap.add_argument("--trace", action="store_true",
                    help="(stream mode) share one request-lifecycle tracer "
                         "and metrics registry across the sweep: writes "
                         "TRACE_stream.json (Chrome/Perfetto trace events) "
                         "and METRICS_stream.prom (Prometheus text "
                         "exposition), prints the SLO-miss attribution table")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    fn = {"paged": run_paged, "migrate": run_migrate,
          "pipeline": run, "directory": run_directory,
          "stream": run_stream, "transport": run_transport,
          "multimodel": run_multimodel, "proactive": run_proactive}[args.mode]
    kwargs = {"seed": args.seed}
    if args.n is not None:
        kwargs["n_requests"] = args.n
    if args.mode in ("directory", "stream", "transport", "multimodel",
                     "proactive"):
        kwargs["strict"] = False     # report failures after writing the json
    if args.mode == "stream" and args.trace:
        kwargs.update(trace=True, trace_out="TRACE_stream.json",
                      metrics_out="METRICS_stream.prom")
    res = fn(**kwargs)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2, default=float)
        print(f"wrote {args.json}")
    if res.get("check_failures"):
        print("BENCH CHECKS FAILED:")
        for msg in res["check_failures"]:
            print(f"  {msg}")
        sys.exit(1)
