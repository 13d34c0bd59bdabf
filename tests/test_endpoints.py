"""Multi-model endpoint registry: routing by model name, scale-to-zero
cold starts (queue -> spin-up -> serve -> back to zero), priority
eviction, weighted-fair tenant scheduling, the unknown-model error DTO,
and single-endpoint equivalence with a bare orchestrator."""

import numpy as np
import pytest

from repro.configs import get_config
from repro.core.autoscaler import HPAConfig
from repro.core.endpoints import EndpointRegistry, ModelEndpoint, TenantQuota
from repro.core.orchestrator import Orchestrator, OrchestratorConfig
from repro.serving import (CompletionError, CompletionRequest, CompletionsAPI,
                           InferenceEngine, ModelsAPI, Request, SamplingParams,
                           State)
from repro.serving.scheduler import Scheduler, SchedulerConfig

ARCH = "qwen2-0.5b-smoke"


@pytest.fixture(scope="module")
def cfg():
    return get_config(ARCH)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _spec(name, cfg, **kw):
    kw.setdefault("capacity", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("buckets", (8, 16))
    kw.setdefault("cold_start_steps", 0)
    return ModelEndpoint(name=name, model=cfg, **kw)


def _req(rid, cfg, rng, model=None, tenant=None, plen=8, max_new=4):
    return Request(
        rid=rid, model=model, tenant=tenant,
        prompt=[int(x) for x in rng.integers(0, cfg.vocab_size, plen)],
        sampling=SamplingParams(max_new_tokens=max_new))


def _run(registry, t0=0.0, max_steps=400):
    t = t0
    while registry.pending() and max_steps > 0:
        registry.step(t)
        t += 1.0
        max_steps -= 1
    assert registry.pending() == 0, "registry failed to drain"
    return t


# ---------------------------------------------------------------- routing
def test_registry_routes_by_model_name(cfg, rng):
    reg = EndpointRegistry([_spec("base", cfg, seed=7),
                            _spec("draft", cfg, seed=11)])
    assert reg.names() == ["base", "draft"]
    r1 = _req(0, cfg, rng, model="draft")
    r2 = _req(1, cfg, rng, model="base")
    r3 = _req(2, cfg, rng, model="base")
    for r in (r1, r2, r3):
        assert reg.submit(r, now=0.0)
    assert reg.resolve("draft").pending() == 1
    assert reg.resolve("base").pending() == 2
    # tenant label hygiene: unset tenants land in "default"
    assert r1.tenant == "default"
    done = reg.run(max_steps=300, now=1.0)
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert all(len(r.output) == 4 for r in done)
    c = reg.metrics.get("endpoint_requests_total")
    assert c.value(endpoint="base", tenant="default") == 2
    assert c.value(endpoint="draft", tenant="default") == 1

    with pytest.raises(KeyError):
        reg.submit(_req(9, cfg, rng, model="nope"), now=0.0)


# ---------------------------------------------------------- scale-to-zero
def test_scale_to_zero_cold_start_and_teardown(cfg, rng):
    reg = EndpointRegistry([_spec(
        "z", cfg, min_replicas=0, cold_start_steps=3,
        idle_ticks_to_zero=2, control_every_steps=2)])
    assert reg.state("z") == "scaled_to_zero"
    assert reg.resolve("z").engines == []

    # first request wakes the endpoint: it queues behind the warm-up
    # instead of rejecting, and its TTFT pays for the cold start
    req = _req(0, cfg, rng, model="z")
    assert reg.submit(req, now=0.0)
    assert reg.state("z") == "cold"
    assert len(reg.resolve("z").engines) == 1
    assert req.state is State.QUEUED

    t = _run(reg, t0=0.0)
    assert req.state is State.DONE and len(req.output) == 4
    assert req.ttft is not None and req.ttft >= 3.0

    m = reg.metrics
    assert m.get("endpoint_cold_starts_total").value(endpoint="z") == 1
    assert m.get("endpoint_cold_start_steps").value(endpoint="z") == 3
    assert m.get("endpoint_cold_start_seconds").value(endpoint="z") > 0
    # the cold start is a closed trace span
    cold = [s for tr in reg.tracer.traces() for s in tr.spans
            if s.name == "cold_start"]
    assert len(cold) == 1 and cold[0].t1 is not None

    # idle teardown: after idle_ticks_to_zero quiet control ticks the
    # endpoint scales back to zero
    for _ in range(12):
        reg.step(t)
        t += 1.0
    assert reg.state("z") == "scaled_to_zero"
    assert reg.resolve("z").engines == []

    # and the next request cold-starts again
    req2 = _req(1, cfg, rng, model="z")
    assert reg.submit(req2, now=t)
    _run(reg, t0=t)
    assert req2.state is State.DONE
    assert m.get("endpoint_cold_starts_total").value(endpoint="z") == 2


# ------------------------------------------------------- priority eviction
def test_priority_eviction_frees_capacity(cfg, rng):
    reg = EndpointRegistry(
        [_spec("low", cfg, priority=0, min_replicas=1, seed=7),
         _spec("high", cfg, priority=1, min_replicas=0, cold_start_steps=1,
               seed=11)],
        cluster_max_replicas=1)
    assert reg.total_replicas() == 1

    # the high-priority endpoint's wakeup evicts low's idle replica
    req = _req(0, cfg, rng, model="high")
    assert reg.submit(req, now=0.0)
    assert reg.resolve("low").engines == []
    assert len(reg.resolve("high").engines) == 1
    assert reg.state("low") == "scaled_to_zero" or not reg.resolve("low").engines
    assert reg.metrics.get("endpoint_evictions_total").value(
        victim="low", claimant="high") == 1
    _run(reg)
    assert req.state is State.DONE and len(req.output) == 4

    # the reverse never happens: low cannot evict high, so its wakeup is
    # rejected for capacity (priority strictly lower than any victim's)
    req2 = _req(1, cfg, rng, model="low")
    assert not reg.submit(req2, now=50.0)
    assert req2.state is State.REJECTED
    assert reg.metrics.get("tenant_rejections_total").value(
        tenant="default", reason="capacity") == 1
    assert len(reg.resolve("high").engines) == 1


# ------------------------------------------------------------ weighted fair
def test_wfq_scheduler_token_shares_follow_weights():
    sched = Scheduler(SchedulerConfig(
        policy="wfq", tenant_weights={"a": 3.0, "b": 1.0},
        max_prefill_per_step=4))
    for i in range(40):
        for tenant in ("a", "b"):
            r = Request(rid=len(sched.queue), prompt=[1] * 8, tenant=tenant,
                        sampling=SamplingParams(max_new_tokens=4))
            sched.submit(r, now=0.0)
    admitted = {"a": 0, "b": 0}
    # drain half the backlog: under saturation the admitted token shares
    # must track the 3:1 weights
    for step in range(10):
        for r in sched.next_batch(free_slots=4, now=float(step)):
            admitted[r.tenant] += len(r.prompt) + r.sampling.max_new_tokens
    assert admitted["a"] + admitted["b"] == 40 * 12
    ratio = admitted["a"] / admitted["b"]
    assert 2.0 <= ratio <= 4.0, ratio
    # FIFO within a tenant is preserved and both tenants drain eventually
    while sched.queue:
        sched.next_batch(free_slots=8, now=100.0)
    assert sched.depth() == 0


def test_wfq_new_tenant_joins_at_min_vtime_no_banked_credit():
    sched = Scheduler(SchedulerConfig(policy="wfq", max_prefill_per_step=2))
    for i in range(8):
        sched.submit(Request(rid=i, prompt=[1] * 8, tenant="a",
                             sampling=SamplingParams(max_new_tokens=4)),
                     now=0.0)
    for _ in range(3):
        sched.next_batch(free_slots=2, now=1.0)
    # "b" arrives late: it must not monopolize admission with credit
    # banked while idle — picks alternate rather than all-b
    for i in range(8):
        sched.submit(Request(rid=100 + i, prompt=[1] * 8, tenant="b",
                             sampling=SamplingParams(max_new_tokens=4)),
                     now=2.0)
    batch = sched.next_batch(free_slots=4, now=2.0)
    tenants = [r.tenant for r in batch]
    assert "a" in tenants and "b" in tenants


def test_wfq_tenant_ttft_tracks_weight_under_saturation(cfg, rng):
    reg = EndpointRegistry(
        [_spec("m", cfg, capacity=2,
               sched=SchedulerConfig(policy="wfq", max_prefill_per_step=2,
                                     tenant_weights={"gold": 4.0,
                                                     "free": 1.0}))],
        tenants={"gold": TenantQuota(weight=4.0),
                 "free": TenantQuota(weight=1.0)})
    reqs = []
    for i in range(8):
        for tenant in ("gold", "free"):
            r = _req(len(reqs), cfg, rng, model="m", tenant=tenant, plen=8,
                     max_new=3)
            reqs.append(r)
            assert reg.submit(r, now=0.0)
    _run(reg)
    by = {"gold": [], "free": []}
    for r in reqs:
        assert r.state is State.DONE
        by[r.tenant].append(r.ttft)
    # saturating trace on one capacity-2 replica: the weight-4 tenant's
    # requests get admitted ahead of the weight-1 tenant's backlog
    assert np.mean(by["gold"]) < np.mean(by["free"])


# ----------------------------------------------------------- tenant quotas
def test_tenant_quota_rejects_over_inflight(cfg, rng):
    reg = EndpointRegistry(
        [_spec("m", cfg)],
        tenants={"capped": TenantQuota(max_inflight=2)})
    r1 = _req(0, cfg, rng, model="m", tenant="capped")
    r2 = _req(1, cfg, rng, model="m", tenant="capped")
    r3 = _req(2, cfg, rng, model="m", tenant="capped")
    assert reg.submit(r1, now=0.0) and reg.submit(r2, now=0.0)
    assert not reg.submit(r3, now=0.0)
    assert r3.state is State.REJECTED
    assert reg.metrics.get("tenant_rejections_total").value(
        tenant="capped", reason="quota") == 1
    t = _run(reg)
    # quota releases as requests finish
    r4 = _req(3, cfg, rng, model="m", tenant="capped")
    assert reg.submit(r4, now=t)
    _run(reg, t0=t)
    assert r4.state is State.DONE


# ----------------------------------------------------- unknown-model errors
def test_unknown_model_returns_error_dto(cfg, rng):
    reg = EndpointRegistry([_spec("real", cfg)])
    api = CompletionsAPI(reg)
    prompt = [int(x) for x in rng.integers(0, cfg.vocab_size, 6)]

    resp = api.create(CompletionRequest(prompt=prompt, model="ghost"),
                      now=0.0)
    assert isinstance(resp, CompletionError)
    assert resp.type == "invalid_request_error"
    assert resp.param == "model" and resp.code == "model_not_found"
    d = resp.to_dict()
    assert d["error"]["type"] == "invalid_request_error"
    assert "ghost" in d["error"]["message"]
    assert resp.to_sse().startswith("data: ")

    frames = list(api.stream(CompletionRequest(prompt=prompt, model="ghost",
                                               stream=True), now=0.0))
    assert len(frames) == 1 and isinstance(frames[0], CompletionError)
    # nothing was admitted anywhere
    assert reg.pending() == 0

    # a routable model serves normally and the response echoes the
    # endpoint name
    ok = api.create(CompletionRequest(prompt=prompt, model="real",
                                      max_tokens=3), now=0.0)
    assert not isinstance(ok, CompletionError)
    assert ok.model == "real"
    assert len(ok.choices[0].tokens) == 3

    # single-model backends reject mismatches the same way
    eng_api = CompletionsAPI(InferenceEngine(cfg, capacity=2, max_len=64,
                                             buckets=(8, 16)), model="solo")
    bad = eng_api.create(CompletionRequest(prompt=prompt, model="other"),
                         now=0.0)
    assert isinstance(bad, CompletionError)


def test_models_api_lists_endpoint_states(cfg):
    reg = EndpointRegistry([_spec("warm", cfg),
                            _spec("zero", cfg, min_replicas=0)])
    api = ModelsAPI(reg)
    listing = api.list()
    assert listing.object == "list"
    byid = {m.id: m for m in listing.data}
    assert byid["warm"].state == "ready" and byid["warm"].replicas == 1
    assert byid["zero"].state == "scaled_to_zero"
    assert byid["zero"].replicas == 0
    one = api.retrieve("warm")
    assert one.object == "model" and one.priority == 0
    missing = api.retrieve("ghost")
    assert isinstance(missing, CompletionError)
    assert missing.code == "model_not_found"


# ------------------------------------------------- wrapper equivalence
def test_single_endpoint_registry_matches_bare_orchestrator(cfg, rng):
    """One-endpoint registry == pre-registry orchestrator, token for
    token: same engines, same clock, same control cadence."""
    hpa = HPAConfig(metric="queue", target=4.0, max_replicas=2,
                    stabilization_s=5.0, scale_down_cooldown_s=5.0)

    def make():
        return InferenceEngine(cfg, capacity=4, max_len=64, buckets=(8, 16),
                               seed=7)

    orch = Orchestrator(make, OrchestratorConfig(
        hpa=hpa, max_replicas=2, cold_start_steps=0))
    reg = EndpointRegistry([ModelEndpoint(
        name="solo", make_engine=make, hpa=hpa, max_replicas=2,
        cold_start_steps=0)])

    prompts = [[int(x) for x in rng.integers(0, cfg.vocab_size, 6 + i % 5)]
               for i in range(8)]
    for i, p in enumerate(prompts):
        orch.submit(Request(rid=i, prompt=list(p),
                            sampling=SamplingParams(max_new_tokens=4)),
                    now=0.0)
        reg.submit(Request(rid=i, prompt=list(p), model="solo",
                           sampling=SamplingParams(max_new_tokens=4)),
                   now=0.0)
    t = 0.0
    while (orch.pending() or reg.pending()) and t < 300:
        if orch.pending():
            orch.step(t)
        if reg.pending():
            reg.step(t)
        t += 1.0
    a = {r.rid: r.output for r in orch.run(max_steps=0)}
    b = {r.rid: r.output for r in reg.finished()}
    assert set(a) == set(b) == set(range(8))
    assert a == b


# ------------------------------------------------------- tenant stamping
def test_bare_orchestrator_stamps_default_tenant(cfg, rng):
    orch = Orchestrator(
        lambda: InferenceEngine(cfg, capacity=2, max_len=64, buckets=(8, 16)),
        OrchestratorConfig(cold_start_steps=0))
    r = _req(0, cfg, rng)
    assert r.tenant is None
    orch.submit(r, now=0.0)
    assert r.tenant == "default"


_PLACEMENT = """
import jax
from repro.configs import get_config
from repro.core.endpoints import EndpointRegistry, ModelEndpoint
from repro.serving import Request, SamplingParams, State

cfg = get_config("qwen2-0.5b-smoke")
reg = EndpointRegistry([
    ModelEndpoint(name="a", model=cfg, kv_backend="paged", min_replicas=3,
                  max_replicas=3, cold_start_steps=0),
    ModelEndpoint(name="b", model=cfg, min_replicas=2, max_replicas=2,
                  cold_start_steps=0)])
a, b = reg.resolve("a"), reg.resolve("b")
devs = jax.devices()
# one replica per device across endpoints; shared only once all are taken
assert [e.device for e in a.engines] == devs[:3]
assert [e.device for e in b.engines] == [devs[3], devs[0]]
for e in a.engines + b.engines:
    leaves = jax.tree.leaves((e.params, e.caches, e.tokens))
    assert all(x.devices() == {e.device} for x in leaves)
# a live paged migration between replicas on different devices equals the
# unmigrated run token for token
prompt = list(range(3, 40))
ref, mig = (Request(rid=i, model="a", prompt=prompt,
                    sampling=SamplingParams(max_new_tokens=12))
            for i in range(2))
a.engines[0].submit(ref, 0.0)
a.engines[1].submit(mig, 0.0)
t = 0.0
while not (mig.state is State.DECODE and len(mig.output) >= 4):
    reg.step(t); t += 1
ev = a.migrations.migrate(a.engines[1], a.engines[2], mig.rid, t, 1, 2)
assert ev is not None
assert any(q is mig for q in a.engines[2].row_req.values())
while not (ref.done() and mig.done()):
    reg.step(t); t += 1
assert mig.output == ref.output and len(ref.output) == 12
print("ok")
"""


def test_replicas_placed_one_per_device_and_migrate_across():
    """Four virtual CPU devices in a child process (the device count is
    fixed when JAX starts): replicas of every endpoint spread one per
    device, each engine's arrays live on its device, and a paged migration
    across devices is token-identical to the unmigrated run."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _PLACEMENT], capture_output=True,
                       text=True, env=env, cwd=repo, timeout=300)
    assert r.returncode == 0 and r.stdout.strip() == "ok", \
        r.stdout[-2000:] + r.stderr[-2000:]
