"""Paged KV serving backend: token-exactness vs. the dense engine, prefix
caching (hits skip prefill, CoW on shared tails), per-block telemetry, and
the padded/true cost-model split."""
import numpy as np
import pytest

from repro.configs import get_config
from repro.serving import InferenceEngine, Request, SamplingParams

ARCH = "qwen2-0.5b-smoke"


def _mk(backend, **kw):
    cfg = get_config(ARCH)
    kw.setdefault("capacity", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("buckets", (8, 16))
    kw.setdefault("block_size", 8)
    kw.setdefault("seed", 0)
    return cfg, InferenceEngine(cfg, kv_backend=backend, **kw)


def _submit_all(eng, cfg, prompts, rid0=0, max_new=6):
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=rid0 + i, prompt=list(p),
                           sampling=SamplingParams(max_new_tokens=max_new)))


def test_paged_matches_dense_on_mixed_trace(rng):
    """Short (bucketed-on-dense), long (chunked), and mid prompts: greedy
    outputs are token-identical across backends, and the paged engine
    charges KV per block."""
    cfg = get_config(ARCH)
    prompts = [[int(x) for x in rng.integers(0, cfg.vocab_size, n)]
               for n in (5, 11, 20, 40, 7, 33)]
    outs = {}
    for backend in ("dense", "paged"):
        _, eng = _mk(backend)
        _submit_all(eng, cfg, prompts)
        done = eng.run(max_steps=300)
        assert len(done) == len(prompts)
        outs[backend] = {r.rid: r.output for r in done}
        if backend == "paged":
            assert eng.paged
            eng.prefix.check_invariants()
            peak = max(s.kv_blocks_used for s in eng.history)
            assert 0 < peak <= eng.num_blocks
            # per-block charge beats the dense per-row worst case: 6 rows
            # of short/mid prompts never touch rows*max_blk blocks
            assert peak < eng.capacity * eng.max_blk
            assert any(s.kv_util > 0 for s in eng.history)
    assert outs["dense"] == outs["paged"]


def test_prefix_cache_hits_skip_prefill(rng):
    """Re-serving the same prompts hits the prefix cache: fewer prompt
    tokens prefilled, hit telemetry reported, outputs unchanged."""
    cfg, eng = _mk("paged")
    prompts = [[int(x) for x in rng.integers(0, cfg.vocab_size, n)]
               for n in (9, 20, 40)]
    _submit_all(eng, cfg, prompts, max_new=4)
    first = {r.rid: r.output for r in eng.run(max_steps=300)}
    true1 = sum(s.prefill_tokens_true for s in eng.history)
    eng.history.clear()
    eng.finished.clear()
    _submit_all(eng, cfg, prompts, rid0=100, max_new=4)
    eng.run(max_steps=300)
    second = {r.rid: r.output for r in eng.finished}
    true2 = sum(s.prefill_tokens_true for s in eng.history)
    hits = sum(s.prefix_hit_tokens for s in eng.history)
    assert hits > 0
    assert true2 + hits == true1, "hits must replace prefill work 1:1"
    assert true2 < true1
    assert eng.history[-1].prefix_hit_rate > 0
    assert all(second[100 + i] == first[i] for i in range(len(prompts)))
    eng.prefix.check_invariants()


def test_shared_tail_cow_matches_dense(rng):
    """A continuation prompt (multi-turn) matches a partially-filled cached
    tail block; the engine must copy-on-write before appending, and the
    continuation must equal a cold dense serve of the same prompt."""
    cfg, eng = _mk("paged")
    p0 = [int(x) for x in rng.integers(0, cfg.vocab_size, 12)]
    eng.submit(Request(rid=0, prompt=list(p0),
                       sampling=SamplingParams(max_new_tokens=3)))
    turn1 = eng.run(max_steps=100)[0]
    cont = list(p0) + turn1.output[:2] + [int(rng.integers(0, cfg.vocab_size))]
    eng.finished.clear()
    eng.submit(Request(rid=1, prompt=list(cont),
                       sampling=SamplingParams(max_new_tokens=4)))
    got = eng.run(max_steps=100)[0]
    assert got.prefix_hit_tokens > 0
    assert got.prefix_hit_tokens % eng.block_size != 0, "tail block matched"
    assert eng.prefix.cow_copies >= 1
    _, ref_eng = _mk("dense")
    ref_eng.params = eng.params
    ref_eng.submit(Request(rid=1, prompt=list(cont),
                           sampling=SamplingParams(max_new_tokens=4)))
    assert ref_eng.run(max_steps=100)[0].output == got.output
    eng.prefix.check_invariants()


def test_padded_vs_true_token_accounting(rng):
    """Dense bucketed prefill reports both the compute launched (bucket
    round-up) and the prompt tokens it actually served."""
    cfg, eng = _mk("dense")
    eng.submit(Request(rid=0,
                       prompt=[int(x) for x in rng.integers(0, cfg.vocab_size, 5)],
                       sampling=SamplingParams(max_new_tokens=2)))
    eng.run(max_steps=40)
    st = [s for s in eng.history if s.prefill_tokens][0]
    assert st.prefill_tokens_true == 5
    assert st.prefill_tokens_padded == 8          # rounded to bucket 8
    assert st.prefill_tokens == st.prefill_tokens_true
    # admission cost exposes the same split
    req = Request(rid=1, prompt=list(range(5)), sampling=SamplingParams())
    assert eng._admit_cost(req) == (8, 5)


def test_tight_pool_drops_tail_hit_instead_of_deadlocking(rng):
    """A request whose worst-case footprint spans the whole pool and whose
    prompt matches a cached partial tail cannot afford the CoW slack block;
    the engine must drop the tail hit and serve, not requeue forever."""
    cfg, eng = _mk("paged", capacity=1, max_len=32)   # num_blocks == 4
    p0 = [int(x) for x in rng.integers(0, cfg.vocab_size, 12)]
    eng.submit(Request(rid=0, prompt=list(p0),
                       sampling=SamplingParams(max_new_tokens=3)))
    turn1 = eng.run(max_steps=60)[0]
    cont = list(p0) + turn1.output[:2] + [int(rng.integers(0, cfg.vocab_size))]
    eng.finished.clear()
    eng.submit(Request(rid=1, prompt=list(cont),
                       sampling=SamplingParams(max_new_tokens=20)))
    done = eng.run(max_steps=120)
    assert len(done) == 1 and done[0].state.name == "DONE"
    assert done[0].prefix_hit_tokens % eng.block_size == 0, \
        "tail hit should have been dropped under block pressure"
    eng.prefix.check_invariants()


def test_paged_backend_is_per_config(rng):
    """Families with per-row state keep the dense backend even when paged
    is requested — and still serve."""
    cfg = get_config("mamba2-780m-smoke")
    eng = InferenceEngine(cfg, capacity=2, max_len=32, buckets=(8, 16),
                          kv_backend="paged", seed=0)
    assert not eng.paged
    eng.submit(Request(rid=0,
                       prompt=[int(x) for x in rng.integers(0, cfg.vocab_size, 6)],
                       sampling=SamplingParams(max_new_tokens=3)))
    assert len(eng.run(max_steps=60)) == 1


def test_paged_migration_preserves_generation(rng):
    """Block-table handoff between paged replicas: a request migrated
    mid-decode produces bit-identical greedy output to an unmigrated run,
    and both engines' block spaces stay invariant-clean."""
    from repro.core.migration import MigrationManager
    cfg, eng_a = _mk("paged")
    _, eng_b = _mk("paged")
    eng_b.params = eng_a.params
    prompt = [int(x) for x in rng.integers(0, cfg.vocab_size, 20)]
    ref_eng = _mk("paged")[1]
    ref_eng.params = eng_a.params
    ref_eng.submit(Request(rid=0, prompt=list(prompt),
                           sampling=SamplingParams(max_new_tokens=8)))
    ref = ref_eng.run(max_steps=100)[0].output

    req = Request(rid=0, prompt=list(prompt),
                  sampling=SamplingParams(max_new_tokens=8))
    eng_a.submit(req)
    for _ in range(5):                 # chunked prefill + a few decode steps
        eng_a.step()
    assert req.state.name == "DECODE" and len(req.output) >= 2
    mgr = MigrationManager()
    ev = mgr.migrate(eng_a, eng_b, rid=0, now=0.0)
    assert ev is not None and ev.bytes > 0 and ev.phase == "decode"
    done = eng_b.run(max_steps=100)
    assert done[0].output == ref
    assert done[0].migrations == 1
    eng_a.prefix.check_invariants()
    eng_b.prefix.check_invariants()


def test_paged_migration_skips_destination_cached_blocks(rng):
    """Cross-replica prefix handoff: migrating a request whose prompt the
    destination already caches transfers fewer bytes than its full
    kv_bytes, and the transferred blocks are donated into the destination
    index so a subsequent identical prompt hits them."""
    from repro.core.migration import MigrationManager
    cfg, eng_a = _mk("paged")
    _, eng_b = _mk("paged")
    eng_b.params = eng_a.params
    prompt = [int(x) for x in rng.integers(0, cfg.vocab_size, 20)]
    # warm the destination's prefix cache with the same prompt
    eng_b.submit(Request(rid=9, prompt=list(prompt),
                         sampling=SamplingParams(max_new_tokens=2)))
    ref = eng_b.run(max_steps=60)[0]
    eng_b.finished.clear()

    req = Request(rid=0, prompt=list(prompt),
                  sampling=SamplingParams(max_new_tokens=8))
    eng_a.submit(req)
    for _ in range(4):
        eng_a.step()
    full = eng_a.kv_bytes(0)
    ev = MigrationManager().migrate(eng_a, eng_b, rid=0, now=0.0)
    assert ev is not None
    assert ev.blocks_skipped > 0
    assert ev.bytes < full == ev.bytes_full, "dst-cached blocks still shipped"
    done = eng_b.run(max_steps=100)[0]
    assert done.output[:2] == ref.output    # same greedy continuation
    # donation: a fresh identical prompt now hits the migrated blocks too
    eng_b.finished.clear()
    eng_b.submit(Request(rid=1, prompt=list(prompt),
                         sampling=SamplingParams(max_new_tokens=2)))
    got = eng_b.run(max_steps=60)[0]
    assert got.prefix_hit_tokens > 0
    eng_b.prefix.check_invariants()


def test_paged_migration_rollback_and_requeue(rng, monkeypatch):
    """A refused handoff rolls back into the source; if the source cannot
    re-admit either, the request is explicitly requeued at the source
    scheduler (never silently dropped) and the failure is recorded."""
    from repro.core.migration import MigrationManager
    cfg, eng_a = _mk("paged", capacity=1)
    _, eng_b = _mk("paged", capacity=1)
    eng_b.params = eng_a.params
    pa = [int(x) for x in rng.integers(0, cfg.vocab_size, 10)]
    eng_a.submit(Request(rid=0, prompt=list(pa),
                         sampling=SamplingParams(max_new_tokens=8)))
    eng_b.submit(Request(rid=1,
                         prompt=[int(x) for x in rng.integers(0, cfg.vocab_size, 10)],
                         sampling=SamplingParams(max_new_tokens=8)))
    for _ in range(3):
        eng_a.step()
        eng_b.step()
    ref_eng = _mk("paged", capacity=1)[1]
    ref_eng.params = eng_a.params
    ref_eng.submit(Request(rid=5, prompt=list(pa),
                           sampling=SamplingParams(max_new_tokens=8)))
    ref = ref_eng.run(max_steps=100)[0].output

    # destination full -> rollback into the source, generation unharmed
    mgr = MigrationManager()
    assert mgr.migrate(eng_a, eng_b, rid=0, now=0.0) is None
    assert mgr.failures[-1].reason == "dst-full"
    assert eng_a.run(max_steps=100)[0].output == ref
    eng_a.finished.clear()

    # source cannot re-admit either -> explicit requeue, still served.
    # eng_b is drained first so the cheap probe passes and the handoff
    # reaches the adopt stage, where both engines are forced to refuse.
    eng_b.run(max_steps=100)
    eng_a.submit(Request(rid=0, prompt=list(pa),
                         sampling=SamplingParams(max_new_tokens=8)))
    for _ in range(3):
        eng_a.step()
    real_adopt = eng_a.adopt
    monkeypatch.setattr(eng_a, "adopt",
                        lambda req, payload, now=None: False)
    monkeypatch.setattr(eng_b, "adopt",
                        lambda req, payload, now=None: False)
    assert mgr.migrate(eng_a, eng_b, rid=0, now=0.0) is None
    assert mgr.failures[-1].reason == "requeued"
    assert eng_a.scheduler.depth() == 1       # back in the source queue
    monkeypatch.setattr(eng_a, "adopt", real_adopt)
    done = eng_a.run(max_steps=100)
    assert len(done) == 1 and done[0].output == ref
    eng_a.prefix.check_invariants()


def test_paged_disaggregation_hands_off_every_request(rng):
    """A paged DisaggregatedServer moves every request to the decode pool:
    multi-chunk prompts at their last chunk boundary (zero decode tokens on
    prefill engines), outputs identical to a monolithic paged serve, and
    handoff telemetry exposed per step."""
    from repro.core.disaggregation import DisaggConfig, DisaggregatedServer
    cfg = get_config(ARCH)

    def mk():
        return InferenceEngine(cfg, capacity=4, max_len=96, buckets=(8, 16),
                               kv_backend="paged", block_size=8, seed=21)

    rng_p = np.random.default_rng(3)
    prompts = [[int(x) for x in rng_p.integers(0, cfg.vocab_size, n)]
               for n in (40, 25, 33, 29)]           # all multi-chunk
    ref_eng = mk()
    for i, p in enumerate(prompts):
        ref_eng.submit(Request(rid=i, prompt=list(p),
                               sampling=SamplingParams(max_new_tokens=6)))
    ref = {r.rid: r.output for r in ref_eng.run(max_steps=300)}

    srv = DisaggregatedServer(mk, DisaggConfig(prefill_engines=1,
                                               decode_engines=2))
    srv.prefill_pool[0].params = ref_eng.params
    for e in srv.decode_pool:
        e.params = ref_eng.params
    for i, p in enumerate(prompts):
        srv.submit(Request(rid=i, prompt=list(p),
                           sampling=SamplingParams(max_new_tokens=6)))
    done = srv.run(max_steps=400)
    assert {r.rid: r.output for r in done} == ref
    assert all(r.migrations == 1 for r in done)
    assert all(e.phase == "prefill" for e in srv.migrations.events), \
        "multi-chunk prompts should hand off at a chunk boundary"
    # prefill engines never ran a decode step after (or before) handoff
    assert sum(s.tokens_out for pe in srv.prefill_pool
               for s in pe.history) == 0
    assert sum(s.handoffs_succeeded for s in srv.history) == len(prompts)
    assert sum(s.handoffs_failed for s in srv.history) == 0
    for e in srv.prefill_pool + srv.decode_pool:
        e.prefix.check_invariants()


def test_orchestrator_paged_prefix_affinity(rng):
    """Cluster layer over paged replicas: prefix-affinity routing sends a
    shared system prompt to one replica, whose cache then serves the hits;
    kv telemetry flows into the control-plane profiler."""
    from repro.core.autoscaler import HPAConfig
    from repro.core.orchestrator import Orchestrator, OrchestratorConfig
    cfg = get_config(ARCH)

    def make_engine():
        return InferenceEngine(cfg, capacity=4, max_len=64, buckets=(8, 16),
                               kv_backend="paged", block_size=8, seed=11)

    orch = Orchestrator(make_engine, OrchestratorConfig(
        min_replicas=2, lb_policy="prefix",
        hpa=HPAConfig(metric="queue", target=100.0, max_replicas=2),
        control_every_steps=4))
    system = [int(x) for x in rng.integers(0, cfg.vocab_size, 24)]
    reqs = []
    for i in range(5):
        tail = [int(x) for x in rng.integers(0, cfg.vocab_size, 4)]
        reqs.append(Request(rid=i, prompt=system + tail,
                            sampling=SamplingParams(max_new_tokens=3)))
        orch.submit(reqs[-1])
    done = orch.run(max_steps=400)
    assert len(done) == 5
    # affinity: every shared-prefix request landed on the same replica
    assert len({r.replica for r in done}) == 1
    hits = sum(s.prefix_hit_tokens
               for e in orch.engines for s in e.history)
    assert hits > 0
    assert any(orch.profiler.util[t].count() for t in orch.profiler.util
               if t.endswith("/kv"))


def _long_prompts(cfg, n_rows, seed=5):
    """Prompts of three or four 16-token chunks, one per row."""
    r = np.random.default_rng(seed)
    return [[int(x) for x in r.integers(0, cfg.vocab_size, n)]
            for n in r.integers(40, 62, n_rows)]


def test_chunk_groups_match_dense_and_alone():
    """Eight rows chunk at once on a capacity-8 pool, so a step makes two
    group calls of four: the tokens equal the dense backend's pool-wide
    chunk program and each request served alone; the chunk program
    compiled one shape and the sampler saw one."""
    cfg = get_config(ARCH)
    prompts = _long_prompts(cfg, 8)
    outs = {}
    for backend in ("dense", "paged"):
        _, eng = _mk(backend, capacity=8, max_len=96)
        sampler, shapes = eng._sampler, set()

        def spy(logits, *args):
            shapes.add(logits.shape)
            return sampler(logits, *args)

        eng._sampler = spy
        _submit_all(eng, cfg, prompts)
        outs[backend] = {r.rid: r.output for r in eng.run(max_steps=300)}
        if backend == "paged":
            assert max(s.chunk_calls for s in eng.history) >= 2
            assert eng._chunk_paged._cache_size() == 1
            assert shapes == {(8, cfg.vocab_size)}, shapes
    assert len(outs["paged"]) == len(prompts)
    assert outs["paged"] == outs["dense"]
    _, alone = _mk("paged", capacity=8, max_len=96)
    for i, p in enumerate(prompts):
        _submit_all(alone, cfg, [p], rid0=i)
        alone.run(max_steps=100)
        assert alone.finished[-1].output == outs["paged"][i]
        assert max(s.chunk_calls for s in alone.history) == 1


def test_chunk_tokens_do_not_depend_on_capacity():
    """A row's tokens do not depend on the pool's capacity or on which
    rows share its group: the same requests on a capacity-4 and a
    capacity-16 paged engine give identical tokens."""
    cfg = get_config(ARCH)
    prompts = _long_prompts(cfg, 10, seed=6)
    outs = []
    for capacity in (4, 16):
        _, eng = _mk("paged", capacity=capacity, max_len=96)
        assert eng._group == 4
        _submit_all(eng, cfg, prompts)
        outs.append({r.rid: r.output for r in eng.run(max_steps=400)})
    assert len(outs[0]) == len(prompts)
    assert outs[0] == outs[1]


@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_chunk_calls_counted_per_step(backend):
    """``StepStats.chunk_calls`` is ceil(chunk_rows / group) on the paged
    backend and one pool-wide call on the dense one; the registry's
    ``engine_chunk_calls_total`` is their sum."""
    from repro.core.metrics import parse_exposition
    cfg, eng = _mk(backend, capacity=8, max_len=96)
    _submit_all(eng, cfg, _long_prompts(cfg, 7, seed=7) + [[1, 2, 3]])
    eng.run(max_steps=300)
    G = eng._group
    for st in eng.history:
        per_call = G if backend == "paged" else eng.capacity
        assert st.chunk_calls == -(-st.chunk_rows // per_call)
    calls = sum(st.chunk_calls for st in eng.history)
    assert calls > 0
    exp = parse_exposition(eng.metrics.render())
    assert exp.value("engine_chunk_calls_total", replica="0") == calls
