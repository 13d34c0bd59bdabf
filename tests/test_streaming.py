"""Event-driven streaming: per-token events vs. final Request.output
(dense + paged + across live migration), SLO-deadline preemption, the
completions front-end (sync == streamed), and the slo_met falsy-zero fix."""

import pytest

from repro.configs import get_config
from repro.serving import (CompletionRequest, CompletionsAPI, FinishEvent,
                           FirstTokenEvent, InferenceEngine, PreemptEvent,
                           Request, SamplingParams, State, StreamDemux,
                           TokenEvent)
from repro.serving.scheduler import SchedulerConfig, deadline_risk

ARCH = "qwen2-0.5b-smoke"


def _mk(backend="dense", **kw):
    cfg = get_config(ARCH)
    kw.setdefault("capacity", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("buckets", (8, 16))
    if backend == "paged":
        kw.setdefault("block_size", 8)
    return cfg, InferenceEngine(cfg, kv_backend=backend, **kw)


def _collect(eng, demux, streamed, events):
    """Run an engine to empty, feeding every step's events through the
    shared demux into per-rid token streams."""
    t = float(len(events))
    while eng.pending() and t < 500:
        st = eng.step(now=t)
        events.extend(st.events)
        for tok in demux.feed(st.events):
            streamed.setdefault(tok.rid, []).append(tok.token)
        t += 1.0


# ------------------------------------------------------------- slo_met fix
def test_slo_met_accepts_zero_ttft_and_tpot():
    """ttft == 0.0 / tpot == 0.0 are legitimate values (first token in the
    arrival step under a logical clock) and must count as met, not be
    misread as missing by an ``(x or default)`` falsy-zero pattern."""
    r = Request(rid=0, prompt=[1], slo_ttft=0.5, slo_tpot=0.5)
    r.arrival = 10.0
    r.t_first_token = 10.0                   # ttft == 0.0
    r.token_times = [10.0, 10.0, 10.0]       # tpot == 0.0
    assert r.ttft == 0.0 and r.tpot == 0.0
    assert r.slo_met()
    # and genuinely-missing ttft still misses a ttft SLO
    r2 = Request(rid=1, prompt=[1], slo_ttft=0.5)
    r2.arrival = 0.0
    assert r2.ttft is None and not r2.slo_met()
    # a real miss still misses
    r3 = Request(rid=2, prompt=[1], slo_ttft=0.5)
    r3.arrival = 0.0
    r3.t_first_token = 2.0
    assert not r3.slo_met()


def test_deadline_risk_needs_two_tokens_and_a_slo():
    a = Request(rid=0, prompt=[1], slo_tpot=1.0)
    a.token_times = [0.0, 5.0]               # tpot 5 >= 1
    b = Request(rid=1, prompt=[1], slo_tpot=1.0)
    b.token_times = [0.0]                    # no tpot yet
    c = Request(rid=2, prompt=[1])           # no SLO
    c.token_times = [0.0, 5.0]
    assert deadline_risk([a, b, c]) == [a]
    assert deadline_risk([a], margin=10.0) == []


# --------------------------------------------------------- event semantics
@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_streamed_tokens_match_output(backend, rng):
    """Tokens streamed via events are identical to the final
    Request.output, for bucketed, chunked, and prefix-cache-hit prompts."""
    cfg, eng = _mk(backend)
    prompts = [[int(x) for x in rng.integers(0, cfg.vocab_size, n)]
               for n in (5, 11, 40, 20)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=list(p),
                           sampling=SamplingParams(max_new_tokens=6)))
    demux, streamed, events = StreamDemux(), {}, []
    _collect(eng, demux, streamed, events)
    done = {r.rid: r.output for r in eng.finished}
    assert len(done) == len(prompts)
    assert streamed == done
    firsts = [e for e in events if isinstance(e, FirstTokenEvent)]
    finishes = [e for e in events if isinstance(e, FinishEvent)]
    assert sorted(e.rid for e in firsts) == list(range(len(prompts)))
    assert sorted(e.rid for e in finishes) == list(range(len(prompts)))
    for e in finishes:
        assert e.reason == "length" and e.n_tokens == 6
    # per-request TTFT truth: the FirstTokenEvent timestamp
    for e in firsts:
        r = next(r for r in eng.finished if r.rid == e.rid)
        assert r.t_first_token == e.t and e.index == 0


def test_finish_reason_stop_token():
    import numpy as np
    cfg, eng = _mk()
    # its own generator: the prompt must not depend on which tests ran
    # earlier on this worker
    rng = np.random.default_rng(11)
    prompt = [int(x) for x in rng.integers(0, cfg.vocab_size, 9)]
    # greedy-decode once to learn a token it will emit, then stop on it
    eng.submit(Request(rid=0, prompt=list(prompt),
                       sampling=SamplingParams(max_new_tokens=8)))
    ref = eng.run(max_steps=60)[0].output
    eng.finished.clear()
    # the engine stops at the first occurrence of the stop token, so stop
    # on the first token not emitted earlier
    cut = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    eng.submit(Request(rid=1, prompt=list(prompt),
                       sampling=SamplingParams(max_new_tokens=8,
                                               stop_token=ref[cut])))
    demux, streamed, events = StreamDemux(), {}, []
    _collect(eng, demux, streamed, events)
    (req,) = eng.finished
    assert req.finish_reason == "stop"
    assert streamed[1] == req.output == ref[:cut + 1]
    fin = [e for e in events if isinstance(e, FinishEvent)]
    assert fin[-1].reason == "stop"


@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_stream_survives_mid_decode_migration(backend, rng):
    """A request migrated mid-decode keeps streaming from its new replica:
    the merged two-replica event stream carries every output token exactly
    once — no duplicates, no gaps — and matches an unmigrated run."""
    from repro.core.migration import MigrationManager
    cfg, eng_a = _mk(backend, seed=3)
    _, eng_b = _mk(backend, seed=3)
    eng_b.params = eng_a.params
    prompt = [int(x) for x in rng.integers(0, cfg.vocab_size, 9)]

    ref_eng = _mk(backend, seed=3)[1]
    ref_eng.params = eng_a.params
    ref_eng.submit(Request(rid=0, prompt=list(prompt),
                           sampling=SamplingParams(max_new_tokens=8)))
    ref = ref_eng.run(max_steps=60)[0].output

    req = Request(rid=0, prompt=list(prompt),
                  sampling=SamplingParams(max_new_tokens=8))
    eng_a.submit(req)
    demux, streamed, events = StreamDemux(), {}, []
    for t in range(4):                       # prefill + a few decode steps
        st = eng_a.step(now=float(t))
        events.extend(st.events)
        for tok in demux.feed(st.events):
            streamed.setdefault(tok.rid, []).append(tok.token)
    assert req.state is State.DECODE and len(streamed[0]) >= 2
    mgr = MigrationManager()
    ev = mgr.migrate(eng_a, eng_b, rid=0, now=4.0)
    assert ev is not None
    # the source's handoff preempt surfaces when its events are drained
    moved = eng_a.drain_events()
    events.extend(moved)
    assert any(isinstance(e, PreemptEvent) and e.reason == "migrate"
               for e in moved)
    _collect(eng_b, demux, streamed, events)
    done = eng_b.finished[0]
    assert done.migrations == 1
    assert streamed[0] == done.output == ref
    toks = [e for e in events if isinstance(e, TokenEvent) and e.rid == 0]
    assert [e.index for e in toks] == list(range(len(ref))), \
        "token indices must be gapless and duplicate-free across migration"


def test_demux_drops_rollback_reemission():
    """After a migration rollback-requeue the re-serving replica re-emits
    earlier indices; the demux keeps the downstream stream append-only."""
    d = StreamDemux()
    out = d.feed([TokenEvent(t=0.0, rid=7, token=11, index=0),
                  TokenEvent(t=1.0, rid=7, token=12, index=1)])
    assert [e.token for e in out] == [11, 12]
    # rollback: replica restarts from index 0 (greedy => same tokens)
    out = d.feed([PreemptEvent(t=2.0, rid=7, reason="requeued"),
                  TokenEvent(t=3.0, rid=7, token=11, index=0),
                  TokenEvent(t=4.0, rid=7, token=12, index=1),
                  TokenEvent(t=5.0, rid=7, token=13, index=2)])
    assert [e.token for e in out] == [13]
    with pytest.raises(RuntimeError, match="stream gap"):
        d.feed([TokenEvent(t=6.0, rid=7, token=99, index=9)])


# ----------------------------------------------------------- SLO preemption
def test_deadline_risk_decode_displaces_fresh_prefill(rng):
    """With the SLO guard on, a decode row whose TPOT is past deadline
    withholds admission and preempts the freshest mid-prefill row back to
    the queue head; the preempted request still completes with unchanged
    greedy output once the pressure clears."""
    cfg, eng = _mk(sched=SchedulerConfig(slo_guard=True,
                                         slo_guard_patience=1))
    short = [int(x) for x in rng.integers(0, cfg.vocab_size, 5)]
    long = [int(x) for x in rng.integers(0, cfg.vocab_size, 40)]  # chunked

    ref_eng = _mk()[1]
    ref_eng.params = eng.params
    ref_eng.submit(Request(rid=1, prompt=list(long),
                           sampling=SamplingParams(max_new_tokens=4)))
    ref = ref_eng.run(max_steps=60)[0].output

    a = Request(rid=0, prompt=list(short),
                sampling=SamplingParams(max_new_tokens=8), slo_tpot=2.0)
    b = Request(rid=1, prompt=list(long),
                sampling=SamplingParams(max_new_tokens=4))
    eng.submit(a, now=0.0)
    eng.step(now=0.0)                        # A: prefill + first token
    eng.step(now=1.0)                        # A decoding, tpot == 1 < 2
    eng.submit(b, now=2.0)
    st = eng.step(now=2.0)                   # no risk: B admitted, chunk 1
    assert st.n_prefill == 1 and b.state is State.PREFILL
    eng.step(now=9.0)                        # A's token lands late (gap)
    # the guard sees A's tpot (9-0)/3 = 3 >= 2 at the *next* step's check
    st = eng.step(now=10.0)
    assert st.preempted == 1 and eng.preemptions == 1
    assert b.state is State.QUEUED and b.preemptions == 1
    assert any(isinstance(e, PreemptEvent)
               and e.reason == "slo-decode-pressure" for e in st.events)
    assert st.n_prefill == 0, "admission must be withheld under risk"
    # pressure clears as A's TPOT recovers / A finishes; B then re-admits
    t = 10.0
    while eng.pending() and t < 100.0:
        eng.step(now=t)
        t += 1.0
    done = {r.rid: r for r in eng.finished}
    assert set(done) == {0, 1}
    assert done[1].output == ref, "preemption must not corrupt the output"


# ------------------------------------------------------------ the frontend
def test_completions_api_sync_and_stream_match(rng):
    cfg, eng = _mk()
    api = CompletionsAPI(eng, model=ARCH)
    prompt = [int(x) for x in rng.integers(0, cfg.vocab_size, 11)]
    resp = api.create(CompletionRequest(prompt=list(prompt), model=ARCH,
                                        max_tokens=6),
                      now=0.0)
    assert resp.choices[0].finish_reason == "length"
    assert len(resp.choices[0].tokens) == 6
    assert resp.usage.total_tokens == 11 + 6
    assert resp.x_ttft is not None

    chunks = list(api.stream(CompletionRequest(prompt=list(prompt), model=ARCH,
                                               max_tokens=6, stream=True),
                             now=100.0))
    toks = [c.choices[0]["tokens"][0] for c in chunks
            if c.choices[0]["tokens"]]
    assert toks == resp.choices[0].tokens, \
        "streaming and sync must serve byte-identical completions"
    assert chunks[-1].choices[0]["finish_reason"] == "length"
    sse = chunks[0].to_sse()
    assert sse.startswith("data: ") and sse.endswith("\n\n")


def test_completions_api_interleaved_streams(rng):
    """Concurrent stream() generators share one backend: each pump fans
    events to every open stream, frames interleave, streams stay exact."""
    cfg, eng = _mk()
    api = CompletionsAPI(eng)
    gens, want = [], []
    for i in range(3):
        p = [int(x) for x in rng.integers(0, cfg.vocab_size, 6 + i)]
        want.append(p)
        gens.append(api.stream(CompletionRequest(prompt=p, model="repro-lm",
                                                 max_tokens=5),
                               now=0.0))
    got = {i: [] for i in range(3)}
    live = list(enumerate(gens))
    while live:
        for i, g in list(live):
            try:
                chunk = next(g)
            except StopIteration:
                live.remove((i, g))
                continue
            got[i].extend(chunk.choices[0]["tokens"])
    done = sorted(eng.finished, key=lambda r: r.rid)
    assert [got[i] for i in range(3)] == [r.output for r in done]


def test_completions_api_rejects_oversized_prompt(rng):
    cfg, eng = _mk()
    api = CompletionsAPI(eng)
    resp = api.create(CompletionRequest(
        prompt=[1] * (eng.max_len + 40), model="repro-lm",
        max_tokens=4), now=0.0)
    assert resp.choices[0].finish_reason == "rejected"
    assert resp.choices[0].tokens == []
    chunks = list(api.stream(CompletionRequest(
        prompt=[1] * (eng.max_len + 40), model="repro-lm",
        max_tokens=4), now=0.0))
    assert len(chunks) == 1
    assert chunks[0].choices[0]["finish_reason"] == "rejected"


def test_completions_api_over_orchestrator(rng):
    """The same front-end backed by the cluster: events are forwarded
    through orchestrator replica steps."""
    from repro.core.autoscaler import HPAConfig
    from repro.core.orchestrator import Orchestrator, OrchestratorConfig
    cfg = get_config(ARCH)
    orch = Orchestrator(
        lambda: InferenceEngine(cfg, capacity=2, max_len=48, buckets=(8, 16),
                                seed=11),
        OrchestratorConfig(min_replicas=1, hpa=HPAConfig(
            metric="queue", target=4.0, max_replicas=2, tolerance=0.0,
            stabilization_s=0.0, scale_down_cooldown_s=1e9)))
    api = CompletionsAPI(orch)
    prompt = [int(x) for x in rng.integers(0, cfg.vocab_size, 9)]
    resp = api.create(CompletionRequest(prompt=prompt, model="repro-lm",
                                        max_tokens=5), now=0.0)
    assert len(resp.choices[0].tokens) == 5
    assert resp.choices[0].finish_reason == "length"


def test_stream_survives_disaggregated_handoff(rng):
    """Prefill->decode handoff is a mid-flight migration: the pool-wide
    event stream hands each request from the prefill engine's first token
    to the decode engine's tokens with no duplicated or dropped indices."""
    from repro.core.disaggregation import DisaggConfig, DisaggregatedServer
    cfg = get_config(ARCH)

    def mk():
        return InferenceEngine(cfg, capacity=4, max_len=64, buckets=(8, 16),
                               seed=21)

    ref_eng = mk()
    prompts = [[int(x) for x in rng.integers(0, cfg.vocab_size, 9)]
               for _ in range(3)]
    for i, p in enumerate(prompts):
        ref_eng.submit(Request(rid=i, prompt=list(p),
                               sampling=SamplingParams(max_new_tokens=6)))
    ref = {r.rid: r.output for r in ref_eng.run(max_steps=100)}

    srv = DisaggregatedServer(mk, DisaggConfig(prefill_engines=1,
                                               decode_engines=2))
    srv.prefill_pool[0].params = ref_eng.params
    for e in srv.decode_pool:
        e.params = ref_eng.params
    for i, p in enumerate(prompts):
        srv.submit(Request(rid=i, prompt=list(p),
                           sampling=SamplingParams(max_new_tokens=6)))
    demux, streamed, preempts = StreamDemux(), {}, []
    t = 0.0
    while srv.pending() and t < 200:
        srv.step(now=t)
        evs = srv.drain_events()
        preempts += [e for e in evs if isinstance(e, PreemptEvent)]
        for tok in demux.feed(evs):
            streamed.setdefault(tok.rid, []).append(tok.token)
        t += 1.0
    done = {r.rid: r.output for r in srv.run(max_steps=10)}
    assert streamed == done == ref
    assert len(preempts) == 3 and all(e.reason == "migrate"
                                      for e in preempts)
