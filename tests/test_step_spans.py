"""Host spans inside the engine step and stable names for the engine's
jitted programs: the spans' durations in ``StepStats``, their nesting in a
``jax.profiler`` capture, and the program names the capture shows."""
import glob
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core.metrics import parse_exposition
from repro.core.orchestrator import Orchestrator, OrchestratorConfig
from repro.core.transport import Transport
from repro.serving import InferenceEngine, Request, SamplingParams

ARCH = "qwen2-0.5b-smoke"
PHASES = ("engine.schedule", "engine.prefill", "engine.chunk",
          "engine.decode", "engine.observe")
# what the engine launches: its own programs, the sampler, and the two
# operations JAX names itself (the sampler's key split and its unpacking)
PROGRAM = re.compile(r"^(decode|decode_live|decode_paged|chunk|chunk_paged|"
                     r"prefill_\d+x\d+|insert_rows|copy_block|sample)$")
EAGER = {"_threefry_split", "_unstack"}


def _engine(backend, **kw):
    cfg = get_config(ARCH)
    return cfg, InferenceEngine(cfg, kv_backend=backend, capacity=4,
                                max_len=64, buckets=(8, 16), block_size=8,
                                seed=0, **kw)


def _submit(eng, cfg, lengths, rid0=0, max_new=5):
    rng = np.random.default_rng(rid0)
    for i, n in enumerate(lengths):
        eng.submit(Request(rid=rid0 + i,
                           prompt=[int(x) for x in
                                   rng.integers(0, cfg.vocab_size, n)],
                           sampling=SamplingParams(max_new_tokens=max_new)))


def _run(eng, steps=200):
    for _ in range(steps):
        if not eng.pending():
            break
        eng.step()


@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_step_times_nest(backend):
    """Short prompts (bucketed on dense), long ones (chunked) and decode:
    the whole step bounds its waits, its decode and its prefill part."""
    cfg, eng = _engine(backend)
    _submit(eng, cfg, (5, 30, 12, 40))
    _run(eng)
    assert len(eng.finished) == 4
    h = eng.history
    assert any(st.chunk_rows for st in h)
    assert any(st.prefill_tokens and st.tokens_out for st in h)
    assert any(st.tokens_out and not st.prefill_tokens for st in h)
    for st in h:
        assert 0.0 <= st.wait_s <= st.step_s
        assert 0.0 <= st.decode_s <= st.step_s
        assert 0.0 < st.prefill_s <= st.step_s
        if st.tokens_out:       # the sampled tokens come back to the host
            assert st.decode_s > 0 and st.wait_s > 0


@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_step_histogram_takes_each_phase(backend):
    cfg, eng = _engine(backend)
    _submit(eng, cfg, (5, 30))
    _run(eng)
    exp = parse_exposition(eng.metrics.render())
    count = {ph: exp.samples.get(("engine_step_seconds_count",
                                  (("phase", ph), ("replica", "0"))))
             for ph in ("schedule", "prefill", "chunk", "decode", "wait",
                        "observe")}
    steps = len(eng.history)
    assert count["schedule"] == count["observe"] == steps
    assert count["decode"] == sum(1 for st in eng.history if st.tokens_out)
    assert count["wait"] == sum(1 for st in eng.history if st.wait_s > 0)
    assert count["chunk"] == sum(1 for st in eng.history if st.chunk_rows)
    if backend == "paged":      # every prompt goes through the chunk program
        assert count["prefill"] is None
    else:
        assert count["prefill"] >= 1
    total = exp.value("engine_step_seconds_sum", replica="0", phase="decode")
    assert total >= sum(st.decode_s for st in eng.history)


def _profile(tmp_path, fn):
    with jax.profiler.trace(str(tmp_path)):
        fn()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
    return events


def _inside(ev, outer):
    return any(o[1] <= ev[1] and ev[2] <= o[2] for o in outer)


@pytest.mark.parametrize("backend", ["dense", "paged"])
def test_profile_spans_nest_and_programs_are_named(backend, tmp_path):
    cfg, eng = _engine(backend)
    _submit(eng, cfg, (5, 12), rid0=0)
    _run(eng)                                   # compile outside the capture
    _submit(eng, cfg, (5, 30, 12, 40), rid0=10)
    events = _profile(tmp_path, lambda: _run(eng))
    named = lambda *names: [e for e in events if e[0] in names]  # noqa: E731
    steps = named("engine.step")
    assert len(steps) >= 5
    for ph in PHASES:
        spans = named(ph)
        assert spans or ph == "engine.prefill" and backend == "paged", ph
        assert all(_inside(e, steps) for e in spans), ph
    waits = named("engine.wait")
    assert waits and all(_inside(e, named(*PHASES)) for e in waits)

    # the programs the steps launched, outermost calls only (a call traced
    # inside another program's trace is not a launch of its own)
    calls = [(e[0][len("PjitFunction("):-1], e[1], e[2]) for e in events
             if e[0].startswith("PjitFunction(") and _inside(e, steps)]
    top = {c[0] for c in calls
           if not any(o is not c and o[1] <= c[1] and c[2] <= o[2]
                      and (o[1], o[2]) != (c[1], c[2]) for o in calls)}
    assert not any("lambda" in n for n in top), top
    assert all(PROGRAM.match(n) or n in EAGER for n in top), top
    want = ({"decode_paged", "chunk_paged", "sample"} if backend == "paged"
            else {"decode", "decode_live", "chunk", "prefill_8x4",
                  "prefill_16x4", "insert_rows", "sample"})
    assert want <= top, top
    if backend == "paged":
        # a step whose rows chunk launches the group calls, the decode and
        # the sampler, and no eager scatter or gather of their inputs
        chunk_steps = [s for s in steps
                       if any(_inside(c, [s]) for c in named("engine.chunk"))]
        launched = {c[0] for c in calls
                    if c[0] in top and _inside(c, chunk_steps)}
        assert "chunk_paged" in launched, launched
        assert launched - EAGER <= {"chunk_paged", "decode_paged", "sample"}, \
            launched


def test_every_engine_program_has_a_stable_name():
    for backend in ("dense", "paged"):
        _, eng = _engine(backend)
        progs = [eng._sampler, eng._decode, eng._decode_live, eng._insert,
                 eng._chunk_program(), eng._prefill_fn(16, eng._group)]
        if backend == "paged":
            progs += [eng._decode_paged, eng._chunk_paged, eng._copy_block]
        names = [p.__name__ for p in progs]
        assert all(PROGRAM.match(n) for n in names), names
        assert "prefill_16x4" in names


def test_orchestrator_spans(tmp_path):
    cfg = get_config(ARCH)

    def make():
        return InferenceEngine(cfg, capacity=2, max_len=48, buckets=(8, 16))

    orch = Orchestrator(make, OrchestratorConfig(control_every_steps=2,
                                                 transport=Transport()))
    rng = np.random.default_rng(3)
    for i in range(3):
        orch.submit(Request(rid=i, prompt=[int(x) for x in
                                           rng.integers(0, cfg.vocab_size, 6)],
                            sampling=SamplingParams(max_new_tokens=4)))
    orch.step()
    events = _profile(tmp_path, lambda: orch.run(max_steps=50))
    assert orch.spans.seconds["cluster.control"] > 0
    assert orch.spans.seconds["cluster.transport"] > 0
    steps = [e for e in events if e[0] == "engine.step"]
    for name in ("cluster.control", "cluster.transport"):
        spans = [e for e in events if e[0] == name]
        assert spans and not any(_inside(e, steps) for e in spans)
