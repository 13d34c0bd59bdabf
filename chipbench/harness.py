"""The benchmark's harness: one cell, one process, one run.

A run loads the cell's files by the names in ``BENCHMARK.json``, checks for
the chips, keeps JAX's compile cache in the checkout, builds the
configuration's endpoint registry with weights made on the device from the
seed, warms the shapes the cell's traffic uses through the served path,
offers the traffic for a ramp and then a measured window, drains the
requests due in the window, reads the peak device memory, frees the
program, and checks a sample of the served tokens against the plain
reference.  The last line of standard output is the result.

The client drives the serving program's own request path in one loop:
``CompletionRequest.to_request`` -> ``EndpointRegistry.submit`` ->
``EndpointRegistry.step`` -> the merged event stream ordered per request by
``StreamDemux``.  That is what ``CompletionsAPI.stream`` does, minus its
blocking: a stream generator pumps the backend from submission until its
own first token, so an open-loop client built on it would send every
later request at least a TTFT late.
"""
from __future__ import annotations

import dataclasses
import gc
import heapq
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np

import stats
import traffic

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
WARM_RID = 10 ** 9


# ------------------------------------------------------------------ spec
def load_cell(name: str, root: pathlib.Path = ROOT) -> SimpleNamespace:
    """The cell ``name`` and everything its entries name, from the files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    mix = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    cell = json.loads((HERE / "cells" / f"{name}.json").read_text())
    conf = deployment(json.loads((root / conf_entry["file"]).read_text()),
                      cell, w["chips"])

    def mine(metric):
        return name in metric.get("workloads", [name])
    return SimpleNamespace(
        name=name, chips=w["chips"], config=conf, mix=mix, cell=cell,
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)],
        conf_dir=(root / conf_entry["file"]).parent)


DEPLOYMENT = ("replicas", "lb_policy")


def deployment(conf: dict, cell: dict, chips: int) -> dict:
    """The configuration as the cell deploys it: the cell file's
    ``replicas`` and ``lb_policy``, where it names them, in place of the
    configuration's; every other key from the configuration.  A cell may
    not ask for more replicas than chips."""
    out = dict(conf, **{k: cell[k] for k in DEPLOYMENT if k in cell})
    if out["replicas"] > chips:
        raise SystemExit(f"chipbench: {out['replicas']} replicas on {chips} "
                         f"chip(s): a cell runs one replica a chip at most")
    return out


def load_module(path: pathlib.Path):
    """Import a file of the benchmark (a reference or a metric reader) by
    its path; its directory goes on ``sys.path`` for its own imports."""
    if str(path.parent) not in sys.path:
        sys.path.insert(0, str(path.parent))
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------- device
def require_chips(n: int) -> tuple[list, dict]:
    """The first ``n`` TPU devices and their peaks, or exit non-zero."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU; JAX found platform "
                         f"{devs[0].platform!r}")
    if len(devs) < n:
        raise SystemExit(f"chipbench: the cell needs {n} chips; JAX found "
                         f"{len(devs)}")
    return devs[:n], peaks_for(devs[0].device_kind)


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r}; "
                         f"known: {sorted(table)}")
    return table[kind]


def enable_compile_cache() -> None:
    """Keep compiled programs in the checkout (``<root>/.jax_cache``)
    unless ``JAX_COMPILATION_CACHE_DIR`` names a directory, and cache every
    program however quickly it compiled, so a second run compiles none."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def seed_key(seed: int):
    """A JAX key from any whole-number seed (wider than 32 bits too)."""
    import jax
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


# ----------------------------------------------------------------- build
def model_config(conf: dict):
    """The program's ModelConfig for ``conf``: the registered arch with
    every size the configuration file gives."""
    from repro.configs import get_config
    base = get_config(conf["arch"])
    unknown = set(conf["model"]) - {f.name for f in dataclasses.fields(base)}
    if unknown:
        raise SystemExit(f"chipbench: {conf['arch']} has no fields {unknown}")
    return dataclasses.replace(base, **conf["model"])


def make_weights(conf: dict, ref, seed: int, device):
    """The canonical weights, made on ``device`` in one jitted call."""
    import jax
    from jax.sharding import SingleDeviceSharding
    init = jax.jit(lambda k: ref.init(k, conf["model"]),
                   out_shardings=SingleDeviceSharding(device))
    return init(seed_key(seed))


def build(conf: dict, ref, weights, cfg):
    """The endpoint registry of ``conf['replicas']`` replicas serving the
    given weights (replicas on further chips receive copies)."""
    import jax
    from repro.core.endpoints import EndpointRegistry, ModelEndpoint
    from repro.models import params as P
    from repro.models.lm import make_model
    from repro.serving import InferenceEngine

    prog = ref.to_program(weights)
    want = P.abstract(make_model(cfg).param_specs())
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), prog)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise SystemExit(f"chipbench: {conf['reference']} lays out weights "
                         f"unlike the program's {conf['arch']}")
    eng = dict(conf["engine"], buckets=tuple(conf["engine"]["buckets"]))
    n = conf["replicas"]
    spec = ModelEndpoint(name=conf["arch"], model=cfg, min_replicas=n,
                         max_replicas=n, cold_start_steps=0,
                         lb_policy=conf["lb_policy"], seed=0, **eng)
    spec.make_engine = lambda: InferenceEngine(cfg, params=prog, seed=0, **eng)
    reg = EndpointRegistry([spec])
    orch = reg.resolve(conf["arch"])
    jax.block_until_ready([e.params for e in orch.engines])
    return reg, orch


def warm_lengths(conf: dict, mix: dict) -> list[int]:
    """Prompt lengths that reach every program the mix's prompts can:
    on the paged backend one prompt of two chunks (the chunk program at
    its one shape); on the dense backend one prompt per bucket the mix
    can fill, and one of two chunks where its prompts can pass the largest
    bucket."""
    buckets = conf["engine"]["buckets"]
    top = mix["max_total"] - mix["answer_tokens"]["min"]
    if not mix.get("history"):
        sp = mix.get("system_prompts")
        top = min(top, mix["user_tokens"]["max"] + (sp["tokens"] if sp else 0))
    if conf["engine"]["kv_backend"] == "paged":
        return [buckets[-1] + buckets[-1] // 2]
    lo = mix["user_tokens"]["min"]
    out, prev = [], 0
    for b in buckets:
        if b >= lo and prev < top:
            out.append(max(lo, min(b, top), prev + 1))
        prev = b
    if top > buckets[-1]:
        out.append(buckets[-1] + buckets[-1] // 2)
    return out


def warm(reg, orch, conf: dict, mix: dict, vocab: int) -> None:
    """Run the served path over fixed warm-up requests until every program
    the cell's traffic uses has compiled: on every replica, each prompt
    length of :func:`warm_lengths`, then (paged) the first prompt again
    with its answer and a few more tokens, which hits the prefix cache up
    to a partly filled block and so copies that block on write."""
    from repro.serving import Request, SamplingParams
    rng = np.random.default_rng(12345)
    rid = WARM_RID

    def serve(eng, prompts, n_out):
        nonlocal rid
        reqs = []
        for p in prompts:
            reqs.append(Request(rid=rid, prompt=list(p), model=conf["arch"],
                                sampling=SamplingParams(max_new_tokens=n_out)))
            rid += 1
            eng.submit(reqs[-1])
        for _ in range(10_000):
            if all(r.done() for r in reqs):
                reg.drain_events()
                return reqs
            reg.step()
        raise RuntimeError("warm-up requests unfinished")

    for eng in orch.engines:
        prompts = [rng.integers(0, vocab, n).tolist()
                   for n in warm_lengths(conf, mix)]
        done = serve(eng, prompts, 3)
        if conf["engine"]["kv_backend"] == "paged":
            first = done[0]
            serve(eng, [first.prompt + first.output +
                        rng.integers(0, vocab, 40).tolist()], 2)


def prime(reg, conf: dict, rounds: list[list[list[int]]]) -> list[list]:
    """Leave each round's prompts in the prefix cache, as a deployment
    that served them before holds them: each is served once with a
    one-token answer (the paged backend indexes a finished request's
    blocks), a round after the one before it has finished.  Returns each
    round's served requests (``Request.replica`` says where each went)."""
    from repro.serving import Request, SamplingParams
    if conf["engine"]["kv_backend"] != "paged":
        return [[] for _ in rounds]
    rid = WARM_RID
    served = []
    for prompts in rounds:
        reqs = []
        for p in prompts:
            rid -= 1
            reqs.append(Request(rid=rid, prompt=list(p), model=conf["arch"],
                                sampling=SamplingParams(max_new_tokens=1)))
            reg.submit(reqs[-1])
        for _ in range(100_000):
            if all(r.done() for r in reqs):
                break
            reg.step()
        else:
            raise RuntimeError("priming requests unfinished")
        reg.drain_events()
        served.append(reqs)
    return served


def plan(conf: dict, mix: dict, seed: int, seconds: float, ramp_s: float,
         rate_per_s: float | None = None) -> SimpleNamespace:
    """The run's sessions, token ids and prefix-cache primes."""
    sess = traffic.sessions(mix, ramp_s + seconds, rate_per_s)
    toks = traffic.Tokens(mix, seed, conf["token_ids_below"])
    return SimpleNamespace(sessions=sess, tokens=toks,
                           primes=traffic.primes(mix, toks, sess),
                           seconds=seconds, ramp_s=ramp_s)


# ---------------------------------------------------------------- client
@dataclasses.dataclass
class Rec:
    """One request as its client saw it."""
    rid: int
    session: int
    turn: int
    due: float
    sent: float
    prompt: list[int]
    max_tokens: int
    req: object = None
    times: list[float] = dataclasses.field(default_factory=list)
    tokens: list[int] = dataclasses.field(default_factory=list)
    steps: list[int] = dataclasses.field(default_factory=list)
    finish: str | None = None
    rejected: bool = False


def drive(reg, conf: dict, mix: dict, p: SimpleNamespace, *, drain_s: float,
          trace_s: float = 0.0, trace_dir: str | None = None,
          rid0: int = 0) -> SimpleNamespace:
    """Offer the planned traffic for its ramp and then a window of its
    seconds.  Open loop: after the close, serve on until every request due
    in the window has its first token (at most ``drain_s`` past the
    close).  Closed loop: stop at the close.  With
    ``trace_s`` the profiler records the first ``trace_s`` seconds of the
    window into ``trace_dir``."""
    import jax
    from jax.profiler import TraceAnnotation
    from repro.serving import CompletionRequest, StreamDemux
    from repro.serving.events import FinishEvent

    sess, toks = p.sessions, p.tokens
    open_loop = mix["loop"] == "open"
    recs: dict[int, Rec] = {}
    order: list[Rec] = []
    served: dict[int, dict[int, list[int]]] = {}    # session -> turn -> answer
    steps: list[tuple[float, float]] = []
    demux = StreamDemux()
    rid = rid0
    t0 = time.perf_counter()
    w0, w1 = t0 + p.ramp_s, t0 + p.ramp_s + p.seconds
    due = [(t0 + s.due_s, si, s.first) for si, s in enumerate(sess)]
    heapq.heapify(due)
    traced = None
    tracing = False
    t_trace = w1

    def send(t_due, si, k, now):
        nonlocal rid
        s = sess[si]
        prompt = traffic.prompt_for(mix, toks, s, k, served.get(si))
        if prompt is None:
            return
        creq = CompletionRequest(prompt=prompt, model=conf["arch"],
                                 max_tokens=s.turns[k][1], temperature=0.0)
        req = creq.to_request(rid)
        req.arrival = t_due
        r = Rec(rid=rid, session=si, turn=k, due=t_due, sent=now,
                prompt=prompt, max_tokens=s.turns[k][1], req=req)
        rid += 1
        recs[r.rid] = r
        order.append(r)
        if not reg.submit(req, now):
            r.rejected = True

    def finished(r, t):
        s = sess[r.session]
        served.setdefault(r.session, {})[r.turn] = list(r.tokens)
        if r.turn + 1 < len(s.turns):
            heapq.heappush(due, (t + s.think_s[r.turn], r.session, r.turn + 1))

    def waiting():
        return open_loop and any(w0 <= r.due < w1 and not r.times
                                 and not r.rejected for r in order)

    while True:
        now = time.perf_counter()
        if trace_s and not tracing and traced is None and now >= w1 - trace_s:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # the host spans are enough
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing, traced, t_trace = True, [len(steps), None], now
        if tracing and now >= w1:
            # writing the trace stalls this loop for seconds: after the close
            jax.profiler.stop_trace()
            tracing, traced[1] = False, len(steps)
        with TraceAnnotation("bench.generator"):
            while due and due[0][0] <= now:
                t_due, si, k = heapq.heappop(due)
                if t_due < w1:
                    send(t_due, si, k, now)
        if now >= w1 and (not waiting() or now >= w1 + drain_s):
            break
        if reg.pending():
            with TraceAnnotation("bench.registry_step"):
                reg.step(now)
            t = time.perf_counter()
            steps.append((now, t, reg.pending()))
            with TraceAnnotation("bench.api_pump"):
                events = reg.drain_events()
                for ev in demux.feed(events):
                    r = recs.get(ev.rid)
                    if r is not None:
                        r.times.append(t)
                        r.tokens.append(ev.token)
                        r.steps.append(len(steps) - 1)
                for ev in events:
                    if isinstance(ev, FinishEvent) and ev.rid in recs:
                        r = recs[ev.rid]
                        r.finish = ev.reason
                        finished(r, t)
        else:
            with TraceAnnotation("bench.wait"):
                nxt = due[0][0] if due else now + 1e-3
                time.sleep(min(max(nxt - time.perf_counter(), 0.0), 1e-3))
    if tracing:
        jax.profiler.stop_trace()
        traced[1] = len(steps)
    for r in order:
        if r.req.state.name == "REJECTED":
            r.rejected = True
    return SimpleNamespace(recs=order, steps=steps, w0=w0, w1=w1, t0=t0,
                           traced=traced, t_trace=t_trace, seconds=p.seconds,
                           open_loop=open_loop)


# ---------------------------------------------------------- correctness
def sample(recs, seed: int, want_tokens: int, max_reqs: int,
           moved=frozenset()) -> list[Rec]:
    """Finished requests drawn from the seed: the longest first, then the
    first drawn of each replica that has none in the sample yet (so every
    replica's copy of the weights is checked), then those whose rid is in
    ``moved`` (requests that migrated between replicas, so the exchange of
    their cache is checked) while fewer than ``max_reqs``, then more until
    ``want_tokens`` served tokens or ``max_reqs`` requests."""
    done = [r for r in recs if r.finish is not None and r.tokens]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), r.rid))
    rest = [r for r in done if r is not longest]
    order = [rest[i] for i in np.random.default_rng([seed, 5]).permutation(len(rest))]
    out, seen = [longest], {longest.req.replica}
    for r in order:
        if r.req.replica not in seen:
            out.append(r)
            seen.add(r.req.replica)
    picked = {r.rid for r in out}
    for r in order:
        if r.rid in moved and r.rid not in picked and len(out) < max_reqs:
            out.append(r)
            picked.add(r.rid)
    n = sum(len(r.tokens) for r in out)
    for r in order:
        if n >= want_tokens or len(out) >= max_reqs:
            break
        if r.rid not in picked:
            out.append(r)
            n += len(r.tokens)
    return out


def _pad_len(n: int, quantum: int) -> int:
    return max(quantum, 1 << (n - 1).bit_length()) if n > quantum else quantum


def gap_fn(ref, m: dict, control: bool):
    """Jitted per-token gaps for one sequence: how far below the float32
    reference's best logit lies the served token's (``control``: also the
    token that the float8 reference puts first)."""
    import jax
    import jax.numpy as jnp

    def fn(w, tokens, at, served):
        lg = ref.logits(w, m, tokens, at)
        best = lg.max(-1)
        gap = best - jnp.take_along_axis(lg, served[:, None], 1)[:, 0]
        if not control:
            return gap, gap
        c = ref.logits(w, m, tokens, at, fp8=True).argmax(-1)
        return gap, best - jnp.take_along_axis(lg, c[:, None], 1)[:, 0]
    return jax.jit(fn)


def reference_gaps(ref, m: dict, weights, recs: list[Rec], *,
                   control: bool = False, quantum: int = 256,
                   max_out: int = 512) -> tuple[np.ndarray, np.ndarray]:
    """Gaps of every served token of ``recs`` (and, with ``control``, of
    the float8 reference's choices at the same positions)."""
    import jax
    fn = gap_fn(ref, m, control)
    prog, ctrl = [], []
    for r in recs:
        n, k = len(r.prompt), len(r.tokens)
        seq = r.prompt + r.tokens[:-1]
        S = _pad_len(len(seq), quantum)
        tokens = np.zeros(S, np.int32)
        tokens[:len(seq)] = seq
        K = max(max_out, k)
        at = np.zeros(K, np.int32)
        at[:k] = np.arange(n - 1, n - 1 + k)
        served = np.zeros(K, np.int32)
        served[:k] = r.tokens
        with jax.default_matmul_precision("highest"):
            g, c = fn(weights, tokens, at, served)
        prog.append(np.asarray(g)[:k])
        ctrl.append(np.asarray(c)[:k])
    return np.concatenate(prog), np.concatenate(ctrl)


def judge(c: dict, gap: float, faults: int, tokens: int,
          attempted: bool) -> tuple[bool, dict]:
    """``correct`` for a run whose served tokens read the widest logit gap
    ``gap`` over ``tokens`` checked tokens, with ``faults`` bad streams; and
    each number compared beside its limit (``logit_gap`` and
    ``bad_streams`` are upper limits, ``tokens_checked`` a lower one)."""
    checks = {
        "logit_gap": {"value": gap, "limit": c["limits"]["logit_gap"]},
        "bad_streams": {"value": faults, "limit": 0},
        "tokens_checked": {"value": tokens, "limit": c["check_tokens"] // 2},
    }
    correct = bool(gap <= c["limits"]["logit_gap"] and faults == 0
                   and attempted and tokens >= c["check_tokens"] // 2)
    return correct, checks


def stream_faults(recs) -> int:
    """Finished requests whose stream is not the program's own output or
    is shorter than asked (no mix has a stop token)."""
    return sum(1 for r in recs if r.finish is not None and
               (r.tokens != list(r.req.output) or len(r.tokens) != r.max_tokens))


# -------------------------------------------------------------- metrics
def end_to_end(name: str, run, setup_s: float) -> float:
    w0, w1 = run.w0, run.w1
    if name == "setup_s":
        return setup_s
    if name == "ttft_p90_s":
        v = stats.percentile(stats.ttft(run.recs, w0, w1), 90)
        # a failed request at the 90th percentile: report the least it
        # could be, the time from its due time to the end of the drain
        return v if math.isfinite(v) else run.end - min(
            r.due for r in run.recs if w0 <= r.due < w1 and not r.times)
    if name == "itl_p95_s":
        return stats.percentile(stats.gaps(run.recs, w0, w1), 95)
    if name == "output_tok_s":
        return stats.tokens_in(run.recs, w0, w1) / (w1 - w0)
    raise SystemExit(f"chipbench: no end-to-end metric {name!r}")


def window_requests(r) -> list[Rec]:
    """The window's attempted requests.  Open loop: those due in it (one
    that was refused, or had no token when the drain ended, failed).
    Closed loop: those whose answer ended, or that were refused, in it."""
    if r.open_loop:
        return [x for x in r.recs if r.w0 <= x.due < r.w1]
    return [x for x in r.recs
            if (x.rejected and r.w0 <= x.sent < r.w1)
            or (x.finish is not None and r.w0 <= x.times[-1] < r.w1)]


def lateness(recs) -> str:
    late = [r.sent - r.due for r in recs]
    if not late:
        return "generator: no request sent"
    return (f"generator lateness over {len(late)} requests: p50 "
            f"{stats.percentile(late, 50):.6f} s, p99 "
            f"{stats.percentile(late, 99):.6f} s, max {max(late):.6f} s")


class Watch:
    """Host-side events that should not happen inside the window: a
    program traced or compiled (or fetched from the compile cache), and
    pauses of Python's garbage collector."""

    COMPILE = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/backend_compile_duration",
               "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax
        self.compiles: list[tuple[float, str, float]] = []
        self.gc: list[tuple[float, float]] = []
        self._gc0 = None
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        gc.callbacks.append(self._on_gc)

    def _on_event(self, name, duration, **_):
        if name in self.COMPILE:
            self.compiles.append((time.perf_counter(), name, duration))

    def _on_gc(self, phase, info):
        if phase == "start":
            self._gc0 = time.perf_counter()
        elif self._gc0 is not None:
            self.gc.append((self._gc0, time.perf_counter() - self._gc0))

    def report(self, w0: float, w1: float) -> str:
        c = [x for x in self.compiles if w0 <= x[0] < w1]
        g = [d for t, d in self.gc if w0 <= t < w1]
        return (f"in the window: {len(c)} trace/compile/cache events "
                f"({sum(x[2] for x in c):.3f} s, at "
                f"{[round(x[0] - w0, 3) for x in c[:10]]} s), {len(g)} garbage "
                f"collections (longest {max(g, default=0.0):.4f} s)")


def migrations(mgr, w0: float, w1: float) -> str:
    """The router's moves of live requests between replicas in [w0, w1)."""
    moved = [e for e in mgr.events if w0 <= e.t < w1]
    failed = [f for f in mgr.failures if w0 <= f.t < w1]
    return (f"migrations in the window: {len(moved)} moved "
            f"({sum(e.bytes for e in moved)} bytes, at "
            f"{[round(e.t - w0, 3) for e in moved]} s), {len(failed)} failed "
            f"({sorted({f.reason for f in failed})})")


def memory_peaks(devices) -> list[int]:
    """Each device's peak bytes in use so far."""
    return [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in devices]


# ------------------------------------------------------------------ run
def run(cell: SimpleNamespace, seed: int, seconds: float, trace: bool, *,
        devices, peaks: dict, t_start: float, log=print) -> dict:
    """One measured run of ``cell``; returns the result object."""
    import jax
    watch = Watch()
    conf, mix, c = cell.config, cell.mix, cell.cell
    ref = load_module(cell.conf_dir / conf["reference"])
    cfg = model_config(conf)
    weights = make_weights(conf, ref, seed, devices[0])
    reg, orch = build(conf, ref, weights, cfg)
    log(f"built {len(orch.engines)} replica(s) of {conf['arch']} on "
        f"{[str(e.device) for e in orch.engines]}")
    warm(reg, orch, conf, mix, conf["token_ids_below"])
    p = plan(conf, mix, seed, seconds, c["ramp_s"])
    primed = prime(reg, conf, p.primes)[-1]
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    r = drive(reg, conf, mix, p, drain_s=c["drain_s"],
              trace_s=min(seconds, c["trace_s"]) if trace else 0.0,
              trace_dir=trace_dir)
    r.end = time.perf_counter()
    log(lateness(r.recs))
    log(watch.report(r.w0, r.w1))
    attempted = window_requests(r)
    failed = [x for x in attempted if x.rejected or not x.times]
    half = (r.w0 + r.w1) / 2
    log(f"window {seconds} s: {len(attempted)} attempted, {len(failed)} "
        f"failed, {stats.tokens_in(r.recs, r.w0, r.w1)} tokens "
        f"({stats.tokens_in(r.recs, r.w0, half)} in the first half, "
        f"{stats.tokens_in(r.recs, half, r.w1)} in the second), "
        f"{len(r.steps)} steps")
    in_use = memory_peaks(devices)
    peak = max(in_use)
    if len(devices) > 1:
        log(f"peak bytes in use per chip: {in_use}")
    histories = [list(e.history) for e in orch.engines]
    if len(orch.engines) > 1:
        log(migrations(orch.migrations, r.w0, r.w1))
    primed = [(q.prompt, q.replica) for q in primed]
    moved = {e.rid for e in orch.migrations.events}
    for x in r.recs:          # keep what the readers need, drop the program
        x.req = SimpleNamespace(output=list(x.req.output), t_admit=x.req.t_admit,
                                prefix_hit_tokens=x.req.prefix_hit_tokens,
                                replica=x.req.replica)
    del reg, orch
    gc.collect()
    jax.clear_caches()

    metrics, breakdown, device = {}, None, {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        import tracefile as tr
        summary = None
        files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        if files:
            summary = tr.reduce(tr.read(str(files[-1])),
                                chips=[d.id for d in devices])
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = tr.breakdown(summary)
        # the readers of program counters and spans take the window up to
        # the traced seconds, which the profiler slows
        info = SimpleNamespace(m=conf["model"], conf=conf, recs=r.recs,
                               w0=r.w0, w1=r.t_trace, traced=r.traced,
                               trace=summary, peaks=peaks, histories=histories,
                               primed=primed)
        for spec in cell.per_layer:
            reader = load_module(HERE / "layer_metrics" / f"{spec['name']}.py")
            v = reader.read(info)
            if v is not None:
                metrics[spec["name"]] = {"value": v, "unit": spec["unit"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for spec in cell.end_to_end:
            metrics[spec["name"]] = {"value": end_to_end(spec["name"], r, setup_s),
                                     "unit": spec["unit"]}

    chosen = sample(r.recs, seed, c["check_tokens"], c["check_requests"], moved)
    t = time.perf_counter()
    gaps, _ = (reference_gaps(ref, conf["model"], weights, chosen)
               if chosen else (np.zeros(0), None))
    worst = float(gaps.max(initial=0.0))
    faults = stream_faults(r.recs)
    log(f"reference: {len(chosen)} requests from replicas "
        f"{sorted({x.req.replica for x in chosen})} ({len(moved)} migrated, "
        f"{sum(x.rid in moved for x in chosen)} of them checked), {len(gaps)} "
        f"served tokens, {time.perf_counter() - t:.3f} s")
    correct, checks = judge(c, worst, faults, len(gaps), bool(attempted))
    out = {"correct": correct, "attempted": len(attempted),
           "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
