"""Continuous-batching inference engine (Orca-style iteration scheduling).

One engine = one model replica on one device: its params, caches and every
per-step input are committed to that device.  Static shapes
throughout: a fixed decode batch of ``capacity`` rows over a ``RowPool``,
prefill bucketed to a few lengths, per-row sampling parameter vectors — so
the engine never recompiles as the request mix changes.

Prefill is a pipeline, not a one-request-at-a-time call:

* requests admitted in the same step are grouped by bucket and prefilled as
  one batched program per bucket (group size padded to a fixed power of two
  so each bucket compiles exactly once);
* prompts longer than the largest bucket are **chunked**: bucket-sized
  slices append into the row's KV/SSM cache across steps instead of raising,
  so a long prompt is a supported scenario and per-step prefill work stays
  bounded (``SchedulerConfig.prefill_token_budget``) to limit head-of-line
  blocking of running decodes.  On the dense backend one chunk program
  covers the whole pool — idle rows ride along as exact no-ops; the paged
  backend, whose pools are indexed only through the block table, runs its
  chunk program over the advancing rows alone, a fixed group at a time.

The control plane (core/) consumes the per-step telemetry this engine
emits; the same engine class serves as the *real* backend behind the
cluster simulator's cost model.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.configs.perf import BASELINE, PerfConfig
from repro.models import params as P
from repro.models.lm import make_model
from repro.serving.events import (EngineEvent, FinishEvent, FirstTokenEvent,
                                  PreemptEvent, TokenEvent)
from repro.serving.kv_cache import RowPool
from repro.serving.prefix_cache import PrefixCache
from repro.serving.request import Request, State
from repro.serving.sampling import make_sampler
from repro.serving.scheduler import Scheduler, SchedulerConfig, deadline_risk


def _default_device() -> jax.Device:
    """Where uncommitted arrays land here: the device of an enclosing
    ``jax.default_device`` scope, else the first device."""
    d = jax.config.jax_default_device
    return d if isinstance(d, jax.Device) else jax.devices(d)[0]


def _jit(name: str, fn, **kw):
    """``jax.jit`` of ``fn`` under a stable program name: the compiled
    module is ``jit_<name>``, which is what a profile's ``XLA Modules`` line
    shows for it."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return jax.jit(program, **kw)


def _round_bucket(n: int, buckets: tuple[int, ...]) -> int:
    for b in buckets:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds largest bucket {buckets[-1]}")


@dataclasses.dataclass
class StepStats:
    t: float
    decode_s: float
    prefill_s: float
    n_prefill: int
    occupancy: int
    queue_depth: int
    tokens_out: int
    prefill_tokens: int = 0     # prompt tokens prefilled this step (all paths)
    chunk_rows: int = 0         # rows advanced by the chunked-prefill program
    chunk_calls: int = 0        # launches of the chunk program (paged: one
                                # per group of ``_group`` rows)
    # cost-model split: what the step computed vs. what requests needed.
    # Dense charges bucket round-up / chunk slice width; the paged backend's
    # chunk program masks rather than pads per row, so there padded == true
    # (both cache-aware: prefix hits are never charged).
    prefill_tokens_padded: int = 0  # incl. bucket round-up / chunk slice width
    prefill_tokens_true: int = 0    # actual prompt tokens advanced
    # paged-KV / prefix-cache telemetry (zero on the dense backend)
    prefix_hit_tokens: int = 0      # prompt tokens skipped at admission
    prefix_hit_rate: float = 0.0    # cumulative token hit rate
    kv_blocks_used: int = 0         # blocks referenced by live rows
    kv_blocks_cached: int = 0       # blocks retained by the prefix index
    kv_util: float = 0.0            # live-block (paged) / row (dense) fraction
    kv_frag: float = 0.0            # wasted tail-of-block slots / allocated
    # per-request events this step emitted (serving/events.py): every output
    # token, first tokens, finishes, preemptions — the streaming front-end
    # and the control plane consume these instead of per-step aggregates
    events: list[EngineEvent] = dataclasses.field(default_factory=list)
    preempted: int = 0              # rows displaced by the SLO guard this step
    # host spans (core/tracing.HostSpans): the whole step, and the part of
    # it the host spent blocked on the device (every ``engine.wait``)
    step_s: float = 0.0
    wait_s: float = 0.0


class InferenceEngine:
    def __init__(self, cfg: ModelConfig, params=None, *,
                 capacity: int = 8, max_len: int = 128,
                 perf: PerfConfig = BASELINE,
                 sched: SchedulerConfig = SchedulerConfig(),
                 buckets: tuple[int, ...] = (16, 32, 64),
                 kv_backend: str = "dense",
                 block_size: int = 16, num_blocks: int | None = None,
                 enable_prefix_cache: bool = True,
                 seed: int = 0, tracer=None, metrics=None):
        assert kv_backend in ("dense", "paged")
        # the replica's device: built under ``jax.default_device(d)`` (the
        # orchestrator places each replica so), the engine lives on d
        self.device = _default_device()
        self.cfg = cfg
        self.perf = perf
        self.model = make_model(cfg, perf)
        self.capacity = capacity
        self.max_len = max_len
        self.buckets = tuple(sorted(buckets))
        self.chunk = self.buckets[-1]       # chunked-prefill slice length
        # chunked prefill appends at absolute text positions — it covers pure
        # decoders; vision-prefix and encoder-decoder requests stay bucketed
        self._can_chunk = not (cfg.is_encoder_decoder or cfg.num_vision_tokens)
        # paged KV backend: pure global-attention decoders only; families
        # with per-row state (SSM/conv, ring slots, enc-dec, vision prefix)
        # keep the dense RowPool backend — the engine chooses per config
        self.paged = kv_backend == "paged" and self.model.supports_paged()
        self.params = (self._init_on_device(self.model.param_specs(), seed)
                       if params is None else self._move(params))
        self.scheduler = Scheduler(sched)
        self.pool = RowPool(capacity)
        self.key = self._move(jax.random.PRNGKey(seed + 1))
        # fixed batched-prefill group size (pow2) => one compile per bucket
        g = max(1, min(capacity, sched.max_prefill_per_step))
        self._group = 1 << (g - 1).bit_length()

        # device state ------------------------------------------------------
        cache_specs = self.model.cache_specs(capacity, max_len)
        spec_leaves = jax.tree.leaves(cache_specs, is_leaf=P.is_spec)
        self._batch_axes = [s.axes.index("batch") for s in spec_leaves]
        # per-leaf reset fill (ring slot-position caches hold -1 when empty)
        self._reset_vals = [s.scale if s.init == "const" else 0.0
                            for s in spec_leaves]
        # per-leaf KV sequence axis length (None: per-row state, e.g. SSM)
        self._seq_lens = [s.shape[s.axes.index("act_kv")]
                          if "act_kv" in s.axes else None for s in spec_leaves]
        # per-leaf KV sequence axis index in the *dense* layout — the pivot
        # cross-backend payload conversion reshapes around (None: per-row
        # state with no block representation => genuinely unconvertible)
        self._seq_axes = [s.axes.index("act_kv")
                          if "act_kv" in s.axes else None for s in spec_leaves]
        if self.paged:
            self.block_size = block_size
            self.max_blk = -(-max_len // block_size)
            # default pool = the dense backend's worst-case footprint; KV is
            # *charged* per block, so idle tail blocks become prefix-cache
            # retention instead of dead per-row reservation
            self.num_blocks = (capacity * self.max_blk if num_blocks is None
                               else num_blocks)
            self.prefix = PrefixCache(self.num_blocks, block_size)
            self.prefix_enabled = enable_prefix_cache
            paged_specs = self.model.paged_cache_specs(self.num_blocks,
                                                       block_size)
            pleaves = jax.tree.leaves(paged_specs, is_leaf=P.is_spec)
            self._pool_block_axes = [s.axes.index("kv_blocks") for s in pleaves]
            self.caches = self._init_on_device(paged_specs)
            self.block_tables = np.full((capacity, self.max_blk), -1, np.int32)
            self._row_blocks: dict[int, list[int]] = {}
            self._row_reserved: dict[int, int] = {}
            self._reserved_total = 0
            self._hit_tokens_step = 0
        else:
            self.caches = self._init_on_device(cache_specs)
        self.tokens = self._put(np.zeros((capacity, 1), np.int32))
        self.pos = np.zeros((capacity,), np.int32)

        # host-side per-row bookkeeping --------------------------------------
        self.row_req: dict[int, Request] = {}
        self._temp = np.zeros((capacity,), np.float32)
        self._topk = np.zeros((capacity,), np.int32)
        self._topp = np.ones((capacity,), np.float32)
        # chunked-prefill rows: admission order preserved by dict insertion
        self._prefilling: dict[int, Request] = {}
        self._consumed: dict[int, int] = {}
        self._fresh: set[int] = set()
        self.rejected_long = 0
        # in-progress async adoptions (ticket -> reservation state): rows
        # whose KV is still streaming in over the transport.  Invisible to
        # stepping/migration — absent from row_req and _prefilling — until
        # commit_adopt activates them
        self._pending_adopt: dict[int, dict] = {}
        self._next_ticket = 0

        # jitted programs, each under a stable name ---------------------------
        self._sampler = make_sampler()                  # jit_sample
        self._decode = _jit("decode", self.model.decode_step,
                            donate_argnums=(3,))
        self._decode_live = _jit("decode_live", self._decode_live_impl,
                                 donate_argnums=(3,))
        self._prefill = {}  # (bucket, group) -> jit_prefill_<bucket>x<group>
        self._insert = _jit("insert_rows", self._insert_rows_impl,
                            donate_argnums=(0,))
        self._chunk_fn = None  # lazily-built chunked-prefill program
        if self.paged:
            self._decode_paged = _jit("decode_paged",
                                      self.model.decode_step_paged,
                                      donate_argnums=(3,))
            self._chunk_paged = _jit("chunk_paged", self._chunk_paged_impl,
                                     donate_argnums=(1, 2))
            # last-position logits of the rows that chunked, at their pool
            # rows: the sampler keeps its one (capacity, V) shape
            self._chunk_logits = self._put(
                np.zeros((capacity, cfg.vocab_size), np.float32))
            self._copy_block = _jit("copy_block", self._copy_block_impl,
                                    donate_argnums=(0,))
        self.history: list[StepStats] = []
        self.finished: list[Request] = []
        # event stream (serving/events.py): appended by every request-visible
        # transition, drained into StepStats.events at the end of each step.
        # Out-of-step emissions (migration extract/requeue between steps) are
        # picked up by the next drain — the orchestrator drains explicitly
        # after its control tick so scale-down victims' events are not lost.
        self._pending_events: list[EngineEvent] = []
        self._risk_streak = 0       # consecutive SLO-guard-risky steps
        self.preemptions = 0        # rows displaced by the SLO guard (total)

        # observability (core/tracing.py, core/metrics.py) — imported at
        # runtime: core/__init__ imports serving.engine, so a module-level
        # import here would be circular.  Standalone engines get their own
        # tracer/registry; the orchestrator and the disaggregated server
        # rebind every replica to shared ones via set_tracer/set_metrics.
        from repro.core.metrics import MetricsRegistry
        from repro.core.tracing import HostSpans, Tracer
        self._spans = HostSpans()   # host spans of the current step
        self._rlabel = str(getattr(self, "replica_label", getattr(self, "lb_id", 0)))
        self.tracer = tracer if tracer is not None else Tracer()
        self.metrics: Any = None
        self._bind_instruments(metrics if metrics is not None
                               else MetricsRegistry())
        self.scheduler.on_reject = self._trace_reject

    # ------------------------------------------------------------- internals
    def _put(self, x):
        """Commit host values (a pytree of numpy arrays) to this engine's
        device as copies, so in-place updates of the host bookkeeping never
        reach a step still in flight."""
        return jax.device_put(x, self.device, may_alias=False)

    def _move(self, tree):
        """Commit device arrays (params, or a migration payload from a
        replica on another device) to this engine's device; arrays already
        there are not copied."""
        return jax.device_put(tree, self.device)

    def _init_on_device(self, specs, seed: int = 0):
        """Initialise a spec tree directly on this engine's device."""
        with jax.default_device(self.device):
            return self._move(P.init(jax.random.PRNGKey(seed), specs))

    def _insert_rows_impl(self, pool_tree, new_tree, rows):
        """Scatter a batched prefill's rows into the pool along each leaf's
        batch axis.  Pad entries carry row == capacity and are dropped."""
        pl = jax.tree.leaves(pool_tree)
        nl = jax.tree.leaves(new_tree)
        out = []
        for pool, new, ax in zip(pl, nl, self._batch_axes):
            idx = (slice(None),) * ax + (rows,)
            out.append(pool.at[idx].set(new.astype(pool.dtype), mode="drop"))
        return jax.tree.unflatten(jax.tree.structure(pool_tree), out)

    def _select_rows(self, mask, true_leaves, caches):
        """Per-leaf select along each leaf's batch axis: mask rows take the
        corresponding ``true_leaves`` entry (array leaf or scalar fill)."""
        out = []
        for t, o, ax in zip(true_leaves, jax.tree.leaves(caches),
                            self._batch_axes):
            shape = [1] * o.ndim
            shape[ax] = o.shape[ax]
            t = t if hasattr(t, "ndim") else jnp.asarray(t, o.dtype)
            out.append(jnp.where(mask.reshape(shape), t, o))
        return jax.tree.unflatten(jax.tree.structure(caches), out)

    def _decode_live_impl(self, params, tokens, pos, caches, live):
        """Decode step that leaves live=False rows bit-unchanged.  Rows mid
        chunked-prefill must not take decode-step cache writes (the SSM state
        update in particular is destructive)."""
        logits, new = self.model.decode_step(params, tokens, pos, caches)
        return logits, self._select_rows(live, jax.tree.leaves(new), caches)

    def _chunk_impl(self, params, caches, tokens, pos0, n_valid, fresh):
        """One chunk for every selected pool row (n_valid==0 rows no-op).
        fresh rows are reset first — a reused row must not leak the previous
        occupant's ring positions or SSM state into a new prompt."""
        caches = self._select_rows(fresh, self._reset_vals, caches)
        return self.model.prefill_chunk(params, tokens, pos0, n_valid, caches)

    def _chunk_paged_impl(self, params, caches, logits, tokens, pos0, n_valid,
                          block_table, rows):
        """One chunk for a group of rows (tokens (G, C); pos0, n_valid,
        rows (G,); their block-table rows (G, max_blk)); the group's logits
        land in the (capacity, V) buffer at ``rows``, where pad entries'
        row == capacity drops them."""
        new, caches = self.model.prefill_chunk_paged(
            params, tokens, pos0, n_valid, caches, block_table)
        return logits.at[rows].set(new, mode="drop"), caches

    def _prefill_fn(self, bucket: int, group: int):
        key = (bucket, group)
        if key not in self._prefill:
            def fn(p, batch, true_len):
                logits, caches = self.model.prefill(p, batch, self.max_len,
                                                    true_len=true_len)
                return logits, caches
            self._prefill[key] = _jit(f"prefill_{bucket}x{group}", fn)
        return self._prefill[key]

    def _chunk_program(self):
        if self._chunk_fn is None:
            self._chunk_fn = _jit("chunk", self._chunk_impl,
                                  donate_argnums=(1,))
        return self._chunk_fn

    # -------------------------------------------------- paged block plumbing
    def _copy_block_impl(self, caches, src, dst):
        """Copy one KV block across every layer pool (copy-on-write)."""
        out = []
        for pool, ax in zip(jax.tree.leaves(caches), self._pool_block_axes):
            blk = jax.lax.dynamic_slice_in_dim(pool, src, 1, axis=ax)
            out.append(jax.lax.dynamic_update_slice_in_dim(pool, blk, dst,
                                                           axis=ax))
        return jax.tree.unflatten(jax.tree.structure(caches), out)

    def _blocks_horizon(self, req: Request, n_blocks_hit: int,
                        tail_hit: bool) -> int:
        """New blocks this request may still need at its peak length: total
        footprint minus cache-shared blocks, plus one CoW replacement if the
        shared tail block must be copied before the first append."""
        total = min(len(req.prompt) + req.sampling.max_new_tokens, self.max_len)
        return max(-(-total // self.block_size) - n_blocks_hit, 0) + int(tail_hit)

    def _paged_available(self) -> int:
        """Blocks a new request could still claim without over-committing:
        free + evictable-cache minus what live rows have reserved."""
        return (self.prefix.free_blocks + self.prefix.evictable_blocks
                - self._reserved_total)

    def _take_reserved(self, row: int, n: int) -> None:
        take = min(self._row_reserved.get(row, 0), n)
        if take:
            self._row_reserved[row] -= take
            self._reserved_total -= take

    def _ensure_blocks(self, row: int, upto_tokens: int) -> None:
        """Grow the row's block list to cover positions [0, upto_tokens)."""
        blocks = self._row_blocks[row]
        need = -(-upto_tokens // self.block_size) - len(blocks)
        if need <= 0:
            return
        new = self.prefix.allocate(need)
        if new is None:
            raise RuntimeError(
                f"paged KV pool exhausted: need {need} blocks, "
                f"{self.prefix.free_blocks} free / "
                f"{self.prefix.evictable_blocks} evictable "
                f"(num_blocks={self.num_blocks})")
        self.block_tables[row, len(blocks):len(blocks) + need] = new
        blocks.extend(new)
        self._take_reserved(row, need)

    def _ensure_writable(self, row: int, block_idx: int) -> None:
        """Copy-on-write: the block about to take an append may be shared
        with other rows or retained by the prefix index (a matched partial
        tail).  Writing in place would corrupt those readers, so the row
        gets a private copy first."""
        blocks = self._row_blocks[row]
        if block_idx >= len(blocks):
            return
        old = blocks[block_idx]
        if not self.prefix.needs_cow(old):
            return
        new = self.prefix.allocate(1)
        if new is None:
            raise RuntimeError("paged KV pool exhausted during copy-on-write")
        self.caches = self._copy_block(self.caches, self._put(np.int32(old)),
                                       self._put(np.int32(new[0])))
        blocks[block_idx] = new[0]
        self.block_tables[row, block_idx] = new[0]
        self.prefix.decref(old)
        self.prefix.cow_copies += 1
        self._take_reserved(row, 1)

    def _release_row(self, row: int, req: Request, insert: bool) -> None:
        """Return a row's blocks: index them under the sequence's tokens
        first (so the *next* request with this prefix skips its prefill),
        then drop the row's references — cached blocks become LRU-evictable
        instead of being zeroed, uncached ones go back to the free list."""
        blocks = self._row_blocks.pop(row, None)
        if blocks is None:
            return
        if insert and self.prefix_enabled:
            n_valid = int(self.pos[row])        # KV covers positions [0, pos)
            seq = (list(req.prompt) + list(req.output))[:n_valid]
            self.prefix.insert(seq, blocks, n_valid)
        self.prefix.release(blocks)
        self.block_tables[row, :] = -1
        self._reserved_total -= self._row_reserved.pop(row, 0)

    # ------------------------------------------------------------- interface
    def submit(self, req: Request, now: float | None = None) -> bool:
        now = time.perf_counter() if now is None else now
        prefix = self.cfg.num_vision_tokens or 0
        limit = self.max_len - 1 - prefix
        if not self._can_chunk:
            limit = min(limit, self.buckets[-1])
        if len(req.prompt) > limit:
            # served-or-rejected, never a crash: a prompt that cannot fit a
            # cache row (or cannot be chunked on this family) bounces here
            req.state = State.REJECTED
            self.rejected_long += 1
            self._trace_reject(req, now, "prompt-too-long")
            return False
        if self.paged:
            total = min(len(req.prompt) + req.sampling.max_new_tokens,
                        self.max_len)
            if -(-total // self.block_size) > self.num_blocks:
                # an under-provisioned block pool can never map this request
                req.state = State.REJECTED
                self.rejected_long += 1
                self._trace_reject(req, now, "kv-unmappable")
                return False
        ok = self.scheduler.submit(req, now)
        if ok:
            self.tracer.start_trace(
                req.rid, now, replica=self._rlabel,
                prompt_tokens=len(req.prompt), slo_ttft=req.slo_ttft,
                slo_tpot=req.slo_tpot)
            # idempotent re-open: a drain/rollback resubmit of a live
            # request already has its queue_wait span running
            if self.tracer.open_span(req.rid, "queue_wait") is None:
                self.tracer.begin(req.rid, "queue_wait", now,
                                  replica=self._rlabel)
        return ok

    def pending(self) -> int:
        return self.scheduler.depth() + self.pool.used

    # --------------------------------------------------------------- prefill
    def _admit_cost(self, req: Request) -> tuple[int, int]:
        """(padded, true) prefill tokens this request consumes in its
        admission step.  Padded counts the compute actually launched (bucket
        round-up, chunk slice); true counts prompt tokens.  On the paged
        backend the cost is cache-aware: tokens whose KV the prefix cache
        already holds are never prefilled, so they cost nothing."""
        n = len(req.prompt)
        if self.paged:
            n_rem = n - (self._cached_prefix_len(req)
                         if self.prefix_enabled else 0)
            c = min(self.chunk, n_rem)
            return c, c
        if n <= self.buckets[-1]:
            return _round_bucket(n, self.buckets), n
        return self.chunk, min(self.chunk, n)

    def _cached_prefix_len(self, req: Request) -> int:
        """Memoised prefix-cache lookup: _admit_cost runs for every queued
        candidate every step, so repeat the (O(prompt) tuple-hashing) walk
        only when the index has actually changed."""
        memo = req.extras.get("_pc_lookup")
        gen = self.prefix.generation
        if memo is None or memo[0] != gen:
            memo = (gen, self.prefix.lookup(req.prompt))
            req.extras["_pc_lookup"] = memo
        return memo[1]

    def _set_row_sampling(self, row: int, req: Request) -> None:
        self._temp[row] = req.sampling.temperature
        self._topk[row] = req.sampling.top_k
        self._topp[row] = req.sampling.top_p

    def _admit_batch(self, reqs: list[Request], bucket: int, now: float) -> int:
        """Batched prefill of one bucket group: single forward, batched cache
        insertion, batched first-token sampling."""
        G = self._group
        assert len(reqs) <= G
        toks = np.zeros((G, bucket), np.int32)
        true = np.zeros((G,), np.int32)
        rows = np.full((G,), self.capacity, np.int32)   # pad => dropped
        temp = np.zeros((G,), np.float32)
        topk = np.zeros((G,), np.int32)
        topp = np.ones((G,), np.float32)
        for i, req in enumerate(reqs):
            row = self.pool.allocate(req.rid)
            assert row is not None
            req.row, req.state, req.t_admit = row, State.PREFILL, now
            self._trace_admit(req, now, kind=f"bucket{bucket}", row=row)
            self.tracer.annotate(req.rid, "prefill_chunk[0]", now,
                                 replica=self._rlabel,
                                 tokens=len(req.prompt), pos0=0)
            rows[i] = row
            toks[i, : len(req.prompt)] = req.prompt
            true[i] = len(req.prompt)
            temp[i] = req.sampling.temperature
            topk[i] = req.sampling.top_k
            topp[i] = req.sampling.top_p
        batch: dict[str, Any] = {"tokens": self._put(toks)}
        if self.cfg.num_vision_tokens:
            patches = np.zeros((G, self.cfg.num_vision_tokens, self.cfg.d_model),
                               np.float32)
            for i, req in enumerate(reqs):
                if "patches" in req.extras:
                    patches[i] = np.asarray(req.extras["patches"])[0]
            batch["patches"] = self._put(patches)
        if self.cfg.is_encoder_decoder:
            frames = np.zeros((G, self.cfg.encoder_seq, self.cfg.d_model),
                              np.float32)
            for i, req in enumerate(reqs):
                if "frames" in req.extras:
                    frames[i] = np.asarray(req.extras["frames"])[0]
            batch["frames"] = self._put(frames)
        logits, row_caches = self._prefill_fn(bucket, G)(
            self.params, batch, self._put(true))
        self.caches = self._insert(self.caches, row_caches, self._put(rows))
        # batched first tokens
        self.key, sk = jax.random.split(self.key)
        sampled = self._sampler(logits.astype(jnp.float32), sk,
                                *self._put((temp, topk, topp)))
        with self._spans("engine.wait"):
            sampled = np.asarray(jax.device_get(sampled))
        prefix = self.cfg.num_vision_tokens or 0
        with self._spans("engine.wait"):
            new_tokens = np.asarray(self.tokens).copy()
        for i, req in enumerate(reqs):
            t = int(sampled[i])
            row = req.row
            req.output.append(t)
            req.t_first_token = now
            req.token_times.append(now)
            req.state = State.DECODE
            self.pos[row] = len(req.prompt) + prefix
            new_tokens[row, 0] = t
            self._set_row_sampling(row, req)
            self.row_req[row] = req
            self._trace_first_token(req, now)
            self._emit_first_token(req, t, now)
            self._maybe_finish_first(row, req, now)
        self.tokens = self._put(new_tokens)
        return sum(len(r.prompt) for r in reqs)

    def _admit_chunked(self, req: Request, now: float) -> int:
        row = self.pool.allocate(req.rid)
        assert row is not None
        req.row, req.state, req.t_admit = row, State.PREFILL, now
        self._trace_admit(req, now, kind="chunked", row=row)
        self._prefilling[row] = req
        self._consumed[row] = 0
        self._fresh.add(row)
        self.pos[row] = 0
        self._set_row_sampling(row, req)
        return row

    def _admit_paged(self, req: Request, now: float) -> int | None:
        """Admit onto the paged backend (every prompt goes through the chunk
        pipeline).  The prefix cache is consulted first: matched blocks are
        mapped read-shared into the row's block table and their tokens are
        never prefilled.  Returns None — leave the request queued — when the
        block pool cannot cover the request's worst-case footprint without
        over-committing blocks other live rows may still claim."""
        blocks, n_hit, tail_hit = [], 0, False
        if self.prefix_enabled:
            blocks, n_hit = self.prefix.match(req.prompt)
            tail_hit = n_hit % self.block_size != 0
        horizon = self._blocks_horizon(req, len(blocks), tail_hit)
        if tail_hit and horizon > self._paged_available():
            # the CoW slack block can be unsatisfiable when the request's
            # footprint spans the whole pool: drop the partial-tail hit
            # (keep the aligned full-block hits) instead of deadlocking
            dropped = n_hit % self.block_size
            self.prefix.decref(blocks.pop())
            self.prefix.hit_tokens -= dropped
            self.prefix.miss_tokens += dropped
            n_hit -= dropped
            tail_hit = False
            horizon = self._blocks_horizon(req, len(blocks), False)
        if horizon > self._paged_available():
            self.prefix.release(blocks)
            # nothing was served: roll the hit/miss counters back so a
            # request retried every step doesn't inflate the reported rate
            self.prefix.hit_tokens -= n_hit
            self.prefix.miss_tokens -= len(req.prompt) - n_hit
            return None
        row = self.pool.allocate(req.rid)
        assert row is not None
        req.row, req.state, req.t_admit = row, State.PREFILL, now
        self._trace_admit(req, now, kind="paged", row=row, cached=n_hit)
        req.prefix_hit_tokens = n_hit
        self._row_blocks[row] = list(blocks)
        self.block_tables[row, :] = -1
        self.block_tables[row, :len(blocks)] = blocks
        self._row_reserved[row] = horizon
        self._reserved_total += horizon
        self._prefilling[row] = req
        self._consumed[row] = n_hit          # cached tokens: already prefilled
        self.pos[row] = n_hit
        self._set_row_sampling(row, req)
        self._hit_tokens_step += n_hit
        return row

    def _run_chunks(self, rows_n: dict[int, int], now: float) -> int:
        """Advance the selected mid-prefill rows by one chunk each; promote
        rows that consumed their prompt.  The dense backend makes one
        pool-wide program call, the paged backend one call per group of
        advancing rows (:meth:`_chunk_groups`).  Returns the calls."""
        B, C = self.capacity, self.chunk
        toks = np.zeros((B, C), np.int32)
        pos0 = np.zeros((B,), np.int32)
        nval = np.zeros((B,), np.int32)
        fresh = np.zeros((B,), bool)
        for row, n in rows_n.items():
            req = self._prefilling[row]
            c0 = self._consumed[row]
            k = self.tracer.count(req.rid, "prefill_chunk")
            self.tracer.annotate(req.rid, f"prefill_chunk[{k}]", now,
                                 replica=self._rlabel, tokens=n, pos0=c0)
            toks[row, :n] = req.prompt[c0:c0 + n]
            pos0[row] = c0
            nval[row] = n
            fresh[row] = row in self._fresh
            if self.paged:
                # map blocks for this chunk's span; CoW a shared first block
                self._ensure_blocks(row, c0 + n)
                self._ensure_writable(row, c0 // self.block_size)
        if self.paged:
            calls = self._chunk_groups(list(rows_n), toks, pos0, nval)
            logits = self._chunk_logits
        else:
            logits, self.caches = self._chunk_program()(
                self.params, self.caches, *self._put((toks, pos0, nval, fresh)))
            calls = 1
        self._fresh -= set(rows_n)
        done_rows = []
        for row, n in rows_n.items():
            self._consumed[row] += n
            self.pos[row] = self._consumed[row]
            if self._consumed[row] >= len(self._prefilling[row].prompt):
                done_rows.append(row)
        if not done_rows:
            return calls
        self.key, sk = jax.random.split(self.key)
        sampled = self._sampler(logits.astype(jnp.float32), sk,
                                *self._put((self._temp, self._topk, self._topp)))
        with self._spans("engine.wait"):
            sampled = np.asarray(jax.device_get(sampled))
        with self._spans("engine.wait"):
            new_tokens = np.asarray(self.tokens).copy()
        for row in done_rows:
            req = self._prefilling.pop(row)
            del self._consumed[row]
            t = int(sampled[row])
            req.output.append(t)
            # a migrated-in decode-phase row resuming here never re-samples
            # its first token; chunk completions are always first tokens
            req.t_first_token = now
            req.token_times.append(now)
            req.state = State.DECODE
            self.pos[row] = len(req.prompt)
            new_tokens[row, 0] = t
            self.row_req[row] = req
            self._trace_first_token(req, now)
            self._emit_first_token(req, t, now)
            self._maybe_finish_first(row, req, now)
        self.tokens = self._put(new_tokens)
        return calls

    def _chunk_groups(self, rows: list[int], toks, pos0, nval) -> int:
        """Paged chunk calls over ``rows`` (admission order), ``self._group``
        at a time, so the program has one shape whatever the capacity; the
        inputs are indexed by pool row.  The pools and the logits buffer are
        donated from call to call.  A short group is padded with pool row
        ``capacity``: an idle entry (n_valid 0, unmapped block-table row)
        that writes nothing and whose logits drop.  Returns the calls."""
        G = self._group
        idx = np.asarray(rows, np.int32)
        idx = np.pad(idx, (0, -len(idx) % G), constant_values=self.capacity)
        toks = np.pad(toks, ((0, 1), (0, 0)))
        pos0, nval = np.pad(pos0, (0, 1)), np.pad(nval, (0, 1))
        bt = np.pad(self.block_tables, ((0, 1), (0, 0)), constant_values=-1)
        for i in range(0, len(idx), G):
            g = idx[i:i + G]
            self._chunk_logits, self.caches = self._chunk_paged(
                self.params, self.caches, self._chunk_logits,
                *self._put((toks[g], pos0[g], nval[g], bt[g], g)))
        return len(idx) // G

    def _maybe_finish_first(self, row: int, req: Request, now: float) -> None:
        """A request can already be complete at its first (prefill) token —
        max_new_tokens=1, stop token sampled, or a prompt filling the row —
        in which case it must not receive a same-step decode token."""
        stop = req.sampling.stop_token
        if (len(req.output) >= req.sampling.max_new_tokens
                or (stop is not None and req.output[-1] == stop)
                or self.pos[row] >= self.max_len - 1):
            self._retire(row, now)

    def _retire(self, row: int, now: float) -> None:
        req = self.row_req.pop(row)
        req.state = State.DONE
        req.t_finish = now
        req.row = None
        stop = req.sampling.stop_token
        req.finish_reason = ("stop" if stop is not None and req.output
                             and req.output[-1] == stop else "length")
        if self.paged:
            self._release_row(row, req, insert=True)
        self.pool.free(row)
        self.finished.append(req)
        self.tracer.end(req.rid, "decode", now, tokens=len(req.output))
        self.tracer.finish(req.rid, now)
        self.emit_event(FinishEvent(t=now, rid=req.rid,
                                    reason=req.finish_reason,
                                    n_tokens=len(req.output)))

    # ------------------------------------------------------------- events
    def emit_event(self, ev: EngineEvent) -> None:
        """Append to the engine's event stream (drained into the next
        ``StepStats.events``).  Public so the migration layer can record
        handoff/rollback transitions against the engine they happened on."""
        self._pending_events.append(ev)
        # central count: every preempt/finish flows through here, including
        # the ones the migration layer emits between steps
        if isinstance(ev, PreemptEvent):
            self._c_preempts.inc(replica=self._rlabel, reason=ev.reason)
        elif isinstance(ev, FinishEvent):
            self._c_finished.inc(replica=self._rlabel, reason=ev.reason)

    def drain_events(self) -> list[EngineEvent]:
        """Return and clear the pending event stream.  ``step()`` drains
        into its StepStats; callers that mutate the engine *between* steps
        (migration, scale-down drains) drain explicitly afterwards."""
        ev, self._pending_events = self._pending_events, []
        return ev

    def _emit_first_token(self, req: Request, token: int, now: float) -> None:
        self.emit_event(FirstTokenEvent(t=now, rid=req.rid, token=token,
                                        index=0))

    # ------------------------------------------------------- observability
    def set_tracer(self, tracer) -> None:
        """Rebind to a shared (cluster-wide) tracer; also refreshes the
        replica label, which the control plane sets via ``lb_id``."""
        self.tracer = tracer
        self._rlabel = str(getattr(self, "replica_label", getattr(self, "lb_id", 0)))

    def set_metrics(self, registry) -> None:
        """Rebind every instrument onto a shared (cluster-wide) registry."""
        self._bind_instruments(registry)

    def _bind_instruments(self, registry) -> None:
        self.metrics = registry
        self._rlabel = str(getattr(self, "replica_label", getattr(self, "lb_id", 0)))
        self._c_prefill_tok = registry.counter(
            "engine_prefill_tokens_total",
            "Prompt tokens prefilled (true) / compute launched (padded)",
            ("replica", "kind"))
        self._c_decode_tok = registry.counter(
            "engine_decode_tokens_total", "Decode tokens emitted", ("replica",))
        self._c_chunk_calls = registry.counter(
            "engine_chunk_calls_total",
            "Launches of the chunked-prefill program", ("replica",))
        self._c_admissions = registry.counter(
            "engine_admissions_total", "Requests admitted onto a row",
            ("replica",))
        self._c_finished = registry.counter(
            "engine_requests_finished_total", "Requests retired, by reason",
            ("replica", "reason"))
        self._c_preempts = registry.counter(
            "engine_preemptions_total",
            "Rows displaced pre-finish, by reason (slo-decode-pressure / "
            "migrate / requeued)", ("replica", "reason"))
        self._c_rejections = registry.counter(
            "serving_rejections_total",
            "Requests rejected, by reason (queue-full / timeout / "
            "prompt-too-long / kv-unmappable)", ("replica", "reason"))
        self._g_occupancy = registry.gauge(
            "engine_batch_occupancy", "Rows occupied / capacity", ("replica",))
        self._g_queue = registry.gauge(
            "engine_queue_depth", "Scheduler queue depth", ("replica",))
        self._g_kv_util = registry.gauge(
            "engine_kv_util", "KV memory utilization fraction", ("replica",))
        self._g_kv_frag = registry.gauge(
            "engine_kv_frag", "Wasted tail-of-block KV slots fraction",
            ("replica",))
        self._h_step = registry.histogram(
            "engine_step_seconds",
            "Host seconds per step phase (schedule / prefill / chunk / "
            "decode / wait / observe), from the step's host spans",
            ("replica", "phase"))
        if self.paged:
            self._c_prefix = registry.counter(
                "prefix_cache_tokens_total",
                "Prefix-cache token outcomes (hit / miss)",
                ("replica", "kind"))
            self._c_prefix_ev = registry.counter(
                "prefix_cache_events_total",
                "Prefix-cache block events (evictions / cow_copies / "
                "inserted_blocks)", ("replica", "kind"))
            self._g_blocks = registry.gauge(
                "prefix_cache_blocks", "KV blocks by state (used / cached)",
                ("replica", "kind"))

    def _observe_step(self, st: StepStats) -> None:
        """Mirror one StepStats into the registry (never affects serving)."""
        rl = self._rlabel
        if st.prefill_tokens:
            self._c_prefill_tok.inc(st.prefill_tokens_true, replica=rl,
                                    kind="true")
            self._c_prefill_tok.inc(st.prefill_tokens_padded, replica=rl,
                                    kind="padded")
        if st.tokens_out:
            self._c_decode_tok.inc(st.tokens_out, replica=rl)
        if st.chunk_calls:
            self._c_chunk_calls.inc(st.chunk_calls, replica=rl)
        if st.n_prefill:
            self._c_admissions.inc(st.n_prefill, replica=rl)
        self._g_occupancy.set(st.occupancy / max(self.capacity, 1), replica=rl)
        self._g_queue.set(st.queue_depth, replica=rl)
        self._g_kv_util.set(st.kv_util, replica=rl)
        self._g_kv_frag.set(st.kv_frag, replica=rl)
        if self.paged:
            # peg, not inc: the prefix cache keeps its own cumulative
            # counters, and a re-bound registry must not double count
            self._c_prefix.peg(self.prefix.hit_tokens, replica=rl, kind="hit")
            self._c_prefix.peg(self.prefix.miss_tokens, replica=rl,
                               kind="miss")
            self._c_prefix_ev.peg(self.prefix.evictions, replica=rl,
                                  kind="evictions")
            self._c_prefix_ev.peg(self.prefix.cow_copies, replica=rl,
                                  kind="cow_copies")
            self._c_prefix_ev.peg(self.prefix.inserted_blocks, replica=rl,
                                  kind="inserted_blocks")
            self._g_blocks.set(st.kv_blocks_used, replica=rl, kind="used")
            self._g_blocks.set(st.kv_blocks_cached, replica=rl, kind="cached")

    def _trace_reject(self, req: Request, now: float, reason: str) -> None:
        """Rejection: a complete (instant) trace plus the rejection counter.
        Doubles as the scheduler's ``on_reject`` hook, so queue-full and
        admission-timeout rejections close their queue_wait span instead of
        orphaning it."""
        self._c_rejections.inc(replica=self._rlabel, reason=reason)
        self.tracer.start_trace(req.rid, now, replica=self._rlabel,
                                prompt_tokens=len(req.prompt))
        self.tracer.finish(req.rid, now, status=f"rejected:{reason}")

    def _trace_admit(self, req: Request, now: float, *, kind: str, row: int,
                     cached: int = 0) -> None:
        """Queue residency ends, prefill phase opens."""
        tr, rid, rl = self.tracer, req.rid, self._rlabel
        tr.end(rid, "queue_wait", now)
        tr.annotate(rid, "admission", now, replica=rl, row=row, kind=kind,
                    cached_prefix_tokens=cached)
        tr.begin(rid, "prefill", now, replica=rl,
                 prompt_tokens=len(req.prompt), cached_prefix_tokens=cached)

    def _trace_first_token(self, req: Request, now: float) -> None:
        """Prefill phase closes at the first token; decode phase opens."""
        self.tracer.end(req.rid, "prefill", now)
        self.tracer.begin(req.rid, "decode", now, replica=self._rlabel)

    # --------------------------------------------------------- SLO preempt
    def _preempt_freshest_prefill(self, now: float) -> bool:
        """Displace the most recently admitted mid-prefill row back to the
        queue head (deadline-risk decode rows outrank fresh prefill work).
        On the paged backend its consumed-prefix blocks are donated to the
        prefix index first, so re-admission is mostly cache hits; a dense
        row restarts its prefill from scratch."""
        if not self._prefilling:
            return False
        row = next(reversed(self._prefilling))      # insertion order = age
        req = self._prefilling.pop(row)
        self._consumed.pop(row, None)
        self._fresh.discard(row)
        if self.paged:
            self._release_row(row, req, insert=True)
        self.pool.free(row)
        self.pos[row] = 0
        req.state = State.QUEUED
        req.row = None
        req.t_admit = None
        req.preemptions += 1
        self.preemptions += 1
        self.scheduler.queue.appendleft(req)
        self.tracer.end(req.rid, "prefill", now, status="preempted")
        self.tracer.annotate(req.rid, "slo_guard_preempt", now,
                             replica=self._rlabel)
        self.tracer.begin(req.rid, "queue_wait", now, replica=self._rlabel,
                          requeued=True)
        self.emit_event(PreemptEvent(t=now, rid=req.rid,
                                     reason="slo-decode-pressure"))
        return True

    # ------------------------------------------------------------------ step
    def step(self, now: float | None = None) -> StepStats:
        """One engine iteration: chunk continuations -> admit (batched
        bucket prefills + new chunk starts) -> one decode step.

        Each phase runs under a host span (``self._spans``, see
        core/tracing.HostSpans): ``engine.step`` around it all, then
        ``engine.schedule``, ``engine.prefill`` (each bucket group),
        ``engine.chunk``, ``engine.decode`` and ``engine.observe``, with an
        ``engine.wait`` inside a phase wherever the host blocks on the
        device.  Their durations feed ``StepStats`` and the
        ``engine_step_seconds`` histogram."""
        now = time.perf_counter() if now is None else now
        sp = self._spans
        sp.reset()
        with sp("engine.step") as whole:
            with sp("engine.schedule") as last:
                (rows_n, groups, admitted, preempted, prefill_tokens,
                 prefill_padded) = self._schedule(now)
            for bucket in sorted(groups):
                with sp("engine.prefill") as last:
                    prefill_tokens += self._admit_batch(groups[bucket], bucket,
                                                        now)
                    prefill_padded += bucket * len(groups[bucket])
            chunk_calls = 0
            if rows_n:
                with sp("engine.chunk") as last:
                    chunk_calls = self._run_chunks(rows_n, now)
            t_pre = last.t1 - whole.t0

            tokens_out = 0
            t_dec = 0.0
            if self.row_req:
                with sp("engine.decode") as dec:
                    t_got, tokens_out = self._decode_rows(now)
                t_dec = t_got - dec.t0

            with sp("engine.observe"):
                st = StepStats(t=now, decode_s=t_dec, prefill_s=t_pre,
                               n_prefill=admitted, occupancy=self.pool.used,
                               queue_depth=self.scheduler.depth(),
                               tokens_out=tokens_out,
                               prefill_tokens=prefill_tokens,
                               chunk_rows=len(rows_n),
                               chunk_calls=chunk_calls,
                               prefill_tokens_padded=prefill_padded,
                               prefill_tokens_true=prefill_tokens,
                               events=self.drain_events(), preempted=preempted)
                if self.paged:
                    alloc = sum(len(b) for b in self._row_blocks.values()) \
                        * self.block_size
                    live_tok = int(sum(int(self.pos[r])
                                       for r in self._row_blocks))
                    st.prefix_hit_tokens = self._hit_tokens_step
                    st.prefix_hit_rate = self.prefix.hit_rate()
                    st.kv_blocks_used = self.prefix.used_blocks
                    st.kv_blocks_cached = self.prefix.cached_blocks
                    st.kv_util = self.prefix.utilization()
                    st.kv_frag = 0.0 if alloc == 0 else 1.0 - live_tok / alloc
                else:
                    st.kv_util = self.pool.utilization()
                self._observe_step(st)
                self.history.append(st)
        st.step_s = sp.seconds["engine.step"]
        st.wait_s = sp.seconds.get("engine.wait", 0.0)
        for name, sec in sp.seconds.items():
            if name != "engine.step":
                self._h_step.observe(sec, replica=self._rlabel,
                                     phase=name.removeprefix("engine."))
        return st

    def _schedule(self, now: float):
        """The SLO guard, the chunk continuations, ``scheduler.next_batch``
        and admission.  Returns (rows_n, bucket groups, admitted, preempted,
        prefill tokens, padded prefill tokens) so far."""
        budget = self.scheduler.cfg.prefill_token_budget
        # a non-positive budget would starve admission forever; clamp to the
        # minimum that still guarantees one (over-budget) pick per step
        remaining = math.inf if budget is None else max(budget, 1)
        prefill_tokens = 0
        prefill_padded = 0
        if self.paged:
            self._hit_tokens_step = 0

        # 0. SLO guard: decode rows at TPOT-deadline risk displace fresh
        # prefill work — no new admissions while any row is at risk, and a
        # persistent streak preempts the freshest mid-prefill row so the
        # next steps' chunk work shrinks
        scfg = self.scheduler.cfg
        at_risk: list[Request] = []
        preempted = 0
        if scfg.slo_guard:
            at_risk = deadline_risk(self.row_req.values(),
                                    scfg.slo_guard_margin)
            self._risk_streak = self._risk_streak + 1 if at_risk else 0
            if at_risk and self._risk_streak >= scfg.slo_guard_patience:
                if self._preempt_freshest_prefill(now):
                    preempted = 1

        # 1. continue in-flight chunked prefills (admission order); the
        # oldest row always advances so progress is never starved
        rows_n: dict[int, int] = {}
        for row, req in self._prefilling.items():
            n = min(self.chunk, len(req.prompt) - self._consumed[row])
            if rows_n and remaining < n:
                continue
            rows_n[row] = n
            remaining -= n
            prefill_tokens += n
            prefill_padded += n if self.paged else self.chunk

        # 2. admission under the remaining budget (withheld entirely while
        # the SLO guard sees deadline-risk decode rows)
        incoming: list[Request] = []
        if remaining > 0 and not at_risk:
            free = self.capacity - self.pool.used
            incoming = self.scheduler.next_batch(
                free, now, budget=None if budget is None else int(remaining),
                cost=self._admit_cost)
        groups: dict[int, list[Request]] = {}
        admitted = 0
        for i, req in enumerate(incoming):
            n = len(req.prompt)
            if self.paged:
                row = self._admit_paged(req, now)
                if row is None:
                    # KV blocks exhausted: requeue (FCFS order preserved)
                    # and stop admitting until blocks free up
                    for r in reversed(incoming[i:]):
                        self.scheduler.queue.appendleft(r)
                    break
                rows_n[row] = min(self.chunk, n - self._consumed[row])
                prefill_tokens += rows_n[row]
                prefill_padded += rows_n[row]
                admitted += 1
            elif n <= self.buckets[-1]:
                groups.setdefault(_round_bucket(n, self.buckets), []).append(req)
                admitted += 1
            elif self._can_chunk:
                row = self._admit_chunked(req, now)
                rows_n[row] = min(self.chunk, n)
                prefill_tokens += rows_n[row]
                prefill_padded += self.chunk
                admitted += 1
            else:  # belt-and-braces: submit() already bounces these
                req.state = State.REJECTED
                self.rejected_long += 1
        return (rows_n, groups, admitted, preempted, prefill_tokens,
                prefill_padded)

    def _decode_rows(self, now: float) -> tuple[float, int]:
        """One decode step over the live rows, their sampled tokens, and
        retirement.  Returns (when the sampled tokens reached the host,
        tokens emitted)."""
        if self.paged:
            # map the block each row's next token lands in (CoW'd if the
            # prefix cache or another row still reads it); dead rows are
            # masked so their writes drop instead of corrupting blocks
            live = np.zeros((self.capacity,), bool)
            for row in self.row_req:
                live[row] = True
                self._ensure_blocks(row, int(self.pos[row]) + 1)
                self._ensure_writable(
                    row, int(self.pos[row]) // self.block_size)
            pos, bt, live = self._put((self.pos, self.block_tables, live))
            logits, self.caches = self._decode_paged(
                self.params, self.tokens, pos, self.caches, bt, live)
        elif self._prefilling:
            live = np.ones((self.capacity,), bool)
            for row in self._prefilling:
                live[row] = False
            logits, self.caches = self._decode_live(
                self.params, self.tokens, self._put(self.pos),
                self.caches, self._put(live))
        else:
            logits, self.caches = self._decode(
                self.params, self.tokens, self._put(self.pos),
                self.caches)
        self.key, sk = jax.random.split(self.key)
        sampled = self._sampler(
            logits.astype(jnp.float32), sk,
            *self._put((self._temp, self._topk, self._topp)))
        with self._spans("engine.wait") as got:
            sampled = np.asarray(jax.device_get(sampled))
        with self._spans("engine.wait"):
            new_tokens = np.asarray(self.tokens).copy()
        tokens_out = 0
        for row, req in list(self.row_req.items()):
            t = int(sampled[row])
            req.output.append(t)
            req.token_times.append(now)
            tokens_out += 1
            self.pos[row] += 1
            new_tokens[row, 0] = t
            self.emit_event(TokenEvent(t=now, rid=req.rid, token=t,
                                       index=len(req.output) - 1))
            stop = req.sampling.stop_token
            if (len(req.output) >= req.sampling.max_new_tokens
                    or (stop is not None and t == stop)
                    or self.pos[row] >= self.max_len - 1):
                self._retire(row, now)
        self.tokens = self._put(new_tokens)
        return got.t1, tokens_out

    def run(self, max_steps: int = 10_000) -> list[Request]:
        steps = 0
        while self.pending() and steps < max_steps:
            self.step()
            steps += 1
        return self.finished

    # --------------------------------------------------------- migration
    def _find_row(self, rid: int) -> tuple[int, Request, str]:
        """Locate a live request by rid: (row, request, phase) where phase
        is "decode" (prefill complete) or "prefill" (mid-chunked-prefill,
        extractable at its current chunk boundary)."""
        for row, q in self.row_req.items():
            if q.rid == rid:
                return row, q, "decode"
        for row, q in self._prefilling.items():
            if q.rid == rid:
                return row, q, "prefill"
        raise KeyError(f"rid {rid} not active here")

    def migratable_requests(self) -> list[Request]:
        """Live requests a migration payload can be built for: every decode
        row, plus mid-prefill rows that have consumed at least one chunk
        (a consumed==0 dense row has not run its cache reset yet — there is
        nothing coherent to extract, only a request to requeue)."""
        out = list(self.row_req.values())
        out += [q for row, q in self._prefilling.items()
                if self._consumed.get(row, 0) > 0]
        return out

    def migration_sequence(self, rid: int) -> list[int]:
        """Tokens whose KV is materialised for this request — what a
        destination's prefix cache can be probed with before transfer."""
        row, req, _ = self._find_row(rid)
        n = int(self.pos[row])
        return (list(req.prompt) + list(req.output))[:n]

    def can_adopt(self, req: Request, n_valid: int,
                  n_keep_blocks: int = 0) -> bool:
        """Cheap adopt admissibility probe — no row taken, no cache data
        touched, no refcounts moved.  Lets the migration layer skip a
        target without paying for a full extract/rollback round-trip.
        ``n_keep_blocks``: full blocks this engine's prefix cache already
        holds for the sequence (it would reuse, not re-allocate, them)."""
        if self.pool.used >= self.capacity:
            return False
        if not self.paged:
            return True
        n_total = -(-n_valid // self.block_size)
        future = self._blocks_horizon(req, n_total, False)
        return (n_total - n_keep_blocks) + future <= self._paged_available()

    def kv_per_block_bytes(self) -> int:
        """Bytes one KV block holds across every layer pool (paged only)."""
        assert self.paged
        return sum(pool.nbytes // pool.shape[ax]
                   for pool, ax in zip(jax.tree.leaves(self.caches),
                                       self._pool_block_axes))

    def _gather_blocks(self, block_ids: list[int]):
        """Per-layer (n_blocks, block_size, ...) slabs for the given pool
        blocks — the data plane of a paged migration payload."""
        ids = self._put(np.asarray(block_ids, np.int32))
        leaves = [jnp.take(pool, ids, axis=ax)
                  for pool, ax in zip(jax.tree.leaves(self.caches),
                                      self._pool_block_axes)]
        return jax.tree.unflatten(jax.tree.structure(self.caches), leaves)

    def _scatter_blocks(self, data, block_ids: list[int], lo: int) -> None:
        """Write payload slabs (skipping the first ``lo`` blocks — the
        destination already holds them) into the given fresh pool blocks.
        Slabs from a replica on another device are moved here first."""
        if not block_ids:
            return
        data = self._move(data)
        ids = self._put(np.asarray(block_ids, np.int32))
        out = []
        for pool, d, ax in zip(jax.tree.leaves(self.caches),
                               jax.tree.leaves(data), self._pool_block_axes):
            sl = jax.lax.slice_in_dim(d, lo, d.shape[ax], axis=ax)
            idx = (slice(None),) * ax + (ids,)
            out.append(pool.at[idx].set(sl.astype(pool.dtype)))
        self.caches = jax.tree.unflatten(jax.tree.structure(self.caches), out)

    def extract_row(self, rid: int, now: float | None = None):
        """Remove a live request, returning its migration payload
        (Llumnix-style pause-and-copy handoff).  Works for decode rows and
        for mid-chunked-prefill rows at their current chunk boundary — the
        payload carries the prefill progress (``phase``/``pos``) so the
        destination resumes exactly where the source stopped.

        Dense payload: the row's cache tree sliced to batch dim 1.  Paged
        payload: per-layer (n_blocks, block_size, ...) slabs for the mapped
        blocks plus the token sequence they hold, so the destination can
        re-allocate through its own PrefixCache and skip blocks it already
        caches.  The source row is freed; its blocks are donated to the
        source's prefix index first, so a rollback re-adopt (or the next
        request with this prefix) is mostly cache hits."""
        row, req, phase = self._find_row(rid)
        if phase == "prefill" and self._consumed.get(row, 0) <= 0:
            raise ValueError(f"rid {rid} has not completed a chunk yet — "
                             "requeue it instead of migrating")
        n_valid = int(self.pos[row])
        payload: dict[str, Any] = {"pos": n_valid, "phase": phase}
        if phase == "decode":
            payload["last_token"] = int(np.asarray(self.tokens)[row, 0])
        if self.paged:
            blocks = self._row_blocks[row][: -(-n_valid // self.block_size)]
            payload["kind"] = "paged"
            payload["seq"] = self.migration_sequence(rid)
            payload["blocks"] = self._gather_blocks(blocks)
            payload["n_blocks"] = len(blocks)
        else:
            leaves = jax.tree.leaves(self.caches)
            sliced = [jax.lax.dynamic_slice_in_dim(pool, row, 1, axis=ax)
                      for pool, ax in zip(leaves, self._batch_axes)]
            payload["kind"] = "dense"
            payload["caches"] = jax.tree.unflatten(
                jax.tree.structure(self.caches), sliced)
        if phase == "decode":
            del self.row_req[row]
        else:
            del self._prefilling[row]
            del self._consumed[row]
            self._fresh.discard(row)
        if self.paged:
            self._release_row(row, req, insert=True)
        req.state = State.MIGRATING
        req.row = None
        req.migrations += 1
        self.pool.free(row)
        now = time.perf_counter() if now is None else now
        # close this replica's slice of the phase span and ship the span
        # context with the KV: the destination continues the same trace
        self.tracer.end(rid, "decode" if phase == "decode" else "prefill",
                        now, status="migrate-out")
        payload["trace"] = self.tracer.export_context(rid)
        self.emit_event(PreemptEvent(t=now, rid=rid, reason="migrate"))
        return req, payload

    def begin_adopt(self, req: Request, payload: dict,
                    now: float | None = None) -> int | None:
        """Reserve everything an incoming migration needs *before* any KV
        lands: a batch row and, on the paged backend, the full block plan —
        destination-cached full blocks are reused (their refcounts pin them
        against eviction for the transfer's whole flight), fresh blocks are
        allocated through the prefix cache with the same reservation-based
        admission as ``_admit_paged``, so an adoption that starts can always
        grow to the request's peak length without deadlocking the pool.

        Returns an opaque ticket for ``feed_adopt``/``commit_adopt``/
        ``abort_adopt``, or None when no row or no admissible block plan is
        available (nothing reserved — the caller rolls back at the source).
        The pending row is invisible to stepping and migration (absent from
        ``row_req`` and ``_prefilling``) until commit activates it."""
        kind = payload.get("kind", "dense")
        want = "paged" if self.paged else "dense"
        if kind != want:
            raise ValueError(f"cannot adopt a {kind!r} payload on a {want!r} "
                             "engine — convert the payload first "
                             "(convert_payload) or migrate same-backend")
        row = self.pool.allocate(req.rid)
        if row is None:
            return None
        st: dict[str, Any] = {"req": req, "row": row, "payload": payload,
                              "n_keep": 0, "blocks": None, "chunks": {},
                              "expected": 1}
        if self.paged:
            seq, n_valid = payload["seq"], payload["pos"]
            n_total = -(-n_valid // self.block_size)
            future = self._blocks_horizon(req, n_total, False)
            if self.prefix_enabled:
                plan = self.prefix.adopt_blocks(seq, n_valid, future,
                                                self._reserved_total)
            else:
                plan = None
                if n_total + future <= self._paged_available():
                    got = self.prefix.allocate(n_total)
                    plan = (got, 0) if got is not None else None
            if plan is None:
                self.pool.free(row)
                return None
            blocks, n_keep = plan
            self._row_blocks[row] = blocks
            self.block_tables[row, :] = -1
            self.block_tables[row, : len(blocks)] = blocks
            self._row_reserved[row] = future
            self._reserved_total += future
            st["blocks"], st["n_keep"] = blocks, n_keep
            # one transfer chunk per block the destination doesn't hold (the
            # reused prefix blocks never cross the wire); adopt_blocks
            # guarantees the tail block is fresh, so expected >= 1
            st["expected"] = payload["n_blocks"] - n_keep
        self.pos[row] = 0          # no live tokens until commit
        self._next_ticket += 1
        self._pending_adopt[self._next_ticket] = st
        return self._next_ticket

    def feed_adopt(self, ticket: int, index: int, data) -> None:
        """Land one transfer chunk of an in-progress adoption.  Paged:
        ``data`` is the per-layer single-block slab for payload block
        ``n_keep + index``, scattered straight into the reserved pool block
        (chunks may arrive in any order; duplicates are ignored).  Dense:
        the single full-row cache tree, buffered host-side — the device
        scatter happens at commit so an in-flight transfer never races the
        whole-batch decode writes."""
        st = self._pending_adopt[ticket]
        if index in st["chunks"]:
            return
        if self.paged:
            block = st["blocks"][st["n_keep"] + index]
            self._scatter_blocks(data, [block], 0)
            st["chunks"][index] = True
        else:
            st["chunks"][index] = data

    def commit_adopt(self, ticket: int, now: float | None = None) -> Request:
        """Activate a fully-transferred adoption: donate the request's full
        blocks into the radix index (their positions are immutable now — the
        partial tail stays private so the row's own appends never trigger a
        copy-on-write), restore position/sampling state, continue the
        request's trace here, and make the row live for the next step."""
        now = time.perf_counter() if now is None else now
        st = self._pending_adopt.pop(ticket)
        req, row, payload = st["req"], st["row"], st["payload"]
        assert len(st["chunks"]) >= st["expected"], \
            "commit_adopt before every chunk landed"
        if self.paged:
            seq, n_valid = payload["seq"], payload["pos"]
            if self.prefix_enabled:
                self.prefix.insert(seq, st["blocks"],
                                   (n_valid // self.block_size)
                                   * self.block_size)
            req.extras["adopt_hit_blocks"] = st["n_keep"]
        else:
            self.caches = self._insert(self.caches,
                                       self._move(st["chunks"][0]),
                                       self._put(np.asarray([row], np.int32)))
        self.pos[row] = payload["pos"]
        self._set_row_sampling(row, req)
        req.row = row
        # continue the request's trace here: same trace id, span ids offset
        # past the source's (no-op import when the cluster shares a tracer)
        self.tracer.import_context(payload.get("trace"))
        if payload["phase"] == "decode":
            self.tokens = self.tokens.at[row, 0].set(payload["last_token"])
            self.row_req[row] = req
            req.state = State.DECODE
            self.tracer.begin(req.rid, "decode", now, replica=self._rlabel,
                              migrated_in=True, resume_pos=payload["pos"])
        else:
            # mid-prefill handoff: resume the chunk pipeline at the boundary
            self._prefilling[row] = req
            self._consumed[row] = payload["pos"]
            req.state = State.PREFILL
            self.tracer.begin(req.rid, "prefill", now, replica=self._rlabel,
                              migrated_in=True, resume_pos=payload["pos"])
        return req

    def abort_adopt(self, ticket: int) -> None:
        """Drop an in-progress adoption and return every reservation."""
        st = self._pending_adopt.pop(ticket)
        if self.paged:
            self._release_row(st["row"], st["req"], insert=False)
        self.pool.free(st["row"])

    def adopt(self, req: Request, payload: dict, now: float | None = None) -> bool:
        """Install a migrated request synchronously (cache shapes must
        match: same cfg, capacity-independent, same max_len/block_size; use
        ``convert_payload`` across KV backends).  Returns False — leaving
        this engine untouched — when no row or, on the paged backend, no
        admissible block plan is available.

        Expressed as begin/feed-all/commit so the synchronous path and the
        transport's block-granular async path share one implementation —
        which is what makes them token-identical by construction."""
        now = time.perf_counter() if now is None else now
        ticket = self.begin_adopt(req, payload, now)
        if ticket is None:
            return False
        st = self._pending_adopt[ticket]
        if self.paged:
            # one-shot scatter of the whole slab, skipping reused blocks
            self._scatter_blocks(payload["blocks"],
                                 st["blocks"][st["n_keep"]:], st["n_keep"])
            st["chunks"] = {i: True for i in range(st["expected"])}
        else:
            st["chunks"][0] = payload["caches"]
        self.commit_adopt(ticket, now)
        return True

    # --------------------------------------- cross-backend payload conversion
    def can_convert(self, other) -> bool:
        """Whether a migration payload from ``other`` (the opposite KV
        backend) is convertible to this engine's layout.  Genuinely
        unservable shapes — any cache leaf without a KV sequence axis
        (SSM state, conv tails, ring buffers: no block representation) —
        are the one case the migration layer still records as a
        ``backend-mismatch`` failure."""
        return (self.model.supports_paged()
                and other.model.supports_paged()
                and self.max_len == other.max_len
                and not any(ax is None for ax in self._seq_axes))

    def convert_payload(self, req: Request, payload: dict) -> dict | None:
        """Rebuild a migration payload from the other KV backend into this
        engine's layout, leaf by leaf (dense and paged cache trees mirror
        each other: the block axis sits where the batch axis was, the slot
        axis where the sequence axis was).  Paged -> dense flattens block
        slabs back into one padded row; dense -> paged slices the row into
        ``block_size`` slots.  Positions past ``pos`` are zero-padding the
        decode mask never reads.  Returns None for shapes ``can_convert``
        rejects."""
        kind = payload.get("kind", "dense")
        want = "paged" if self.paged else "dense"
        if kind == want:
            return payload
        if (any(ax is None for ax in self._seq_axes)
                or not self.model.supports_paged()):
            return None
        pos = payload["pos"]
        out = {k: v for k, v in payload.items()
               if k not in ("kind", "seq", "blocks", "n_blocks", "caches")}
        out["kind"] = want
        if want == "dense":
            leaves = []
            for d, ax, L in zip(jax.tree.leaves(payload["blocks"]),
                                self._batch_axes, self._seq_lens):
                nb, slot = d.shape[ax], d.shape[ax + 1]
                x = d.reshape(d.shape[:ax] + (nb * slot,) + d.shape[ax + 2:])
                if nb * slot < L:
                    pad = [(0, 0)] * x.ndim
                    pad[ax] = (0, L - nb * slot)
                    x = jnp.pad(x, pad)
                else:
                    x = jax.lax.slice_in_dim(x, 0, L, axis=ax)
                leaves.append(jnp.expand_dims(x, ax))
            out["caches"] = jax.tree.unflatten(
                jax.tree.structure(self.caches), leaves)
        else:
            bs = self.block_size
            nb = -(-pos // bs)
            leaves = []
            for d, ax, sx in zip(jax.tree.leaves(payload["caches"]),
                                 self._batch_axes, self._seq_axes):
                x = jnp.squeeze(d, axis=ax)
                s = sx - 1 if ax < sx else sx
                if x.shape[s] < nb * bs:
                    pad = [(0, 0)] * x.ndim
                    pad[s] = (0, nb * bs - x.shape[s])
                    x = jnp.pad(x, pad)
                else:
                    x = jax.lax.slice_in_dim(x, 0, nb * bs, axis=s)
                x = x.reshape(x.shape[:s] + (nb, bs) + x.shape[s + 1:])
                leaves.append(x)
            out["seq"] = (list(req.prompt) + list(req.output))[:pos]
            out["n_blocks"] = nb
            out["blocks"] = jax.tree.unflatten(
                jax.tree.structure(self.caches), leaves)
        return out

    # ------------------------------------------------- cluster cache directory
    def attach_cache_directory(self, directory, replica_id: int | None = None) -> None:
        """Start publishing this replica's prefix-index deltas (insert,
        evict — migration donation and drain flow through the same two
        events) into a cluster cache directory, and push the current index
        so the directory is warm from the first lookup.  A no-op on dense
        or prefix-cache-disabled engines — they have nothing to advertise."""
        if not (self.paged and self.prefix_enabled):
            return
        rid = replica_id if replica_id is not None \
            else getattr(self, "lb_id", id(self))
        self.prefix.attach_sink(directory, rid)
        directory.reconcile(rid, self.prefix.reachable_chains())

    def detach_cache_directory(self, directory=None) -> None:
        """Stop publishing; with ``directory`` given, also invalidate every
        entry this replica claimed (scale-down: its pool is going away)."""
        if not self.paged:
            return
        if directory is not None and self.prefix.replica_id is not None:
            directory.drop_replica(self.prefix.replica_id)
        self.prefix.detach_sink()

    def reconcile_cache_directory(self, directory) -> tuple[int, int]:
        """Periodic anti-entropy: replace the directory's view of this
        replica with the chains its radix tree can actually serve.  Repairs
        orphaned-descendant drift and any lost events; cheap enough
        (O(cached blocks)) to run every few control ticks."""
        if not (self.paged and self.prefix_enabled):
            return (0, 0)
        rid = self.prefix.replica_id
        if rid is None:
            rid = getattr(self, "lb_id", id(self))
        return directory.reconcile(rid, self.prefix.reachable_chains())

    def kv_utilization(self) -> float:
        """KV memory in use as a fraction of the backend's budget: live
        blocks over the pool on the paged backend (the per-block charge the
        control plane trades in), occupied rows over capacity on dense."""
        return self.prefix.utilization() if self.paged else self.pool.utilization()

    def kv_bytes(self, rid: int) -> int:
        """Migration payload size (drives the handoff cost model), scaled by
        the request's actual sequence length: leaves with a KV sequence axis
        are charged min(pos, L) of their L slots; per-row state without one
        (SSM state / conv tails) is charged in full.  On the paged backend a
        request is charged its mapped blocks — per block, not per row."""
        row, _, _ = self._find_row(rid)
        if self.paged:
            return self.kv_per_block_bytes() * len(self._row_blocks[row])
        n = int(self.pos[row])
        leaves = jax.tree.leaves(self.caches)
        total = 0
        for pool, ax, L in zip(leaves, self._batch_axes, self._seq_lens):
            per_row = pool.nbytes // pool.shape[ax]
            if L is not None:
                per_row = per_row * min(n, L) // L
            total += per_row
        return total
