"""Performance knobs — the hillclimbing surface (EXPERIMENTS.md §Perf).

Everything here changes the compiled HLO but never the math (up to remat
recompute and grad-accumulation dtype).  Defaults are the paper-faithful
baseline; the perf loop flips them per-cell.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass


@dataclass(frozen=True)
class PerfConfig:
    # attention
    q_chunk: int = 512
    attn_impl: str = "full"        # full | triangle (causal chunk skipping)
    # loss
    xent_chunk: int = 512
    # training memory
    remat: str = "full"            # none | full | dots
    microbatch: int = 1            # grad-accumulation steps over the global batch
    accum_dtype: str = "bfloat16"  # grad accumulator dtype (bfloat16 | float32)
    # sharding strategy (distributed/sharding.py rule-table variants)
    partitioning: str = "tp"       # tp | zero3 (layer-stack params over data)
    # kv cache dtype for decode shapes ("bfloat16" | "int8")
    kv_dtype: str = "bfloat16"
    # unroll the decode layer loop: a lax.scan DUS-updates the stacked KV
    # buffer every trip (XLA round-trips the whole stack through f32 —
    # measured 14.4 GB/step on qwen2 decode_32k); unrolling gives per-layer
    # cache tensors and in-place writes
    decode_unroll: bool = False
    # donate decode cache / train state buffers
    donate: bool = True


BASELINE = PerfConfig()


def with_overrides(perf: PerfConfig, **kw) -> PerfConfig:
    return dataclasses.replace(perf, **kw)
