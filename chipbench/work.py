"""The work the traced steps did, reconstructed from the client's records.

A request's first token reaches the client after the step that finished
its prompt.  Its prompt was prefilled from its cached prefix on, one chunk
per step in the steps just before (the engines advance every prefilling
row each step; a prompt that fits a bucket is prefilled whole in its first
token's step).  Each later token came from one decode step over a context
of the prompt and the tokens before it.
"""
from __future__ import annotations


def traced_items(run):
    """Yield ("prefill", rec, start, stop) and ("decode", rec, ctx) for the
    work of the steps the trace recorded; nothing when no step was
    traced."""
    if not run.traced or run.traced[1] is None:
        return
    lo, hi = run.traced
    eng = run.conf["engine"]
    paged = eng["kv_backend"] == "paged"
    chunk = eng["buckets"][-1]
    for r in run.recs:
        if not r.steps:
            continue
        n = len(r.prompt)
        hit = r.req.prefix_hit_tokens if paged else 0
        k0 = r.steps[0]
        if paged or n > chunk:
            bounds = list(range(hit, n, chunk)) + [n]
        else:
            bounds = [0, n]
        c = len(bounds) - 1
        for j in range(c):
            if lo <= k0 - (c - 1 - j) < hi:
                yield "prefill", r, bounds[j], bounds[j + 1]
        for i, k in enumerate(r.steps[1:], start=1):
            if lo <= k < hi:
                yield "decode", r, n + i


def sampled_tokens(run) -> int:
    """Tokens of the traced steps whose logits were sampled (first tokens
    and decode tokens)."""
    if not run.traced or run.traced[1] is None:
        return 0
    lo, hi = run.traced
    return sum(1 for r in run.recs for k in r.steps if lo <= k < hi)
