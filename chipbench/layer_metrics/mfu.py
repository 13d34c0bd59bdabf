"""mfu (%, model step): the FLOPs the traced steps' tokens needed (prompt
tokens not served from the cache, decode tokens, and one unembedding per
sampled token) over the device-busy seconds of every chip used times the
chip's peak bf16 FLOP/s.  Moves itl_p95_s."""
import counts
import work


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    m = run.m
    f = counts.unembed_flops(m) * work.sampled_tokens(run)
    for kind, r, *a in work.traced_items(run):
        f += (counts.prefill_flops(m, a[0], a[1]) if kind == "prefill"
              else counts.token_flops(m, a[0]))
    if f <= 0:
        return None
    busy = run.trace["busy_s"] * run.trace["chips"]
    return 100.0 * f / (busy * run.peaks["bf16_flops_per_s"])
