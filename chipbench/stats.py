"""Percentiles and rates of the end-to-end metrics."""
from __future__ import annotations

import math


def percentile(values, p: float) -> float:
    """Nearest-rank percentile (p in (0, 100]): the smallest value with at
    least p% of the values at or below it.  A failed request enters as
    ``math.inf``, so it counts as missing every limit."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of no values")
    return v[max(0, math.ceil(p / 100.0 * len(v)) - 1)]


def ttft(recs, w0: float, w1: float) -> list[float]:
    """Due time to first token at the client, for every request due in
    [w0, w1); ``inf`` for one that failed or never got a token."""
    out = []
    for r in recs:
        if w0 <= r.due < w1:
            ok = r.times and not r.rejected
            out.append(r.times[0] - r.due if ok else math.inf)
    return out


def gaps(recs, w0: float, w1: float) -> list[float]:
    """Every gap between consecutive streamed tokens of a request whose
    later token reached the client in [w0, w1), pooled over requests."""
    out = []
    for r in recs:
        t = r.times
        out += [t[i] - t[i - 1] for i in range(1, len(t)) if w0 <= t[i] < w1]
    return out


def tokens_in(recs, w0: float, w1: float) -> int:
    """Output tokens that reached clients in [w0, w1)."""
    return sum(1 for r in recs for t in r.times if w0 <= t < w1)

