"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: the device's busy union, each device operation's
own time, and the device's idle gaps attributed to what the host was doing.

What a TPU trace holds (seen on a v5e): a plane ``/device:TPU:<n>`` per
chip, whose line ``XLA Ops`` has one event per operation run, named by its
HLO text (``%fusion.189 = f32[...] fusion(...)``); a loop's event
(``%while.4 = ...``) encloses the events of its body.  Pallas kernels are
custom calls named after the jitted wrapper that launched them
(``%paged_decode_attention.9``, ``%ssd_chunked_scan.8``).  The host plane's
lines hold the benchmark's own ``jax.profiler.TraceAnnotation`` spans,
named ``bench.*``; the traced window runs from the first such span's start
to the last one's end.  Host and device events share the trace's clock.
"""
from __future__ import annotations

import bisect
import collections
import gzip
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
_LAYOUT = re.compile(r"\{[^{}]*\}")


def short_name(hlo: str, width: int = 120) -> str:
    """An operation's HLO text without layouts and attributes in braces,
    cut to ``width``."""
    while True:
        out = _LAYOUT.sub("", hlo)
        if out == hlo:
            return out[:width]
        hlo = out


def read(path: str) -> dict:
    """Load the trace (``.xplane.pb``, or gzipped ``.xplane.pb.gz``):
    {"devices": {chip: [(name, start_ns, dur_ns)]},
    "spans": [(name, start_ns, dur_ns)]}."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices: dict[int, list] = {}
    spans = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(int(m.group(1)), []).extend(
                    (e.name, e.start_ns, e.duration_ns) for e in line.events)
            elif not m:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": spans}


def _union(iv: np.ndarray) -> np.ndarray:
    """Merge (n, 2) [start, end) intervals into disjoint sorted ones."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def _self_times(evs, w0: float, w1: float, into: collections.Counter) -> None:
    """Each operation's time less the time of the operations it encloses
    (a loop less its body), for operations starting in [w0, w1)."""
    evs = sorted(evs, key=lambda e: (e[1], -e[2]))
    stack: list[list] = []    # [end, name, own ns or None outside the window]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, own = stack.pop()
            if own is not None:
                into[name] += own * 1e-9
    for name, s, d in evs:
        close(s)
        if stack and stack[-1][2] is not None:
            stack[-1][2] -= min(d, stack[-1][0] - s)
        stack.append([s + d, name, d if w0 <= s < w1 else None])
    close(float("inf"))


def _attribute(gaps: np.ndarray, spans: list, into: collections.Counter) -> None:
    """Add each idle gap's overlap with each host span (by name) to
    ``into``; idle time no span covers goes to ``bench.none``."""
    sp = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in sp]
    longest = max((d for _, _, d in sp), default=0.0)
    for g0, g1 in gaps:
        covered = 0.0
        i = bisect.bisect_left(starts, g1)
        j = bisect.bisect_left(starts, g0 - longest)
        for name, s, d in sp[j:i]:
            ov = min(g1, s + d) - max(g0, s)
            if ov > 0:
                into[name] += ov
                covered += ov
        if g1 - g0 - covered > 0:
            into["bench.none"] += g1 - g0 - covered


def reduce(trace: dict, chips: list[int] | None = None) -> dict | None:
    """Busy and idle time per chip, operations' own times and the idle
    gaps' attribution over the traced window, in seconds.  None when the
    trace holds no host span or no device operation."""
    spans = trace["spans"]
    devs = {k: v for k, v in trace["devices"].items()
            if (chips is None or k in chips) and v}
    if not spans or not devs:
        return None
    w0 = min(s for _, s, _ in spans)
    w1 = max(s + d for _, s, d in spans)
    ops: collections.Counter = collections.Counter()
    gaps: collections.Counter = collections.Counter()
    busy = {}
    for dev, evs in devs.items():
        iv = np.array([(max(s, w0), min(s + d, w1)) for _, s, d in evs
                       if s < w1 and s + d > w0], dtype=float).reshape(-1, 2)
        u = _union(iv)
        busy[dev] = float((u[:, 1] - u[:, 0]).sum()) * 1e-9
        edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
        _attribute(edges[edges[:, 1] > edges[:, 0]], spans, gaps)
        _self_times([(short_name(n), s, d) for n, s, d in evs], w0, w1, ops)
    n = len(devs)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": sum(busy.values()) / n,
        "busy_s_per_chip": {str(k): v for k, v in sorted(busy.items())},
        "op_s": {k: v for k, v in ops.items() if v > 0},
        "idle_gap_s": {k: v * 1e-9 / n for k, v in gaps.items()},
        "chips": n,
    }


def op_seconds(summary: dict, pattern: str) -> float:
    """Device seconds (own time, summed over chips) of the operations whose
    short name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in summary["op_s"].items() if rx.search(k))


def breakdown(summary: dict) -> dict:
    """The ten operations with the most own time, and the ten host spans
    under which the device sat idle longest (seconds a chip)."""
    top = sorted(summary["op_s"].items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(summary["idle_gap_s"].items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}
