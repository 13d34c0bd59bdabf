"""Rate sweep of an open-loop cell (run by hand to find the knee; never by
the benchmark's runs): one set-up, then a window at each rate.

A rate's queue grows where, from the window's first half to its second,
the median TTFT or the mean number of requests in the system (queued and
served) rises by more than a quarter.  The knee is the highest rate swept
below the first at which either does.

The turn spacing printed for a mix's ``lead_turn_s`` is, over the window's
turns that their session follows with another, the turn's TTFT, its answer
at the window's mean token gap, and the think time after it: a next turn
seen to come due before the run ends would leave out the long answers."""
from __future__ import annotations

import json
import statistics
import time

import harness
import stats


def run(cell, seed: int, seconds: float, rates: list[float], devices, log) -> None:
    conf, mix, c = cell.config, cell.mix, cell.cell
    ref = harness.load_module(cell.conf_dir / conf["reference"])
    cfg = harness.model_config(conf)
    weights = harness.make_weights(conf, ref, seed, devices[0])
    reg, orch = harness.build(conf, ref, weights, cfg)
    harness.warm(reg, orch, conf, mix, conf["token_ids_below"])
    rid0 = 0
    for i, rate in enumerate(rates):
        # a seed per rate, so no rate finds another's sessions in the cache
        p = harness.plan(conf, mix, seed + i, seconds, c["ramp_s"], rate)
        harness.prime(reg, conf, p.primes)
        r = harness.drive(reg, conf, mix, p, drain_s=c["drain_s"], rid0=rid0)
        rid0 += len(r.recs) + 1
        att = harness.window_requests(r)
        half = (r.w0 + r.w1) / 2
        tt = stats.ttft(r.recs, r.w0, r.w1)
        early = [v for x, v in zip(att, tt) if x.due < half]
        late = [v for x, v in zip(att, tt) if x.due >= half]
        pend = [p for t0, _, p in r.steps if r.w0 <= t0 < r.w1]
        pend_early = statistics.fmean(pend[:len(pend) // 2] or [0])
        pend_late = statistics.fmean(pend[len(pend) // 2:] or [0])
        by = {(x.session, x.turn): x for x in r.recs}
        seen = [by[(x.session, x.turn + 1)].due - x.due for x in att
                if (x.session, x.turn + 1) in by]
        gap = statistics.fmean(stats.gaps(r.recs, r.w0, r.w1) or [0.0])
        spacing = [t + (x.max_tokens - 1) * gap
                   + p.sessions[x.session].think_s[x.turn]
                   for x, t in zip(att, tt) if t != float("inf")
                   and x.turn + 1 < len(p.sessions[x.session].turns)]
        # requests in the system in each 10 s from the ramp's start
        fill = [[n for t0, _, n in r.steps if r.t0 + a <= t0 < r.t0 + a + 10]
                for a in range(0, int(r.w1 - r.t0), 10)]
        tt_early = stats.percentile(early, 50) if early else None
        tt_late = stats.percentile(late, 50) if late else None
        line = {
            "rate_per_s": rate, "attempted": len(att),
            "failed": sum(1 for x in att if x.rejected or not x.times),
            "ttft_p50_s": stats.percentile(tt, 50) if tt else None,
            "ttft_p90_s": stats.percentile(tt, 90) if tt else None,
            "ttft_p50_first_half_s": tt_early,
            "ttft_p50_second_half_s": tt_late,
            "itl_p95_s": stats.percentile(stats.gaps(r.recs, r.w0, r.w1), 95),
            "output_tok_s": stats.tokens_in(r.recs, r.w0, r.w1) / seconds,
            "prefix_hit_share": sum(x.req.prefix_hit_tokens for x in att)
            / max(1, sum(len(x.prompt) for x in att)),
            "steps": sum(1 for t0, _, _ in r.steps if r.w0 <= t0 < r.w1),
            "pending_first_half": pend_early,
            "pending_second_half": pend_late,
            "turn_spacing_s": statistics.fmean(spacing) if spacing else None,
            "turn_spacing_seen_s": statistics.fmean(seen) if seen else None,
            "pending_by_10s": [round(statistics.fmean(f), 2) if f else None
                               for f in fill],
            "grows": bool(tt_early is None or tt_late is None
                          or tt_late > 1.25 * tt_early
                          or pend_late > 1.25 * pend_early),
        }
        print(json.dumps(line), flush=True)
        log(harness.lateness(r.recs))
        if i + 1 == len(rates):
            break             # nothing follows to find the backlog
        t = time.perf_counter()
        while reg.pending() and time.perf_counter() - t < 120:
            reg.step()
        reg.drain_events()
