"""Percentile and rate arithmetic of the end-to-end metrics."""
import math

import smoke  # noqa: F401  (puts the benchmark on sys.path)
import stats
from harness import Rec


def rec(due, times, rejected=False):
    return Rec(rid=0, session=0, turn=0, due=due, sent=due, prompt=[1],
               max_tokens=len(times), times=list(times), rejected=rejected)


def test_nearest_rank_percentile():
    v = list(range(1, 101))
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([3, 1, 2], 50) == 2


def test_failed_requests_count_as_misses():
    recs = [rec(10.0 + i, [10.5 + i]) for i in range(9)]
    recs.append(rec(19.0, [], rejected=False))      # never got a token
    t = stats.ttft(recs, 10.0, 20.0)
    assert t.count(math.inf) == 1
    assert stats.percentile(t, 90) == 0.5           # 9 of 10 within 0.5 s
    recs.append(rec(19.5, [19.6], rejected=True))   # refused at submit
    t = stats.ttft(recs, 10.0, 20.0)
    assert stats.percentile(t, 90) == math.inf      # 2 of 11 failed


def test_window_bounds_ttft_gaps_and_tokens():
    recs = [rec(9.0, [9.5, 10.0, 10.5]), rec(10.0, [10.2, 10.4, 20.1])]
    assert stats.ttft(recs, 10.0, 20.0) == [10.2 - 10.0]   # due in window only
    # gaps whose later token reached the client in [10, 20)
    g = stats.gaps(recs, 10.0, 20.0)
    assert sorted(round(x, 6) for x in g) == [0.2, 0.5, 0.5]
    assert stats.tokens_in(recs, 10.0, 20.0) == 4
    assert stats.tokens_in(recs, 10.0, 20.0) / 10.0 == 0.4

