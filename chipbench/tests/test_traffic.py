"""Each traffic mix is a function of its file and the seed alone."""
import json
import pathlib

import pytest

import smoke  # noqa: F401
import traffic

MIXES = sorted((pathlib.Path(smoke.HERE) / "traffic").glob("*.json"))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_same_seed_same_traffic(path):
    mix = json.loads(path.read_text())
    seed = 2 ** 31 + 123
    a, b = traffic.sessions(mix, 30.0), traffic.sessions(mix, 30.0)
    assert a == b and a
    ta, tb = traffic.Tokens(mix, seed, 1000), traffic.Tokens(mix, seed, 1000)
    assert ta.system == tb.system
    assert traffic.primes(mix, ta, a) == traffic.primes(mix, tb, b)
    s = a[-1]
    assert traffic.prompt_for(mix, ta, s, 0) == traffic.prompt_for(mix, tb, s, 0)
    assert all(0 <= t < 1000 for t in ta.turn(s.index, 0, 40))


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_other_seed_same_work_other_tokens(path):
    mix = json.loads(path.read_text())
    s = traffic.sessions(mix, 30.0)
    t1, t2 = traffic.Tokens(mix, 1, 1000), traffic.Tokens(mix, 2, 1000)
    p1 = [traffic.prompt_for(mix, t1, x, 0) for x in s]
    p2 = [traffic.prompt_for(mix, t2, x, 0) for x in s]
    assert [len(p) for p in p1] == [len(p) for p in p2]
    assert p1 != p2


def test_bursts_raise_the_rate_inside_them():
    mix = json.loads((pathlib.Path(smoke.HERE) / "traffic" /
                      "mamba2_chat_burst.json").read_text())
    t = traffic.arrival_times(mix, 0.0, 2000.0, 4.0)
    b = mix["bursts"]
    inside = sum(1 for x in t if x % b["every_s"] < b["length_s"])
    frac = b["length_s"] / b["every_s"]
    rate_in = inside / (2000.0 * frac)
    rate_out = (len(t) - inside) / (2000.0 * (1 - frac))
    assert abs(len(t) / 2000.0 - 4.0) < 0.2            # the mean rate holds
    assert abs(rate_in / rate_out - b["factor"]) < 0.5


def test_history_prompt_cap_and_primes():
    mix = {"history": True, "max_total": 100,
           "system_prompts": {"count": 1, "tokens": 10, "zipf_s": 1.0}}
    toks = traffic.Tokens(mix, 5, 50)
    # turns 0 and 1 came before the run; turn 2 is the first sent in it
    s = traffic.Session(index=0, first=2, due_s=20.0, think_s=[1.0] * 4,
                        system=0, turns=[(5, 3), (5, 3), (6, 4), (60, 30)])
    t, a = toks.turn, lambda j, n: toks.turn(0, j, n, part=1)
    p0 = traffic.prompt_for(mix, toks, s, 0)
    assert p0 == toks.system[0] + t(0, 0, 5)
    p2 = traffic.prompt_for(mix, toks, s, 2)
    assert p2 == p0 + a(0, 3) + t(0, 1, 5) + a(1, 3) + t(0, 2, 6)
    # a turn sent in the run carries the answer the client received
    served = {2: [7, 8, 9, 10]}
    s3 = traffic.Session(index=0, first=2, due_s=20.0, think_s=[1.0] * 4,
                         system=0, turns=[(5, 3), (5, 3), (6, 4), (20, 5)])
    assert traffic.prompt_for(mix, toks, s3, 3, served) == (
        p2 + [7, 8, 9, 10] + t(0, 3, 20))
    assert traffic.prompt_for(mix, toks, s, 3, served) is None   # passes 100
    system, hist = traffic.primes(mix, toks, [s])
    assert system == toks.system
    # turn 1 is the last before the run: its prompt and answer, a prefix of
    # turn 2's
    assert hist == [p2[:len(p2) - 6]]


def test_sessions_under_way_and_closed_loop_phases():
    path = pathlib.Path(smoke.HERE) / "traffic"
    mix = json.loads((path / "qwen2_sessions.json").read_text())
    sess = traffic.sessions(mix, 71.0)
    assert all(s.due_s >= 0 and 0 <= s.first < len(s.turns) for s in sess)
    assert any(s.first > 0 for s in sess) and any(s.first == 0 for s in sess)
    assert all(len(s.think_s) == len(s.turns) for s in sess)
    mean = sum(x for s in sess for x in s.think_s) / sum(len(s.turns) for s in sess)
    assert abs(mean - mix["think_s"]) < 0.3 * mix["think_s"]
    batch = json.loads((path / "qwen2_batch.json").read_text())
    clients = traffic.sessions(batch, 71.0)
    first = [c.turns[0][1] for c in clients]
    rest = [a for c in clients for _, a in c.turns[1:]]
    assert min(first) >= 1 and max(first) <= batch["answer_tokens"]["max"]
    # cut to a phase: the first answers are shorter than the later ones
    assert sum(first) / len(first) < 0.7 * sum(rest) / len(rest)
    assert all(c.due_s == 0 and c.think_s == [0.0] * len(c.turns) for c in clients)
