"""What a cell with several replicas adds, on hand-made records and files:
a cell names its own deployment and the configuration stays as its file
says; more replicas than chips is refused; the checked sample takes a
request of every replica and the requests that migrated; the router's
session affinity."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

import smoke
import harness

BENCH = json.loads((smoke.ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
AFFINITY = harness.load_module(smoke.HERE / "layer_metrics" / "session_affinity_share.py")


@pytest.mark.parametrize("name", ["qwen2-sessions", "mamba2-chat-burst", "qwen2-batch"])
def test_one_chip_cells_load_their_configuration_unchanged(name):
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    cell = harness.load_cell(name)
    assert not set(harness.DEPLOYMENT) & set(cell.cell)
    assert cell.config == json.loads(
        (smoke.ROOT / CONFIGS[w["config"]]["file"]).read_text())
    assert cell.config["replicas"] == 1


def test_the_cell_names_its_replicas_and_router():
    """What a cell file names of its deployment replaces the configuration's,
    and nothing else does."""
    file = json.loads((smoke.ROOT / CONFIGS["qwen2-0.5b"]["file"]).read_text())
    conf = harness.deployment(file, dict(smoke.X4, ramp_s=90), 4)
    assert (conf["replicas"], conf["lb_policy"]) == (4, "directory")
    assert {k: v for k, v in conf.items() if k not in harness.DEPLOYMENT} \
        == {k: v for k, v in file.items() if k not in harness.DEPLOYMENT}


def test_more_replicas_than_chips_is_refused():
    conf = {"replicas": 1, "lb_policy": "least", "arch": "x"}
    with pytest.raises(SystemExit, match="4 replicas on 1 chip"):
        harness.deployment(conf, {"replicas": 4}, 1)
    assert harness.deployment(conf, {"lb_policy": "directory"}, 1) == dict(
        conf, lb_policy="directory")
    assert harness.deployment(conf, {"replicas": 2}, 4)["replicas"] == 2


def rec(rid, replica, n_tokens, session=0, turn=0, due=15.0, prompt=None,
        admitted=True):
    return SimpleNamespace(
        rid=rid, session=session, turn=turn, due=due, finish="length",
        prompt=prompt if prompt is not None else [rid] * 10,
        tokens=[1] * n_tokens,
        req=SimpleNamespace(replica=replica, t_admit=due if admitted else None))


def test_sample_takes_a_request_of_every_replica():
    # replica 3's requests are short, so a draw by tokens alone may miss it
    recs = ([rec(i, 0, 100) for i in range(20)] + [rec(20, 1, 50), rec(21, 2, 50)]
            + [rec(22 + i, 3, 2) for i in range(3)])
    for seed in range(20):
        got = harness.sample(recs, seed, 300, 8)
        assert {r.req.replica for r in got} == {0, 1, 2, 3}
        assert len({r.rid for r in got}) == len(got) <= 8


def test_sample_takes_the_requests_that_migrated():
    recs = [rec(i, i % 4, 100) for i in range(24)]
    moved = {5, 17, 22}
    for seed in range(20):
        got = harness.sample(recs, seed, 300, 8, moved)
        assert moved <= {r.rid for r in got}
        assert {r.req.replica for r in got} == {0, 1, 2, 3}
        assert len({r.rid for r in got}) == len(got) <= 8
    # no more than the sample's size, migrated or not
    assert len(harness.sample(recs, 0, 300, 8, set(range(24)))) == 8


def test_one_replica_sample_is_the_longest_then_the_seed_order():
    recs = [rec(i, 0, 10 + i) for i in range(12)]
    got = harness.sample(recs, 5, 60, 8)
    rest = recs[:-1]
    order = [rest[i] for i in np.random.default_rng([5, 5]).permutation(len(rest))]
    want, n = [recs[-1]], len(recs[-1].tokens)
    for r in order:
        if n >= 60:
            break
        want.append(r)
        n += len(r.tokens)
    assert [r.rid for r in got] == [r.rid for r in want]


def affinity(recs, primed=(), w0=10.0, w1=20.0):
    return AFFINITY.read(SimpleNamespace(recs=recs, primed=list(primed), w0=w0, w1=w1))


def test_session_affinity_share():
    head = [7] * 5 + [8] * 5
    recs = [
        rec(0, 1, 3, session=0, turn=0, due=5.0),     # before the window
        rec(1, 1, 3, session=0, turn=1, due=12.0),    # follows 0: same replica
        rec(2, 2, 3, session=0, turn=2, due=14.0),    # follows 1: moved
        rec(3, 0, 3, session=1, turn=0, due=13.0),    # a session's first turn
        # session 2 was under way: its turn 3 opens with its primed turn 2
        rec(4, 3, 3, session=2, turn=3, due=15.0, prompt=head + [1, 2]),
        rec(5, 0, 3, session=0, turn=3, due=16.0, admitted=False),
        rec(6, 2, 3, session=0, turn=4, due=25.0),    # after the window
    ]
    primed = [([7] * 5, 0), (head, 3)]                # a system prompt, session 2
    assert affinity(recs, primed) == pytest.approx(100.0 * 2 / 3)
    # without its primed turn, session 2's turn has nothing to follow
    assert affinity(recs) == pytest.approx(50.0)


def test_session_affinity_share_silent_without_a_later_turn():
    assert affinity([rec(0, 0, 3, session=0, turn=0, due=12.0)]) is None
    assert affinity([]) is None
