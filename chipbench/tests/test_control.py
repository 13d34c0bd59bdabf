"""The control, the plain reference computed in float8 e4m3 in the
program's place, reads wider gaps than the program on the same served
tokens, and a run's own comparison finds it not correct where it finds the
program correct: at smoke size on the CPU, for each cell, against limits
set between the two readings there.  (The readings that set each cell's
own limit are taken on the chip at the cell's own size with
``control.py``.)"""
import json

import jax
import pytest

import smoke
import control

CELLS = [w["name"] for w in json.loads(
    (smoke.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_reads_above_the_program(name):
    c = smoke.cell(name)
    for seed in (11, 2 ** 31 + 12):
        r = control.reading(c, seed, 3.0, True, jax.devices()[:1])
        assert r["tokens"] >= 20 and r["bad_streams"] == 0
        assert r["control_gap"] > r["program_gap"], r
        assert r["control_disagree"] > r["disagree"], r
        assert r["correct"] is True and r["control_correct"] is False, r
