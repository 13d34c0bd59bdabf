"""Each cell end to end on the CPU at smoke size, and the harness's refusal
to run anywhere but on the chips a cell asks for."""
import json
import os
import subprocess
import sys

import pytest

import smoke
import harness

CELLS = [w["name"] for w in json.loads(
    (smoke.ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_correct_at_smoke_size(name):
    out = smoke.run(name)
    assert out["correct"], out
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in smoke.cell(name).end_to_end}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_counter_metrics(name):
    out = smoke.run(name, trace=True)
    assert out["correct"], out
    # no device plane on the CPU: the trace-read metrics stay silent
    names = set(out["metrics"])
    assert not names & {"mfu", "device_idle_share", "paged_decode_roofline",
                        "ssd_scan_roofline"}
    assert names <= {m["name"] for m in smoke.cell(name).per_layer}
    assert "decode_step_ms" in names
    assert {m["name"] for m in smoke.cell(name).per_layer
            if m["source"] == "program_counter"} <= names


def test_four_replicas_run_correct_at_smoke_size():
    """A cell that names four replicas behind the cache-aware router: one
    on each CPU host device, every replica checked."""
    out = smoke.run("qwen2-sessions", trace=True, deploy=smoke.X4)
    assert out["correct"], out
    assert out["device"]["count"] == 4
    assert "prefix_hit_share" in out["metrics"]


def test_cli_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(smoke.HERE / "run.py"),
                        "--workload", CELLS[0], "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_unknown_device_kind_is_an_error():
    with pytest.raises(SystemExit, match="no peaks for device kind"):
        harness.peaks_for("TPU v99")
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_next_turn_follows_the_served_answer():
    import jax
    c = smoke.cell("qwen2-sessions")
    conf, mix = c.config, c.mix
    ref = harness.load_module(c.conf_dir / conf["reference"])
    weights = harness.make_weights(conf, ref, 3, jax.devices()[0])
    reg, _ = harness.build(conf, ref, weights, harness.model_config(conf))
    p = harness.plan(conf, mix, 3, 6.0, 1.0)
    harness.prime(reg, conf, p.primes)
    r = harness.drive(reg, conf, mix, p, drain_s=30.0)
    by = {(x.session, x.turn): x for x in r.recs}
    pairs = [(x, by[(s, k + 1)]) for (s, k), x in by.items() if (s, k + 1) in by]
    assert len(pairs) >= 3
    for a, b in pairs:
        # the next prompt carries the answer as the client received it, and
        # is due the session's think time after that answer's last token
        assert b.prompt[:len(a.prompt) + len(a.tokens)] == a.prompt + a.tokens
        assert b.due == pytest.approx(
            a.times[-1] + p.sessions[a.session].think_s[a.turn])
