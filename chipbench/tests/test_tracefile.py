"""The trace reduction: on a small trace recorded on a v5e chip (a quarter
second of the qwen2-sessions cell, gzipped), and on hand-made events."""
import math
import pathlib

import pytest

import smoke  # noqa: F401
import tracefile as tr

DATA = pathlib.Path(__file__).resolve().parent / "data" / "qwen2_sessions.xplane.pb.gz"


@pytest.fixture(scope="module")
def summary():
    return tr.reduce(tr.read(str(DATA)))


def test_chip_trace_busy_and_idle(summary):
    assert summary["chips"] == 1
    assert 0 < summary["busy_s"] <= summary["window_s"] < 1.0
    idle = sum(summary["idle_gap_s"].values())
    assert math.isclose(idle, summary["window_s"] - summary["busy_s"],
                        rel_tol=1e-6, abs_tol=1e-9)


def test_chip_trace_ops_and_kernel(summary):
    own = sum(summary["op_s"].values())
    assert 0 < own <= summary["busy_s"] * 1.001
    assert tr.op_seconds(summary, r"^%paged_decode_attention\b") > 0
    assert tr.op_seconds(summary, r"^%ssd_chunked_scan\b") == 0
    assert all("{" not in k for k in summary["op_s"])       # layouts dropped
    b = tr.breakdown(summary)
    secs = [v for _, v in b["device_ops"]]
    assert len(secs) == 10 and secs == sorted(secs, reverse=True)
    assert all(k.startswith("bench.") for k, _ in b["idle_gaps"])


def test_reduce_by_hand():
    ops = [("%while.1 = loop", 0, 100), ("%fusion.1 = a", 10, 30),
           ("%fusion.2 = b", 50, 40), ("%copy.3 = c", 150, 50)]
    spans = [("bench.registry_step", 0, 120), ("bench.wait", 120, 30),
             ("bench.registry_step", 150, 60)]
    s = tr.reduce({"devices": {0: ops}, "spans": spans})
    assert s["window_s"] == pytest.approx(210e-9)
    assert s["busy_s"] == pytest.approx(150e-9)            # [0,100) + [150,200)
    assert s["op_s"]["%while.1 = loop"] == pytest.approx(30e-9)   # 100 - 30 - 40
    assert s["op_s"]["%fusion.2 = b"] == pytest.approx(40e-9)
    gaps = s["idle_gap_s"]
    assert gaps["bench.registry_step"] == pytest.approx(30e-9)   # [100,120) + [200,210)
    assert gaps["bench.wait"] == pytest.approx(30e-9)            # [120,150)
    assert tr.reduce({"devices": {0: ops}, "spans": []}) is None
