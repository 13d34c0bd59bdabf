"""The readers of the engine's step spans on hand-made step histories:
the window's edges, a window with no prefill, the share's arithmetic, and
an engine that keeps no step spans."""
from types import SimpleNamespace

import pytest

import smoke
import harness

READERS = {name: harness.load_module(smoke.HERE / "layer_metrics" / f"{name}.py")
           for name in ("step_host_ms", "prefill_step_ms", "prefill_step_share")}


def st(t, step_s, wait_s=0.0, prefill_tokens=0):
    return SimpleNamespace(t=t, step_s=step_s, wait_s=wait_s,
                           prefill_tokens=prefill_tokens)


def read(name, histories, w0=10.0, w1=20.0):
    return READERS[name].read(SimpleNamespace(histories=histories, w0=w0, w1=w1))


# steps at the window's edges: 9.99 and 20.0 lie outside, 10.0 inside
HIST = [[st(9.99, 5.0, 0.0, prefill_tokens=512),
         st(10.0, 0.8, 0.75, prefill_tokens=512),
         st(12.0, 0.2, 0.15),
         st(14.0, 0.6, 0.5, prefill_tokens=64),
         st(20.0, 9.0, 0.0, prefill_tokens=512)],
        [st(15.0, 0.4, 0.2)]]


def test_step_host_ms_is_the_mean_of_step_less_wait():
    got = read("step_host_ms", HIST)
    assert got == pytest.approx(1e3 * (0.05 + 0.05 + 0.1 + 0.2) / 4)


def test_prefill_step_ms_is_the_median_of_prefill_steps():
    assert read("prefill_step_ms", HIST) == pytest.approx(700.0)
    assert read("prefill_step_ms", [HIST[0][1:2]]) == pytest.approx(800.0)


def test_prefill_step_share():
    # (0.8 + 0.6) of (0.8 + 0.2 + 0.6 + 0.4) seconds
    assert read("prefill_step_share", HIST) == pytest.approx(70.0)
    assert read("prefill_step_share", [[st(11.0, 0.3)]]) == 0.0


def test_a_window_without_prefill():
    hist = [[st(11.0, 0.1, 0.05), st(12.0, 0.1, 0.02)]]
    assert read("prefill_step_ms", hist) is None
    assert read("step_host_ms", hist) == pytest.approx(1e3 * 0.065)
    assert read("prefill_step_share", hist) == 0.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_silent_on_an_empty_window_or_without_step_spans(name):
    assert read(name, HIST, w0=30.0, w1=40.0) is None
    assert read(name, []) is None
    # an engine whose StepStats has no step_s field
    bare = [[SimpleNamespace(t=11.0, prefill_tokens=64, decode_s=0.1,
                             prefill_s=0.2)]]
    assert read(name, bare) is None
