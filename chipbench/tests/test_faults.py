"""A run whose timed path is broken underneath comes out not correct: the
harness's look for a chip is skipped, the rest of a run is driven at smoke
size, once for each fault a serving cell can have."""
import json

import jax
import jax.numpy as jnp
import pytest

import smoke

CELLS = [w["name"] for w in json.loads(
    (smoke.ROOT / "BENCHMARK.json").read_text())["workloads"]]


def altered_token(monkeypatch):
    """Every sampled token is altered where it is produced."""
    import repro.serving.engine as E

    def make_sampler():
        return jax.jit(lambda logits, *a: (jnp.argmax(logits, -1) + 1)
                       .astype(jnp.int32) % logits.shape[-1])
    monkeypatch.setattr(E, "make_sampler", make_sampler)


def state_unchanged(monkeypatch):
    """The decode step returns its cache (KV blocks or SSM state) as it
    came in."""
    from repro.models.lm import LM
    for name in ("decode_step", "decode_step_paged"):
        orig = getattr(LM, name)

        def frozen(self, params, tokens, pos, caches, *a, _orig=orig, **k):
            logits, _ = _orig(self, params, tokens, pos, caches, *a, **k)
            return logits, caches
        monkeypatch.setattr(LM, name, frozen)


@pytest.mark.parametrize("fault", [altered_token, state_unchanged],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    out = smoke.run(name)
    assert out["correct"] is False, out["checks"]
