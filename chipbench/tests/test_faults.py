"""A run whose timed path is broken underneath comes out not correct: the
harness's look for a chip is skipped, the rest of a run is driven at smoke
size, once for each fault a serving cell can have, and in a cell of several
replicas once with one replica's copy of the weights altered and once with
the cache of a request that migrates between replicas left behind."""
import json

import jax
import jax.numpy as jnp
import pytest

import smoke
import harness

CELLS = [w["name"] for w in json.loads(
    (smoke.ROOT / "BENCHMARK.json").read_text())["workloads"]]
# a one-chip cell's traffic on the four-replica deployment a cell may name
REPLICATED = ["qwen2-sessions"]


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


@pytest.mark.parametrize("name", REPLICATED)
def test_one_replica_with_altered_weights_is_not_correct(name, monkeypatch):
    """The last replica's copy of the weights carries noise; the others,
    and the reference, keep the seed's."""
    build = harness.build

    def altered(*a, **k):
        reg, orch = build(*a, **k)
        eng = orch.engines[-1]
        leaves, tree = jax.tree.flatten(eng.params)
        keys = jax.random.split(jax.random.key(0), len(leaves))
        eng.params = jax.tree.unflatten(tree, [
            x + (0.05 * jax.random.normal(k, x.shape)).astype(x.dtype)
            for x, k in zip(leaves, keys)])
        return reg, orch
    monkeypatch.setattr(harness, "build", altered)
    out = smoke.run(name, deploy=smoke.X4)
    assert out["correct"] is False, out["checks"]


@pytest.mark.parametrize("name", REPLICATED)
def test_migration_that_leaves_the_cache_behind_is_not_correct(name, monkeypatch):
    """A request that moves to another replica arrives without its KV
    blocks: the exchange between chips is left out, the destination's
    blocks keep whatever they held."""
    from repro.serving.engine import InferenceEngine
    monkeypatch.setattr(InferenceEngine, "_scatter_blocks",
                        lambda self, data, block_ids, lo: None)
    out = smoke.run(name, deploy=smoke.X4)
    assert out["correct"] is False, out["checks"]
