"""A cell cut to smoke size for runs on the CPU: the same files, with the
model, engine and traffic shrunk so a whole run takes seconds."""
from __future__ import annotations

import copy
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
ROOT = HERE.parent
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

SMOKE = {
    "qwen2-0.5b": dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=1,
                       head_dim=16, d_ff=128, vocab_size=512),
    "mamba2-780m": dict(num_layers=2, d_model=64, vocab_size=512, ssm_state=16,
                        ssm_headdim=16, ssm_chunk=32),
}
SMOKE_GAP_LIMIT = 0.05
# a deployment a cell file may name: four one-chip replicas behind the
# cache-aware router (no cell of the benchmark names it yet)
X4 = {"replicas": 4, "lb_policy": "directory"}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9,
         "source": "smoke"}


def cell(name: str, deploy: dict | None = None):
    """``deploy``: the ``replicas`` and ``lb_policy`` the cell file would
    name, one chip a replica."""
    c = copy.deepcopy(harness.load_cell(name))
    if deploy:
        c.cell.update(deploy)
        c.chips = deploy["replicas"]
        c.config = harness.deployment(c.config, c.cell, c.chips)
    conf, mix = c.config, c.mix
    conf["model"].update(SMOKE[conf["arch"]])
    conf["token_ids_below"] = 500
    dense = conf["engine"]["kv_backend"] == "dense"
    conf["engine"].update(capacity=4, max_len=256, buckets=[32, 64] if dense else [16, 32])
    for k, v in (("user_tokens", (8, 40, 16)), ("answer_tokens", (4, 12, 6))):
        mix[k].update(min=v[0], max=v[1], median=v[2])
    mix["max_total"] = 200
    if mix.get("system_prompts"):
        mix["system_prompts"]["tokens"] = 48
    if mix["loop"] == "closed":
        mix["clients"] = 6
    else:
        mix["rate_per_s"] = 2.0
    # room in the check for the request of each further replica
    c.cell.update(ramp_s=1.0, drain_s=30.0, trace_s=1.0, check_tokens=40,
                  check_requests=3 + conf["replicas"])
    # at smoke size on the CPU the program reads gaps of at most 0.003 and
    # the float8 control 0.16-0.44 (three seeds per cell, control.reading)
    c.cell["limits"] = dict(c.cell["limits"], logit_gap=SMOKE_GAP_LIMIT)
    return c


def run(name: str, seed: int = 2 ** 31 + 7, seconds: float = 3.0,
        trace: bool = False, cell_=None, deploy: dict | None = None):
    import time

    import jax
    c = cell_ or cell(name, deploy)
    return harness.run(c, seed, seconds, trace, devices=jax.devices()[:c.chips],
                       peaks=PEAKS, t_start=time.perf_counter(),
                       log=lambda m: None)
