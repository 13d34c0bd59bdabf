#!/usr/bin/env python3
"""Readings the limits of ``correct`` are set from (run on the chip by
hand, never by the benchmark's runs).

    python3 chipbench/control.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds <s>

One process.  For each seed: weights from the seed, the cell's registry,
the warm-up, a window of ``--seconds`` at the cell's own load, and the
run's own sample of served tokens.  It prints, per seed, the widest gap by
which a served token's logit lies below the float32 reference's best (the
program's reading) and, for the control seeds, the widest gap of the
tokens that the reference computed in float8 puts first at the same
positions (the control's reading), each with ``correct`` as a run judges
it against the cell's limits.  A limit lies above every program reading
and below every control reading.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import harness
    cell = harness.load_cell(args.workload)
    devices, _ = harness.require_chips(cell.chips)
    harness.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in seeds:
        print(json.dumps(reading(cell, seed, args.seconds, seed in controls,
                                 devices)), flush=True)
    return 0


def reading(cell, seed: int, seconds: float, control: bool, devices) -> dict:
    import jax
    import numpy as np

    import harness
    conf, mix, c = cell.config, cell.mix, cell.cell
    ref = harness.load_module(cell.conf_dir / conf["reference"])
    weights = harness.make_weights(conf, ref, seed, devices[0])
    reg, orch = harness.build(conf, ref, weights, harness.model_config(conf))
    harness.warm(reg, orch, conf, mix, conf["token_ids_below"])
    p = harness.plan(conf, mix, seed, seconds, c["ramp_s"])
    harness.prime(reg, conf, p.primes)
    r = harness.drive(reg, conf, mix, p, drain_s=c["drain_s"])
    moved = {e.rid for e in orch.migrations.events}
    del reg, orch
    gc.collect()
    jax.clear_caches()
    chosen = harness.sample(r.recs, seed, c["check_tokens"], c["check_requests"],
                            moved)
    t = time.perf_counter()
    prog, ctrl = harness.reference_gaps(ref, conf["model"], weights, chosen,
                                        control=control)
    faults = harness.stream_faults(r.recs)
    attempted = bool(harness.window_requests(r))
    out = {"workload": cell.name, "seed": seed, "requests": len(chosen),
           "tokens": int(len(prog)), "program_gap": float(np.max(prog)),
           "program_gap_p99": float(np.quantile(prog, 0.99)),
           "disagree": int(np.sum(prog > 0)), "bad_streams": faults,
           "correct": harness.judge(c, float(np.max(prog)), faults, len(prog),
                                    attempted)[0],
           "reference_s": time.perf_counter() - t}
    if control:
        # the control stands in the program's place: its tokens are the
        # float8 reference's, judged by the run's own comparison
        out.update(control_gap=float(np.max(ctrl)),
                   control_disagree=int(np.sum(ctrl > 0)),
                   control_correct=harness.judge(c, float(np.max(ctrl)), 0,
                                                 len(ctrl), attempted)[0])
    return out


if __name__ == "__main__":
    sys.exit(main())
