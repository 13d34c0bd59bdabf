#!/usr/bin/env python3
"""Run one cell of the chip benchmark (``BENCHMARK.json`` at the root).

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process on the chips the cell asks for; it exits non-zero, printing no
result, without a TPU or with fewer chips.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each number compared with its limit.

``--sweep r1,r2,...`` (not a benchmark run) steps an open-loop cell through
those arrival rates after one set-up, ``--seconds`` each, and prints a line
per rate: how the tails and the backlog grow.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated arrival rates (per second)")
    args = ap.parse_args(argv)

    import harness
    cell = harness.load_cell(args.workload)
    devices, peaks = harness.require_chips(cell.chips)
    harness.enable_compile_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    if args.sweep:
        import sweep
        sweep.run(cell, args.seed, args.seconds,
                  [float(x) for x in args.sweep.split(",")], devices, log)
        return 0
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                      devices=devices, peaks=peaks, t_start=T_START, log=log)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
