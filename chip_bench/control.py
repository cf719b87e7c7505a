#!/usr/bin/env python3
"""The control: the reference in bfloat16, put in the program's place.

    python3 chip_bench/control.py --workload <cell> --seed <n> [--seed ...]

For each seed it builds the cell's network at its own size, runs the
float64 reference and the bfloat16 one over the cell's compared steps,
and judges the bfloat16 outputs by the cell's limits exactly as a run
judges the program's.  One JSON line per seed: the readings and whether
the control was (rightly) found not correct.  Exits non-zero if any
control passes.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chip_bench import compare, run  # noqa: E402


def control(cell: run.Cell, seed: int) -> dict:
    out = run.reference_outputs(cell, seed,
                                int(cell.checks["compare_steps"]),
                                ("float64", "bfloat16"))
    ok, checks = compare.judge(
        compare.readings(out["bfloat16"][0], out["float64"][0]),
        cell.checks["limits"])
    return {"seed": seed, "control_correct": ok, "checks": checks}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    caught = True
    for seed in args.seed:
        t0 = time.perf_counter()
        res = control(cell, seed)
        res["seconds"] = time.perf_counter() - t0
        caught &= not res["control_correct"]
        print(json.dumps(res), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
