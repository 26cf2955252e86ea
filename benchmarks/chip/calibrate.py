#!/usr/bin/env python3
"""Read the two ends of a cell's correctness limits, in one process on the chip.

    python3 benchmarks/chip/calibrate.py --workload <name> \\
        --seeds 1,2,...,12 --seconds 2 --control-seeds 1,2,3

For each of ``--seeds``: a run of the cell as ``run.py`` makes it (set-up,
a short window at the cell's own load, a sample of its answers drawn from
the seed against the plain reference), and the numbers it compares
(``rel_l2``, ``max_err``): the lower readings. For each of
``--control-seeds``: the control, i.e. the reference computed one precision
lower (float8 operands) put in the program's place, compared in the same
way over every input of the pool: the upper readings. One JSON line per
reading, then a summary line with the largest program reading and the
smallest control reading of each number. The benchmark's own runs never run
this; the limits in ``configs/<config>.json`` are set from its readings.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from typing import Dict, List

import run as bench


def _seeds(text: str) -> List[int]:
    return [int(s) for s in text.split(",") if s]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    args = ap.parse_args()
    sys.path.insert(0, str(bench.ROOT / "src"))
    cell = bench.load_cell(args.workload)
    try:
        device = bench.require_tpu(cell.workload["chips"])
        peaks = bench.load_peaks(device["kind"])
    except bench.NoChipError as exc:
        print(f"calibrate.py: {exc}", file=sys.stderr)
        return 2
    bench.use_compile_cache()
    limits = list(cell.cfg["limits"])
    worst_program: Dict[str, float] = {k: 0.0 for k in limits}
    least_control: Dict[str, float] = {k: float("inf") for k in limits}
    for seed in args.seeds:
        res = bench.run_cell(cell, seed, args.seconds, False, device, peaks,
                             log=lambda line: None)
        nums = {k: v["value"] for k, v in res["check"].items()}
        print(json.dumps({"reading": "program", "seed": seed,
                          "steps": res["attempted"], **nums}), flush=True)
        for k in limits:
            worst_program[k] = max(worst_program[k], nums[k])
        gc.collect()
    for seed in args.control_seeds:
        for index in range(cell.mix["pool"]):
            nums = bench.compare(
                cell.config.reference(cell.cfg, cell.mix, seed, index,
                                      control=True),
                cell.config.reference(cell.cfg, cell.mix, seed, index))
            print(json.dumps({"reading": "control", "seed": seed,
                              "input": index, **nums}), flush=True)
            for k in limits:
                least_control[k] = min(least_control[k], nums[k])
    print(json.dumps({"workload": args.workload, "device": device,
                      "program_max": worst_program,
                      "control_min": least_control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
