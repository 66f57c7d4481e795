#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's numbers over many
seeds, the control's and each planted fault's, at the cell's own size.

    python3 perfbench/calibrate.py --workload <name> --seeds 1,2,3 \\
            [--window] [--control] [--faults state_unchanged,half_batch]

With ``--window`` each seed is a whole run of the cell with a window of
one LC iteration (``lcjob.run``): it prints the L step's and the C step's
numbers, and for the C step also those of its control and faults
(``lcjob.cstep_readings``). Otherwise each seed prints the L step's
numbers of the program, of each fault planted in it (``lcjob.FAULTS``,
``lcjob.CONFIG_FAULTS``; ``router_flipped`` and ``aux_dropped`` are for
configurations with ``moe``) and, with ``--control``, of the reference in
float8 put in its place.
One JSON line a reading. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as entry


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--window", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    cell = entry.load_cell(args.workload)
    import harness
    device = harness.accelerator(cell["chips"])
    if device is None:
        return 3
    harness.enable_cache(entry.CACHE_DIR)
    job = harness.job(cell)
    faults = [f for f in args.faults.split(",") if f]
    runs = ["program"] + faults + (["control"] if args.control else [])
    for seed in (int(s) for s in args.seeds.split(",")):
        if args.window:
            res = job.run(cell, seed=seed,
                          seconds=cell["traffic_file"]["iteration_s"],
                          trace=False, t_start=time.perf_counter(),
                          device=device, calibrate=True)
            print(json.dumps({"seed": seed, "run": "window", **res}),
                  flush=True)
            continue
        for out in job.calibrate(cell, seed, runs):
            print(json.dumps({"seed": seed, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
