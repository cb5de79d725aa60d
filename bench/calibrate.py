"""Readings for a cell's correctness limits: the numbers that `run.py`
compares, on sound runs of the program and on its control, many seeds in
one process (the benchmark's own runs never run this).

    python bench/calibrate.py --workload <name> --seconds <s> \
        --seeds <a,b,...> [--control-seeds <c,d,...>]

Each seed gets the cell's set-up (warm calls only for the first seed of
each side), a short window at the cell's own load, and the comparison of
`run.py`; the control runs the cell with its traffic module's `control` switch
on: the program's bfloat16-stream path where it has one, else the bfloat16
reference in the program's place. One JSON line per run, then a summary:
the largest sound reading and the smallest control reading of each number.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    jax = run.use_checkout_cache()
    cell, cfg, driver = run.find_cell(run.BENCH, args.workload)
    try:
        run.device_info(jax, cell["chips"], True)
    except run.NoChip as e:
        print(f"bench/calibrate.py: {e}", file=sys.stderr)
        return 3
    span = jax.profiler.TraceAnnotation
    readings = {"sound": {}, "control": {}}
    for side, seeds in (("sound", args.seeds),
                        ("control", args.control_seeds)):
        for n, seed in enumerate(int(s) for s in seeds.split(",") if s):
            t0 = time.perf_counter()
            cellrun = driver.make(cfg, cell, seed, span,
                                  control=side == "control")
            if n == 0:             # later seeds reuse the compiled shapes
                cellrun.warm()
            record = cellrun.window(args.seconds)
            cellrun.release()
            nums = cellrun.check()
            for k, v in nums.items():
                readings[side].setdefault(k, []).append(float(v))
            print(json.dumps({"side": side, "seed": seed,
                              "numbers": {k: run._num(v)
                                          for k, v in nums.items()},
                              "attempted": record["attempted"],
                              "failed": record["failed"],
                              "metrics": record["metrics"],
                              "run_s": time.perf_counter() - t0}),
                  flush=True)
    summary = {"sound_max": {k: max(v) for k, v in readings["sound"].items()},
               "control_min": {k: min(v)
                               for k, v in readings["control"].items()}}
    print(json.dumps({"workload": args.workload, **summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
