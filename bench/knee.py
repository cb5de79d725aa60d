"""Sweep of offered rates for an open-loop cell, to find its knee: the
highest rate at which the backlog does not grow. Run once when a cell's
rate is fixed; the benchmark's own runs never run this.

    python bench/knee.py --workload <name> --seed <n> --seconds <s> \
        --rates 2,3,4,5

One set-up, then one window per rate. Per rate it prints the queries
offered and answered, the p90 latency, how late the generator ran, and the
overhang: how long past the window the last answer came. Below the knee
the overhang stays near one service time; above it, it grows with the
window.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    jax = run.use_checkout_cache()
    cell, cfg, driver = run.find_cell(run.BENCH, args.workload)
    try:
        run.device_info(jax, cell["chips"], True)
    except run.NoChip as e:
        print(f"bench/knee.py: {e}", file=sys.stderr)
        return 3
    t0 = time.perf_counter()
    cellrun = driver.make(cfg, cell, args.seed, jax.profiler.TraceAnnotation)
    cellrun.warm()
    print(json.dumps({"setup_s": time.perf_counter() - t0}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        rec = cellrun.window(args.seconds, rate=rate)
        print(json.dumps({
            "rate_per_s": rate, "offered": rec["queries"],
            "answered": rec["answered"], "failed": rec["failed"],
            "query_p90_ms": rec["metrics"]["query_p90_ms"]["value"],
            "late_ms_p90": rec["late_ms_p90"],
            "overhang_s": rec["window_s"] - args.seconds,
            "answered_per_s": rec["answered"] / rec["window_s"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
