"""Run one benchmark cell once, on the chip this process finds.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name under this directory: the cell in
`workloads/<name>.json`, its deployment in `configs/<config>.json`, its
traffic's driver in `drivers/<driver>.py`, and each per-layer metric's
reader in `metrics/<metric>.py`. Adding a cell, a configuration or a metric
adds files and edits none.

A run: JAX start-up, the compile cache at its fixed path inside the
checkout, data made from the seed, and one warm call of the cell's own
shape through the entry the window drives (all of that is `setup_s`); then
the window of `--seconds`; then the device's memory peak; then the answers
of the window compared with a plain float64 reference (`reference.py`).
With `--trace 1` the window is traced and the per-layer metrics are read
from the trace and the traffic module's counters; otherwise the
end-to-end ones.

The last line on standard output is one JSON object (correct, attempted,
failed, metrics, device, with --trace 1 a breakdown, and last the compared
numbers with their limits). Without a TPU, or with fewer chips than the
cell asks for, it prints no result and exits 3.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
CACHE_DIR = CHECKOUT / ".jax_cache"

sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(CHECKOUT / "src"))


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def use_checkout_cache():
    """Import JAX with its compile cache in the checkout, whatever the
    environment says: the directory is part of the cache's key."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path: metric files carry dots in their names."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: Path, workload: str):
    """The cell's file, its configuration and its driver, by name."""
    cell = load_json(bench / "workloads" / f"{workload}.json")
    cfg = load_json(bench / "configs" / f"{cell['config']}.json")
    driver = load_module(bench / "drivers" / f"{cell['driver']}.py")
    return cell, cfg, driver


def metric_readers(bench: Path) -> dict:
    return {p.name[:-3]: load_module(p)
            for p in sorted((bench / "metrics").glob("*.py"))}


def device_info(jax, chips: int, require_chip: bool) -> dict:
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"needs a TPU, JAX found {devs[0].platform!r}")
        if len(devs) < chips:
            raise NoChip(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks_for(bench: Path, kind: str) -> dict:
    """The chip's published peaks; a device the table lacks is an error."""
    table = load_json(bench / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"({sorted(table)})")
    return table[kind]


def memory_peak(jax) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts backend compiles while armed: none belong in the window."""

    def __init__(self, jax):
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def _num(x):
    """A compared number for JSON: non-finite values as strings."""
    x = float(x)
    return x if math.isfinite(x) else str(x)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: Path = BENCH, t_start: float | None = None,
             require_chip: bool = True, control: bool = False) -> dict:
    """One run of one cell; returns the result object. Tests call this
    with `require_chip=False` on configurations of their own."""
    t_start = T_START if t_start is None else t_start
    cell, cfg, driver = find_cell(bench, workload)
    import jax

    device = device_info(jax, cell["chips"], require_chip)
    if require_chip:
        peaks_for(bench, device["kind"])
    compiles = CompileCounter(jax)
    span = jax.profiler.TraceAnnotation
    run = driver.make(cfg, cell, seed, span, control=control)
    run.warm()
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    compiles.armed = True
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1       # the bench.* spans, little else
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with span("bench.window"):
            record = run.window(seconds)
        summary = None
        if trace:
            t_trace = time.perf_counter()
            jax.profiler.stop_trace()
            import trace_reduce

            summary = trace_reduce.reduce_dir(trace_dir)
            t_trace = time.perf_counter() - t_trace
    finally:
        compiles.armed = False
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device["memory_peak_bytes"] = memory_peak(jax)
    print(f"[{workload} seed={seed}] window {record['window_s']:.3f}s, "
          f"compiles in window {compiles.count}"
          + (f", trace written and reduced in {t_trace:.1f}s" if trace
             else ""), file=sys.stderr)
    if trace:
        run.probe(record)
    run.release()
    numbers = run.check()
    limits = cell["limits"]
    checks = {k: {"value": _num(v), "limit": limits[k]}
              for k, v in numbers.items()}
    correct = all(float(v) <= limits[k] for k, v in numbers.items())

    if trace:
        metrics = {}
        for name, mod in metric_readers(bench).items():
            value = mod.read(record, summary)
            if value is not None:
                metrics[name] = {"value": value, "unit": mod.UNIT}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    else:
        metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
        metrics.update(record["metrics"])
    out = {"correct": bool(correct), "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = {"device_ops": summary.ops[:10],
                            "idle_gaps": summary.gaps[:10]}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    use_checkout_cache()
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
