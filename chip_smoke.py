"""Smoke run of the matrix-profile system's main path on a TPU.

    python chip_smoke.py             # one chip: self-join, AB join, service, fleet
    python chip_smoke.py --chips 4   # four chips: the anytime scheduler only

Every phase goes through the entry points a user calls, at the sizes of the
source paper (`configs/natsa.py`), on data made from fixed seeds by
`data/pipeline.random_walk`, and checks its answer:

  * self-join at seismology-64k and power-512k: an explicit band-engine
    plan (the default `matrix_profile` itself takes the kernel on a TPU)
    and `natsa_matrix_profile` (the compiled Pallas kernel) agree in
    correlation space within `precision.corr_tolerance`,
    64 sampled rows of each match an exact f64 host reference, and a motif
    pair planted in the 64k series is recovered exactly by both;
  * AB join: a 4096-sample query against a 524288-sample reference through
    `ab_join` (the planner's rowstream route), both sides checked on sampled
    rows against the same reference;
  * service: `launch.serve.run_service` over a 64 x 32768 corpus answers 64
    queries, every answer complete, two of them equal to per-pair `ab_join`
    minima;
  * fleet: `StreamingFleet(n=10_000, window=32, capacity=512)` ingests mixed
    bursts of ~100k arrivals, and four tenants equal a `StreamingProfile`
    replay bit for bit (README "Exactness contract");
  * with `--chips 4`: `AnytimeScheduler` over a 4-worker mesh on power-512k
    runs to completion, its profile is sharded over all four devices, its
    round program holds the all-reduce, and it matches the one-device
    profile (`natsa_matrix_profile` on the first chip).

One line of findings per phase, then a last line holding one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
Times printed on the way are smoke timings, not benchmark results. There is
no CPU fallback: without a TPU, or when any phase fails, the script exits
nonzero and prints no result line.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_SAMPLED = 64


def _corr(p, m: int) -> np.ndarray:
    """Distance profile -> correlation (unset rows -> -inf)."""
    p = np.asarray(p, np.float64)
    return np.where(np.isfinite(p), 1.0 - p * p / (2.0 * m), -np.inf)


def _window_norms(ts: np.ndarray, m: int) -> np.ndarray:
    """Centered L2 norm of every length-m window, f64."""
    x = np.asarray(ts, np.float64)
    x = x - x.mean()
    c1 = np.concatenate([[0.0], np.cumsum(x)])
    c2 = np.concatenate([[0.0], np.cumsum(x * x)])
    s1, s2 = c1[m:] - c1[:-m], c2[m:] - c2[:-m]
    return np.sqrt(np.maximum(s2 - s1 * s1 / m, 0.0))


def exact_best_corr(ts_rows, rows, ts_cols, m: int, excl: int = 0):
    """Exact f64 best correlation of each sampled row window of `ts_rows`
    against every window of `ts_cols` (rows and columns at distance < excl
    banned: the self-join exclusion zone), by FFT cross-correlation — a
    reference independent of the sweeps under test."""
    a = np.asarray(ts_rows, np.float64)
    b = np.asarray(ts_cols, np.float64)
    nb = b.shape[0]
    size = 1 << (nb + m - 1).bit_length()
    fb = np.fft.rfft(b - b.mean(), size)
    nrm = _window_norms(b, m)
    cols = np.arange(nb - m + 1)
    out = []
    for i in rows:
        q = a[i:i + m] - a[i:i + m].mean()
        q = q / np.linalg.norm(q)
        # sum_k q[k] * b[j + k] for every j; q sums to 0, so the window
        # means of b drop out
        dots = np.fft.irfft(fb * np.fft.rfft(q[::-1], size), size)
        dots = dots[m - 1:m - 1 + cols.size]
        corr = np.where(nrm > 0, dots / np.maximum(nrm, 1e-300), 0.0)
        if excl:
            corr = np.where(np.abs(cols - i) < excl, -np.inf, corr)
        out.append(corr.max())
    return np.asarray(out)


def _sample_rows(l: int, seed: int, extra=()) -> np.ndarray:
    rng = np.random.default_rng(seed)
    rows = rng.choice(l, size=min(N_SAMPLED, l), replace=False)
    return np.unique(np.concatenate([rows, np.asarray(extra, int)]))


def _check(name: str, err: float, tol: float):
    if not err <= tol:
        raise AssertionError(f"{name}: max |corr error| {err:.3e} > {tol:.3e}")


def assert_compiled_kernel(ts_len: int, m: int, plan) -> float:
    """The self-join plan runs the compiled kernel: not the interpreter, and
    the program holds the Mosaic custom call. Compiles that program from
    shapes alone; returns the compile seconds."""
    import jax
    import jax.numpy as jnp

    from repro.core.zstats import ZStats
    from repro.kernels import ops

    assert plan.backend == "kernel" and plan.interpret is False, plan
    l = ts_len - m + 1
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32)
    shapes = ZStats(ts=f32((ts_len,)), mu=f32((l,)), invn=f32((l,)),
                    df=f32((l,)), dg=f32((l,)), cov0=f32((l,)), window=m)
    fn = jax.jit(functools.partial(
        ops.rowmax_from_stats, excl=plan.exclusion, it=plan.it, dt=plan.dt,
        col_tile=plan.col_tile, interpret=plan.interpret))
    t0 = time.perf_counter()
    text = fn.lower(shapes).compile().as_text()
    compile_s = time.perf_counter() - t0
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compile_s


def engine_self_join(ts, m: int):
    """The self-join through an explicit band-engine plan: on a TPU the
    default `matrix_profile` entry takes the kernel."""
    from repro.core import plan as plan_mod
    from repro.core.result import build_result
    from repro.core.zstats import compute_stats_host

    plan = plan_mod.plan_sweep(m, ts.shape[0] - m + 1, backend="engine")
    stats = compute_stats_host(ts, m)
    return build_result(plan, plan_mod.execute(plan, stats), stats)


def phase_self(name: str, n: int, m: int, seed: int, plant: bool):
    from repro.core import plan as plan_mod
    from repro.core.matrix_profile import default_exclusion
    from repro.core.precision import DEFAULT_PRECISION, corr_tolerance
    from repro.data.pipeline import random_walk
    from repro.kernels import ops

    ts = random_walk(n, seed=seed)
    l = n - m + 1
    excl = default_exclusion(m)
    pair = ()
    if plant:
        a, b = n // 8, (5 * n) // 8
        ts[b:b + m] = ts[a:a + m]
        pair = (a, b)

    plan = plan_mod.plan_sweep(m, l, backend="kernel")
    compile_s = assert_compiled_kernel(n, m, plan)

    t0 = time.perf_counter()
    res_e = engine_self_join(ts, m)
    p_e = np.asarray(res_e.p)
    t_engine = time.perf_counter() - t0
    assert res_e.backend == "engine", res_e.backend
    t0 = time.perf_counter()
    res_k = ops.natsa_matrix_profile(ts, m)
    p_k = np.asarray(res_k.p)
    t_kernel = time.perf_counter() - t0
    assert res_k.backend == "kernel", res_k.backend

    tol = corr_tolerance(DEFAULT_PRECISION, m)
    c_e, c_k = _corr(p_e, m), _corr(p_k, m)
    assert np.all(np.isfinite(c_e)) and np.all(np.isfinite(c_k))
    err_ek = float(np.max(np.abs(c_e - c_k)))
    _check(f"{name} kernel vs engine", err_ek, tol)
    rows = _sample_rows(l, seed + 1, pair)
    ref = exact_best_corr(ts, rows, ts, m, excl)
    err_e = float(np.max(np.abs(c_e[rows] - ref)))
    err_k = float(np.max(np.abs(c_k[rows] - ref)))
    _check(f"{name} engine vs f64 reference", err_e, tol)
    _check(f"{name} kernel vs f64 reference", err_k, tol)
    if plant:
        a, b = pair
        for route, res in (("engine", res_e), ("kernel", res_k)):
            idx = np.asarray(res.i)
            assert idx[a] == b and idx[b] == a, (
                f"{route} missed the planted pair {pair}: "
                f"i[{a}]={idx[a]} i[{b}]={idx[b]}")
    print(f"[self {name}] n={n} m={m} kernel it={plan.it} dt={plan.dt} "
          f"col_tile={plan.col_tile} interpret={plan.interpret} "
          f"tpu_custom_call=yes; |corr| kernel-engine {err_ek:.2e}, "
          f"engine-ref {err_e:.2e}, kernel-ref {err_k:.2e} "
          f"({rows.size} rows, tol {tol:.2e})"
          + (f"; planted pair {pair} exact in both" if plant else "")
          + f"; smoke timings: kernel compile {compile_s:.2f}s, "
          f"engine {t_engine:.2f}s, kernel {t_kernel:.2f}s (first calls)",
          flush=True)


def phase_ab(n_q: int = 4096, n_ref: int = 524288, m: int = 256):
    from repro.core.matrix_profile import ab_join
    from repro.core.precision import DEFAULT_PRECISION, corr_tolerance
    from repro.data.pipeline import random_walk

    q = random_walk(n_q, seed=31)
    ref = random_walk(n_ref, seed=32)
    t0 = time.perf_counter()
    res = ab_join(q, ref, m, return_b=True)
    p_a, p_b = np.asarray(res.p), np.asarray(res.b_p)
    t_ab = time.perf_counter() - t0
    assert res.backend == "rowstream", res.backend
    tol = corr_tolerance(DEFAULT_PRECISION, m)
    rows_a = _sample_rows(p_a.shape[0], 33)
    rows_b = _sample_rows(p_b.shape[0], 34)
    err_a = float(np.max(np.abs(
        _corr(p_a, m)[rows_a] - exact_best_corr(q, rows_a, ref, m))))
    err_b = float(np.max(np.abs(
        _corr(p_b, m)[rows_b] - exact_best_corr(ref, rows_b, q, m))))
    _check("AB query side vs f64 reference", err_a, tol)
    _check("AB reference side vs f64 reference", err_b, tol)
    print(f"[ab] {n_q} x {n_ref} m={m} backend={res.backend}; |corr| vs f64 "
          f"reference: A {err_a:.2e}, B {err_b:.2e} ({rows_a.size}+"
          f"{rows_b.size} rows, tol {tol:.2e}); smoke timing: "
          f"{t_ab:.2f}s (first call)", flush=True)


def phase_service(n_series: int = 64, n: int = 32768, m: int = 128,
                  n_queries: int = 64, query_n: int = 2048):
    from repro.core.matrix_profile import ab_join
    from repro.core.precision import DEFAULT_PRECISION, corr_tolerance
    from repro.launch.serve import run_service

    rep = run_service(n_series, n, m, n_queries, query_n, 1, seed=41)
    answers = rep["answers"]
    assert len(answers) == n_queries, len(answers)
    bad = [(a.qid, a.status, a.coverage) for a in answers
           if a.status != "ok" or a.coverage != 1.0]
    assert not bad, f"incomplete answers: {bad[:4]}"
    tol = corr_tolerance(DEFAULT_PRECISION, m)
    errs = []
    for n_q in (0, n_queries - 1):
        per_pair = np.stack([np.asarray(ab_join(rep["queries"][n_q], s, m).p)
                             for s in rep["series"]])
        best = per_pair.min(axis=0)
        err = float(np.max(np.abs(_corr(answers[n_q].result.p, m)
                                  - _corr(best, m))))
        _check(f"service answer {n_q} vs per-pair ab_join", err, tol)
        errs.append(err)
    print(f"[service] corpus {n_series} x {n}, m={m}, {rep['shards']} "
          f"shard(s) on {rep['mesh_devices']} device(s); {n_queries} queries "
          f"x {query_n} all ok at coverage 1.0; answers 0 and "
          f"{n_queries - 1} vs per-pair ab_join minima "
          f"|corr| {max(errs):.2e}; smoke timings: load {rep['load_s']:.2f}s,"
          f" serve {rep['serve_s']:.2f}s ({rep['qps']:.1f} queries/s)",
          flush=True)


def phase_fleet(n_ten: int = 10_000, m: int = 32, cap: int = 512,
                burst: int = 50_000):
    from repro.core.fleet import StreamingFleet
    from repro.core.streaming import StreamingProfile

    checked = (0, 1, 2, 3)
    rng = np.random.default_rng(51)
    fleet = StreamingFleet(n=n_ten, window=m, capacity=cap)
    replay = {t: StreamingProfile(m) for t in checked}
    total = 0
    t0 = time.perf_counter()
    for _ in range(2):
        # a burst: the checked tenants are hot (64 arrivals each), the rest
        # of the burst lands uniformly on the fleet
        hot = np.repeat(checked, 64)
        ids = np.concatenate([hot, rng.integers(len(checked), n_ten,
                                                burst - hot.size)])
        ids = ids[rng.permutation(ids.size)]
        vals = np.cumsum(rng.normal(size=ids.size))
        total += fleet.ingest(ids, vals)
        for t in checked:
            for v in vals[ids == t]:
                replay[t].append(v)
    t_ingest = time.perf_counter() - t0
    for t in checked:
        got, want = fleet.snapshot(t), replay[t].snapshot()
        for field in ("p", "i", "left_p", "left_i", "right_p", "right_i"):
            a, b = np.asarray(getattr(got, field)), np.asarray(
                getattr(want, field))
            assert a.shape == b.shape and np.array_equal(a, b,
                                                         equal_nan=True), (
                f"tenant {t} {field} differs from the replay")
    l_checked = len(np.asarray(fleet.snapshot(checked[0]).p))
    print(f"[fleet] N={n_ten} m={m} cap={cap}: {total} arrivals in 2 bursts; "
          f"tenants {checked} ({l_checked} subsequences each) bitwise equal "
          f"to a StreamingProfile replay; smoke timing: ingest "
          f"{t_ingest:.2f}s incl. replay and compiles", flush=True)


def phase_scheduler_4(n: int = 524288, m: int = 1024):
    import jax.numpy as jnp

    from repro.core.precision import DEFAULT_PRECISION, corr_tolerance
    from repro.core.scheduler import AnytimeScheduler
    from repro.data.pipeline import random_walk
    from repro.kernels import ops
    from repro.launch.mesh import make_worker_mesh

    ts = random_walk(n, seed=61)
    mesh = make_worker_mesh(4)
    t0 = time.perf_counter()
    sch = AnytimeScheduler(ts, m, mesh)
    k0s, k1s = sch._round_bounds(sch.state.plan.rounds[0])
    text = sch._round_fn.lower(sch._round_stats, sch.state.profile,
                               jnp.asarray(k0s),
                               jnp.asarray(k1s)).compile().as_text()
    assert "all-reduce" in text, "round program has no all-reduce"
    sch.run()
    p_s = np.asarray(sch.result().p)
    t_sched = time.perf_counter() - t0
    devs = sch.state.profile.corr.sharding.device_set
    assert len(devs) == 4, f"profile on {len(devs)} device(s)"
    # the one-device profile through the compiled kernel: the band engine
    # would hold all four chips for minutes while one of them works, and the
    # one-chip run already checks the kernel against the engine at this size
    t0 = time.perf_counter()
    p_1 = np.asarray(ops.natsa_matrix_profile(ts, m).p)
    t_one = time.perf_counter() - t0
    tol = corr_tolerance(DEFAULT_PRECISION, m)
    c_s, c_1 = _corr(p_s, m), _corr(p_1, m)
    assert np.all(np.isfinite(c_s)), "scheduler left rows unset"
    err = float(np.max(np.abs(c_s - c_1)))
    _check("scheduler vs one-device kernel profile", err, tol)
    assert sch.state.fraction_done == 1.0
    print(f"[scheduler x4] n={n} m={m}: {sch.state.plan.n_rounds} "
          f"rounds on a 4-worker mesh, profile sharded over "
          f"{sorted(d.id for d in devs)}, all-reduce in the round program; "
          f"|corr| vs one-device kernel profile {err:.2e} (tol {tol:.2e}); "
          f"smoke timings: scheduler {t_sched:.2f}s, one device "
          f"{t_one:.2f}s (first calls)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the four-chip scheduler phase")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.cache import enable_compile_cache

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devs)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"[device] {dev.platform} {dev.device_kind} x{len(devs)}; "
          f"compile cache {cache}", flush=True)

    from repro.configs.natsa import PAPER_WORKLOADS

    wl = {w.name: w for w in PAPER_WORKLOADS}
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_scheduler_4(wl["power-512k"].n, wl["power-512k"].window)
    else:
        phase_self("seismology-64k", wl["seismology-64k"].n,
                   wl["seismology-64k"].window, seed=11, plant=True)
        phase_self("power-512k", wl["power-512k"].n, wl["power-512k"].window,
                   seed=21, plant=False)
        phase_ab()
        phase_service()
        phase_fleet()
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[done] smoke wall {time.perf_counter() - t0:.1f}s; device peak "
          f"memory {peak if peak is None else f'{peak / 2**30:.2f} GiB'}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
