"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

Paper mapping (NATSA, ICCD'20 / CS.AR'22 extended abstract):
  bench_vs_baseline   — Table "NATSA vs CPU/GPU": brute-force oracle vs the
                        vectorized diagonal engine vs the Pallas kernel
                        (interpret mode) on the same host; derived = speedup
                        over brute force.
  bench_long_series   — n=16384 self-join: the banked-column-accumulator
                        regime (kernel col block bounded by col_tile);
                        engine + kernel must beat the dense oracle (CI gate).
  bench_plan          — SweepPlan layer overhead: plan_sweep + execute vs
                        the direct jitted engine call; added host-side cost
                        gated <= 3% of the direct call (CI gate); also the
                        split no-regression tripwire and the PAY-AS-YOU-GO
                        entry gate (public matrix_profile, minimal default
                        harvest, <= 1.1x the direct core).
  bench_topk          — widened (l, k) top-k accumulators vs the k=1 max
                        harvest on the same engine sweep; k=4 gated <= 2.5x
                        the k=1 row in CI.
  bench_scaling       — Fig "speedup vs #PUs": anytime scheduler on 1..8
                        SPMD workers, meshes over this process's own
                        devices (counts above jax.device_count() are
                        skipped); derived = parallel efficiency vs 1
                        worker.
  bench_anytime       — Fig "anytime convergence": profile error vs fraction
                        of rounds completed; derived = area-under-error.
  bench_partition     — Table "load balance": NATSA balanced partitioning vs
                        naive equal-count split; derived = max/mean work.
  bench_bytes_proxy   — Energy proxy: modeled HBM bytes/cell of the kernel
                        vs a cache-oblivious window recompute; derived =
                        data-movement reduction factor (the quantity NATSA's
                        energy win comes from).
  bench_precision     — mixed-precision gates: bf16-vs-f64 error-bound and
                        epsilon-argmin rows and planted-motif exactness;
                        the bf16 throughput row itself rides
                        bench_long_series so the >=1.5x ratio is an
                        interleaved same-loop A/B.
  bench_lm_train/decode — framework sanity: smoke-arch step latency.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.matrix_profile import matrix_profile  # noqa: E402
from repro.core.ref import matrix_profile_bruteforce  # noqa: E402
from repro.core import partition  # noqa: E402
from repro.data import pipeline  # noqa: E402
from repro.kernels import DEFAULT_DT, DEFAULT_IT, ops  # noqa: E402

ROWS: list[str] = []


def emit(name: str, us: float, derived: str):
    # model rows (ratios, bytes/cell, badness) keep significant digits —
    # a flat :.1f rounded the bytes_per_cell_l* values back to the 0.0
    # this PR removes from the JSON mirror, and coarsened ratio rows enough
    # to mask a gate breach (1.14 prints as 1.1)
    val = f"{us:.1f}" if abs(us) >= 1000.0 else f"{us:.6g}"
    row = f"{name},{val},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def _timeit(fn, *args, reps=3):
    """Best-of-reps wall time in us. `min` (not mean) is the noise-robust
    estimator on shared/throttled hosts: scheduler preemption and allocator
    churn only ever ADD time, so the minimum is the closest observation to
    the true cost."""
    fn(*args)  # compile/warmup
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_vs_baseline():
    for n, m in ((2048, 64), (4096, 128)):
        ts = pipeline.random_walk(n, seed=1)
        t_bf = _timeit(lambda t: matrix_profile_bruteforce(jnp.asarray(t), m)[0],
                       ts, reps=3)
        t_eng = _timeit(lambda t: matrix_profile(t, m).p, ts, reps=5)
        t_krn = _timeit(
            lambda t: ops.natsa_matrix_profile(t, m, it=256, dt=16).p, ts,
            reps=5)
        emit(f"mp_bruteforce_n{n}", t_bf, "baseline")
        emit(f"mp_engine_n{n}", t_eng, f"speedup_vs_bf={t_bf/t_eng:.2f}x")
        emit(f"mp_kernel_interp_n{n}", t_krn,
             f"speedup_vs_bf={t_bf/t_krn:.2f}x(interpret-mode)")


def bench_scaling():
    from repro.core.scheduler import AnytimeScheduler
    from repro.launch.mesh import make_worker_mesh

    ts = pipeline.random_walk(6000, seed=2)
    base = None
    for p in (1, 2, 4, 8):
        if p > jax.device_count():
            break
        sch = AnytimeScheduler(ts, 64, make_worker_mesh(p),
                               chunks_per_worker=4, band=64)
        sch.run(1)  # warmup one round
        t0 = time.perf_counter()
        sch.run()   # fused two-sided chunks: run() alone is the exact profile
        jax.block_until_ready(sch.state.profile.corr)
        t = (time.perf_counter() - t0) * 1e6
        base = base or t
        eff = base / t / p
        emit(f"mp_scaling_workers{p}", t,
             f"speedup={base/t:.2f}x efficiency={eff:.2f}")
        if p > 1:
            # explicit efficiency rows so CI can gate the scaling fix
            # (dynamic band counts + committed initial sharding) without
            # parsing the derived string; value is the RATIO, not us
            emit(f"mp_scaling_efficiency_workers{p}", eff,
                 "value is speedup/workers, not us")


def bench_anytime():
    ts = pipeline.plant_discord(pipeline.sines_with_noise(4000, seed=3),
                                2500, 80)
    m = 64
    p_final = matrix_profile(ts, m).p
    p_final = np.asarray(p_final)
    from repro.core.matrix_profile import ProfileState, chunk_rowmax
    from repro.core.zstats import compute_stats_host
    stats = compute_stats_host(ts, m)
    l = stats.n_subsequences
    excl = 16
    plan = partition.interleaved_chunks(l, excl, 8, chunks_per_worker=2,
                                        band=64)
    state = ProfileState.empty(l)
    done_work, total = 0.0, float(plan.chunk_work().sum())
    auc = 0.0
    t0 = time.perf_counter()
    for r in range(plan.n_rounds):
        for c in plan.rounds[r]:
            if c < 0:
                continue
            k0, k1 = plan.chunks[c]
            width = max(k1 - k0, 1)
            st = chunk_rowmax(stats, jnp.int32(k0), width, 64)
            state = state.merge(st)
            done_work += partition.range_work(l, (k0, k1))
        d = np.asarray(state.to_distance(m))
        err = np.nanmean(np.where(np.isfinite(d), d, np.nan) - p_final)
        frac = done_work / total
        auc += max(err, 0) / plan.n_rounds
        emit(f"mp_anytime_round{r}", (time.perf_counter() - t0) * 1e6,
             f"frac_work={frac:.2f} mean_excess_dist={max(err,0):.4f}")
    emit("mp_anytime_auc", (time.perf_counter() - t0) * 1e6,
         f"area_under_error={auc:.4f}")


def bench_ab_join():
    """AB join (query corpus vs reference) — engine, kernel, brute force.

    The engine/kernel rows harvest the B-side profile from the same sweep
    (`return_b`), so each timed call produces BOTH joins; the brute force
    row computes only the A side. Three engine rows separate the two 2-D
    tiling effects: `ab_engine` is `ab_join`'s planner dispatch (short side
    on rows, row-streamed here), `ab_engine_banded` an engine-backend
    `SweepPlan` forcing the row-CLAMPED band sweep — the path large joins
    and the distributed/anytime scheduler use — and `ab_engine_unclamped`
    the `clamp_rows=False` A/B-comparison plan (the ONLY remaining way to
    run the PR-2 full-height sweep), so `clamp_gain` compares like with
    like."""
    from repro.core import plan as plan_mod
    from repro.core.matrix_profile import ab_join
    from repro.core.ref import ab_join_bruteforce
    from repro.core.zstats import compute_cross_stats_host

    def banded(a, b, m, clamp):
        cross = compute_cross_stats_host(np.asarray(a), np.asarray(b), m)
        plan = plan_mod.plan_sweep(m, cross.l_a, cross.l_b, backend="engine",
                                   band=256, reseed_every=512,
                                   clamp_rows=clamp)
        return plan_mod.execute(plan, cross).dist

    for (na, nb, m) in ((2048, 1024, 64), (4096, 512, 128)):
        ts_a = pipeline.random_walk(na, seed=11)
        ts_b = pipeline.random_walk(nb, seed=12)
        t_bf = _timeit(lambda a, b: ab_join_bruteforce(
            jnp.asarray(a), jnp.asarray(b), m)[0], ts_a, ts_b, reps=2)
        t_eng = _timeit(lambda a, b: ab_join(a, b, m, return_b=True).p,
                        ts_a, ts_b, reps=3)
        t_band = _timeit(lambda a, b: banded(a, b, m, True),
                         ts_a, ts_b, reps=2)
        t_unc = _timeit(lambda a, b: banded(a, b, m, False),
                        ts_a, ts_b, reps=2)
        t_krn = _timeit(lambda a, b: ops.natsa_ab_join(
            a, b, m, it=256, dt=16, return_b=True).p, ts_a, ts_b, reps=2)
        emit(f"ab_bruteforce_a{na}_b{nb}", t_bf, "baseline")
        emit(f"ab_engine_a{na}_b{nb}", t_eng,
             f"speedup_vs_bf={t_bf/t_eng:.2f}x(two-sided)")
        emit(f"ab_engine_banded_a{na}_b{nb}", t_band,
             f"speedup_vs_bf={t_bf/t_band:.2f}x(row-clamped band engine)")
        emit(f"ab_engine_unclamped_a{na}_b{nb}", t_unc,
             f"clamp_gain={t_unc/t_band:.2f}x(pre-clamp sweep)")
        emit(f"ab_kernel_interp_a{na}_b{nb}", t_krn,
             f"speedup_vs_bf={t_bf/t_krn:.2f}x(interpret-mode two-sided)")


def bench_long_series():
    """Long self-join (n=16384): the banked-column-accumulator regime.

    The kernel row runs with an explicit `col_tile` so its per-step column
    block is O(col_tile), not O(l) — the layout that scales past VMEM on
    real hardware (ROADMAP open item 2) — and must still beat the dense
    brute-force oracle even in interpret mode. The engine row streams the
    same triangle through the band engine.

    Mixed precision rides the same series: the bf16-stream engine row
    (`precision="bf16"` routes the normalized self-join through the
    dot-product tile sweep) is timed INTERLEAVED with the f32 row so the
    CI-gated >=1.5x ratio is an honest same-loop A/B, and both kernel
    rows convert to `mp_kernel_roofline_fraction_*` — achieved fraction
    of the modeled HBM bandwidth roofline (nonzero/finite is the gate;
    CPU-host interpret wall clock is far below 1.0 by construction)."""
    from repro.core.matrix_profile import matrix_profile
    from repro.core.ref import matrix_profile_bruteforce
    from repro.launch import roofline
    n, m = 16384, 128
    excl = m // 4
    ts = pipeline.random_walk(n, seed=21)
    t_bf = _timeit(lambda t: matrix_profile_bruteforce(jnp.asarray(t), m)[0],
                   ts, reps=1)

    def eng_f32(t):
        return matrix_profile(t, m).p

    def eng_bf16(t):
        return matrix_profile(t, m, precision="bf16").p

    jax.block_until_ready(eng_f32(ts))      # compile/warmup both traces
    jax.block_until_ready(eng_bf16(ts))
    t_eng = t_eng16 = float("inf")
    for r in range(3):
        arms = ((eng_f32, "f32"), (eng_bf16, "bf16"))
        for fn, which in (arms if r % 2 == 0 else arms[::-1]):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(ts))
            dt_ = time.perf_counter() - t0
            if which == "f32":
                t_eng = min(t_eng, dt_)
            else:
                t_eng16 = min(t_eng16, dt_)
    t_eng, t_eng16 = t_eng * 1e6, t_eng16 * 1e6
    t_krn = _timeit(lambda t: ops.natsa_matrix_profile(
        t, m, it=2048, dt=64, col_tile=4096).p, ts, reps=1)
    t_krn16 = _timeit(lambda t: ops.natsa_matrix_profile(
        t, m, it=2048, dt=64, col_tile=4096, precision="bf16").p, ts, reps=1)
    emit(f"mp_bruteforce_n{n}", t_bf, "baseline")
    emit(f"mp_engine_n{n}", t_eng, f"speedup_vs_bf={t_bf/t_eng:.2f}x")
    emit(f"mp_engine_bf16_n{n}", t_eng16,
         f"speedup_vs_f32={t_eng/t_eng16:.2f}x(gate>=1.5; interleaved reps)")
    emit(f"mp_kernel_interp_n{n}", t_krn,
         f"speedup_vs_bf={t_bf/t_krn:.2f}x(banked col_tile=4096)")
    emit(f"mp_kernel_interp_bf16_n{n}", t_krn16,
         f"vs_f32_kernel={t_krn/t_krn16:.2f}x(interpret-mode, ungated)")
    l = n - m + 1
    frac = roofline.roofline_fraction(l, excl, t_krn / 1e6, it=2048, dt=64)
    frac16 = roofline.roofline_fraction(l, excl, t_krn16 / 1e6, it=2048,
                                        dt=64, stream_bytes=2)
    emit(f"mp_kernel_roofline_fraction_n{n}", frac,
         "achieved/HBM-roofline (model units; gate: nonzero, not us)")
    emit(f"mp_kernel_roofline_fraction_bf16_n{n}", frac16,
         "bf16 streams halve the modeled traffic (gate: nonzero, not us)")


def bench_batch():
    """Batched multi-series profiles: one vmapped dispatch vs a host loop."""
    from repro.core.matrix_profile import batch_profile, matrix_profile
    for (bs, n, m) in ((8, 1024, 32), (16, 512, 16)):
        stack = np.stack([pipeline.random_walk(n, seed=100 + i)
                          for i in range(bs)])
        t_loop = _timeit(
            lambda s: jax.block_until_ready(
                [matrix_profile(row, m).p for row in s]),
            stack, reps=2)
        t_batch = _timeit(lambda s: batch_profile(s, m).p, stack, reps=3)
        emit(f"mp_loop_b{bs}_n{n}", t_loop, "baseline")
        emit(f"mp_batch_b{bs}_n{n}", t_batch,
             f"speedup_vs_loop={t_loop/t_batch:.2f}x")


def bench_plan():
    """Planner overhead: `plan_sweep` + `execute` vs the jitted engine core
    called directly — must stay within 3% (CI-gated), so routing EVERY entry
    point through plans costs nothing.

    Both paths run the IDENTICAL jitted executable (one shared jit cache
    entry), so the planner's entire cost is host-side: dataclass build +
    dispatch. That is what the gated row measures — the ADDED host-side time
    (async dispatch, no device wait; a retrace/recompile regression would
    land squarely in it) as a fraction of the direct call's end-to-end
    wall time. Gating the end-to-end RATIO instead is untenable on shared
    runners: a null A/A comparison of the same function against itself
    wobbles ±4% run-to-run (scheduler bursts outlive any interleaving),
    swamping a 3% bound; the end-to-end rows are still emitted and carry a
    generous 1.5x catastrophic-only tripwire in CI."""
    import statistics

    from repro.core import plan as plan_mod
    from repro.core.matrix_profile import (DEFAULT_BAND, DEFAULT_RESEED,
                                           profile_from_stats)
    from repro.core.zstats import compute_stats_host

    n, m, excl = 4096, 128, 32          # excl == default_exclusion(128):
    ts = pipeline.random_walk(n, seed=31)   # both paths share one jit entry
    stats = compute_stats_host(np.asarray(ts), m)

    def direct(s):
        return profile_from_stats(s, excl, DEFAULT_BAND,
                                  DEFAULT_RESEED).merged.to_distance(m)

    def planned(s):
        plan = plan_mod.plan_sweep(m, s.n_subsequences, exclusion=excl)
        return plan_mod.execute(plan, s).dist

    jax.block_until_ready(direct(stats))
    jax.block_until_ready(planned(stats))    # compile/warmup both paths

    def dispatch_us(fn, reps=12):
        """Median host-side cost of one call: dispatch timed against an IDLE
        device (block + discard between samples — back-to-back async calls
        would hit inflight-queue backpressure and time the device instead)."""
        samples = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn(stats)
            samples.append(time.perf_counter() - t0)
            jax.block_until_ready(out)
        return statistics.median(samples) * 1e6

    ts_np = np.asarray(ts)

    def entry(t):
        return matrix_profile(t, m, excl).p

    jax.block_until_ready(entry(ts_np))

    # INTERLEAVED reps: timing all direct reps then all planned reps lets
    # slow host drift (thermal/cgroup throttling) masquerade as a path
    # difference; alternating them exposes both paths to the same noise,
    # so the min-of-reps ratio is an honest A/B. The entry path rides the
    # same loop: its reps see the same noise as the direct reps they are
    # gated against.
    best_d = best_p = best_e = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(direct(stats))
        best_d = min(best_d, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(planned(stats))
        best_p = min(best_p, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(entry(ts_np))
        best_e = min(best_e, time.perf_counter() - t0)
    t_direct, t_plan = best_d * 1e6, best_p * 1e6
    t_entry = best_e * 1e6
    overhead_us = max(dispatch_us(planned) - dispatch_us(direct), 0.0)
    overhead_pct = 100.0 * overhead_us / t_direct
    emit(f"mp_engine_direct_n{n}", t_direct, "baseline(direct engine core)")
    emit(f"mp_plan_execute_n{n}", t_plan,
         f"e2e_ratio={t_plan / t_direct:.3f}x(noise-dominated, tripwire only)")
    emit(f"mp_plan_overhead_pct_n{n}", overhead_pct,
         f"added_host_us={overhead_us:.0f} of {t_direct:.0f}us "
         f"direct(gate<=3)")
    # the planned path now ALSO finishes the left/right split (two extra
    # O(l) distance conversions on top of the shared O(l^2) sweep) — this
    # ratio is the left/right-split no-regression tripwire (CI gate <=1.5x,
    # catastrophic-only: a split path that re-swept or materialized O(l^2)
    # state would blow straight through it)
    emit(f"mp_split_overhead_ratio_n{n}", t_plan / t_direct,
         f"split_e2e_ratio(gate<=1.5; value is the ratio, not us)")
    # the PUBLIC entry — host stats + plan + execute + lazy ProfileResult —
    # against the bare jitted core, interleaved in the same loop. This is
    # the pay-as-you-go reclaim gate: under eager two-sided harvests the
    # entry paid two extra conversions + result materialization per call;
    # with the minimal default harvest it must stay within 1.1x of the
    # direct core (CI gate), stats prep included.
    emit(f"mp_entry_n{n}", t_entry,
         f"entry_e2e(matrix_profile incl host stats)")
    emit(f"mp_entry_overhead_ratio_n{n}", t_entry / t_direct,
         f"entry_vs_direct(gate<=1.1; value is the ratio, not us)")


def bench_topk():
    """Top-k harvest overhead: the widened (l, k) insertion-merge
    accumulators vs the k=1 max harvest, same band engine, same sweep
    (n=4096 matches the CI-gated mp_engine_n4096 row; the gate holds
    k=4 within 2.5x of k=1 — measured ~1.45x on the reference host).
    Also emits the AB rowstream top-k row for visibility (ungated)."""
    from repro.core.matrix_profile import ab_join, matrix_profile

    n, m = 4096, 128
    ts = pipeline.random_walk(n, seed=41)
    t_k1 = _timeit(lambda t: matrix_profile(t, m).p, ts, reps=3)
    t_k4 = _timeit(lambda t: matrix_profile(t, m, k=4).topk_p, ts, reps=3)
    emit(f"mp_engine_topk1_n{n}", t_k1, "baseline(k=1 entry, same bench)")
    emit(f"mp_engine_topk4_n{n}", t_k4,
         f"topk_overhead={t_k4/t_k1:.2f}x(gate<=2.5 vs mp_engine_n{n})")
    a = pipeline.random_walk(4096, seed=42)
    b = pipeline.random_walk(512, seed=43)
    t_ab = _timeit(lambda x, y: ab_join(x, y, m, return_b=True,
                                        k=4).topk_p, a, b, reps=2)
    emit("ab_rowstream_topk4_a4096_b512", t_ab, "rowstream insertion top-k")


def bench_ckpt_overhead():
    """Fault-tolerance tax: a supervised run that checkpoints EVERY round
    (hardened format — crc32 checksums, .prev rotation) vs the plain
    anytime `run()` on the same 1-worker schedule (n=4096). Checkpointing
    is host-side npz + crc off the dispatch path, so the gated ratio row
    must stay <= 1.3x (CI gate). Reps are interleaved so host drift hits
    both arms alike."""
    import tempfile

    from repro.core.faults import FaultPolicy
    from repro.core.scheduler import AnytimeScheduler
    n, m = 4096, 128
    ts = pipeline.random_walk(n, seed=51)
    mesh = jax.make_mesh((1,), ("workers",), (jax.sharding.AxisType.Auto,))

    def mk():
        return AnytimeScheduler(ts, m, mesh, chunks_per_worker=8, band=64)

    mk().run()                          # compile/warmup the round fn

    def plain():
        s = mk()
        s.run()
        jax.block_until_ready(s.state.profile.corr)

    def supervised():
        s = mk()
        with tempfile.TemporaryDirectory() as td:
            s.run_supervised(FaultPolicy(checkpoint_every=1),
                             checkpoint_path=os.path.join(td, "ck.npz"))
        jax.block_until_ready(s.state.profile.corr)

    best_p = best_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        plain()
        best_p = min(best_p, time.perf_counter() - t0)
        t0 = time.perf_counter()
        supervised()
        best_s = min(best_s, time.perf_counter() - t0)
    emit(f"mp_plain_run_n{n}", best_p * 1e6, "baseline(plain anytime run)")
    emit(f"mp_ckpt_supervised_n{n}", best_s * 1e6,
         "supervised, checkpoint every round (hardened format)")
    emit(f"mp_ckpt_overhead_n{n}", best_s / best_p,
         "supervised_ckpt_vs_plain(gate<=1.3; value is the ratio, not us)")


def bench_fleet():
    """Streaming fleet ingest throughput at N=10k tenants vs the per-series
    `StreamingProfile` loop it replaces.

    One fleet round = one arrival for EVERY tenant = ONE jitted dispatch
    (the whole point of the stacked device state); the loop baseline pays
    a full host append per series, so it is timed on 128 series and
    extrapolated linearly (it is embarrassingly linear in N — there is no
    cross-series work to amortize). Gated in CI: fleet arrivals/sec must
    be >= 10x the loop. Throughput/latency rows carry arrivals-per-second
    and us respectively; see each row's derived note."""
    from repro.core.fleet import StreamingFleet
    from repro.core.streaming import StreamingProfile
    import statistics

    n, m, cap, excl = 10_000, 8, 96, 2
    rng = np.random.default_rng(61)
    fleet = StreamingFleet(n, window=m, capacity=cap, exclusion=excl)
    tids = np.arange(n)
    # prefill half the capacity in ONE grouped ingest (tile order puts the
    # r-th repeat of tenant t in round r, matching pre.reshape(-1))
    pre = rng.standard_normal((cap // 2, n))
    fleet.ingest(np.tile(tids, cap // 2), pre.reshape(-1))
    fleet.ingest(tids, rng.standard_normal(n))   # warmup single-round trace
    jax.block_until_ready(fleet._state)
    lat = []
    for _ in range(16):
        v = rng.standard_normal(n)
        t0 = time.perf_counter()
        fleet.ingest(tids, v)
        jax.block_until_ready(fleet._state)
        lat.append(time.perf_counter() - t0)
    p50_us = statistics.median(lat) * 1e6
    fleet_aps = n / min(lat)
    # per-series loop baseline: same window/exclusion, same fill level,
    # one append per series per round, extrapolated from 128 series
    n_loop = 128
    sps = [StreamingProfile(m, excl) for _ in range(n_loop)]
    seed_rows = rng.standard_normal((n_loop, cap // 2))
    for sp, row in zip(sps, seed_rows):
        sp.append(row)
    for sp in sps:                                # warmup the append path
        sp.append(rng.standard_normal(1))
    best = float("inf")
    for _ in range(3):
        vals = rng.standard_normal(n_loop)
        t0 = time.perf_counter()
        for sp, v in zip(sps, vals):
            sp.append([v])
        best = min(best, time.perf_counter() - t0)
    loop_aps = n_loop / best
    emit("fleet_ingest_latency_p50", p50_us,
         f"one round = N={n} arrivals in one dispatch (median of 16)")
    emit("fleet_arrivals_per_sec_n10k", fleet_aps,
         f"vs_loop={fleet_aps/loop_aps:.1f}x(gate>=10x; "
         f"value is arrivals/sec, not us)")
    emit("fleet_loop_arrivals_per_sec", loop_aps,
         f"per-series StreamingProfile x{n_loop} extrapolated "
         f"(value is arrivals/sec, not us)")


def bench_partition():
    l, excl = 500_000, 64
    for parts in (16, 256):
        nat = partition.balanced_ranges(l, excl, parts, band=64)
        naive = [(int(k[0]), int(k[-1]) + 1) for k in
                 np.array_split(np.arange(excl, l), parts)]
        b_nat = partition.balance_badness(l, nat)
        b_naive = partition.balance_badness(l, naive)
        # value column carries the NATSA badness (max/mean work, 1.0 =
        # perfect balance) — these rows used to emit a hardcoded 0.0,
        # making the JSON mirror useless for cross-PR comparison
        emit(f"partition_badness_p{parts}", b_nat,
             f"natsa={b_nat:.3f} naive={b_naive:.3f} "
             f"straggler_reduction={b_naive/b_nat:.2f}x")
    # rectangular AB space: diagonal lengths ramp at BOTH corners
    la, lb = 400_000, 150_000
    for parts in (16, 256):
        nat = partition.balanced_ranges_ab(la, lb, parts, band=64)
        naive = [(int(k[0]), int(k[-1]) + 1) for k in
                 np.array_split(np.arange(-(la - 1), lb), parts)]
        b_nat = partition.balance_badness_ab(la, lb, nat)
        b_naive = partition.balance_badness_ab(la, lb, naive)
        emit(f"partition_ab_badness_p{parts}", b_nat,
             f"natsa={b_nat:.3f} naive={b_naive:.3f} "
             f"straggler_reduction={b_naive/b_nat:.2f}x")


def bench_bytes_proxy():
    # model the kernel's ACTUAL default tiling (repro.kernels.DEFAULT_IT/DT
    # — the same constants the launch signatures use) instead of the stale
    # it=512/dt=32 this bench used to hardcode; value column carries the
    # modeled bytes/cell (used to be a flat 0.0)
    for l, m in ((65536, 256), (262144, 512)):
        excl = m // 4
        streamed = ops.hbm_bytes_per_cell(l, excl, it=DEFAULT_IT,
                                          dt=DEFAULT_DT)
        naive = 2 * m * 4  # re-reading both windows per cell
        emit(f"bytes_per_cell_l{l}", streamed,
             f"natsa_stream={streamed:.4g}B naive={naive}B "
             f"movement_reduction={naive/streamed:.0f}x "
             f"(it={DEFAULT_IT} dt={DEFAULT_DT})")
        # reduced-stream variant: df/dg/invn move at 2 B/elem, seeds and
        # profile/column traffic stay 4-byte — the ratio is what a bf16
        # PrecisionSpec buys in pure data movement
        bf16 = ops.hbm_bytes_per_cell(l, excl, it=DEFAULT_IT, dt=DEFAULT_DT,
                                      stream_bytes=2)
        emit(f"bytes_per_cell_bf16_l{l}", bf16,
             f"bf16_stream={bf16:.4g}B "
             f"reduction_vs_f32={streamed/bf16:.2f}x "
             f"(it={DEFAULT_IT} dt={DEFAULT_DT})")


def bench_precision():
    """Mixed-precision error bounds.

    Two row families, both CI-gated:

      * error bounds on the SAME n=16384 series the throughput gate uses:
        bf16-stream profile vs the f64 oracle (`precision="f64"` under
        `x64_scope`). `mp_bf16_err_ratio_n16384` is max|p_bf16 - p_f64|
        over the ANALYTIC `profile_tolerance` (gate <= 1.0 — the bound is
        derived, not fitted); `mp_bf16_argmin_agree_n16384` is the
        epsilon-argmin rate: the fraction of rows whose bf16-chosen
        neighbor is within tolerance of the oracle's best distance
        (gate >= 0.99 on smooth data; strict index agreement rides the
        derived column for visibility);
      * planted-motif exactness: two bitwise-identical windows planted far
        apart — the bf16 sweep must pair them EXACTLY (value 1.0)."""
    from repro.core.matrix_profile import matrix_profile
    from repro.core.precision import as_precision, profile_tolerance
    from repro.core.zstats import x64_scope

    n, m = 16384, 128
    ts = pipeline.random_walk(n, seed=21)
    spec = as_precision("bf16")
    tol = profile_tolerance(spec, m)
    res16 = matrix_profile(ts, m, precision="bf16")
    p16 = np.asarray(res16.p, np.float64)
    i16 = np.asarray(res16.i)
    with x64_scope():
        res64 = matrix_profile(np.asarray(ts, np.float64), m,
                               precision="f64")
        p64 = np.asarray(res64.p, np.float64)
        i64 = np.asarray(res64.i)
    finite = np.isfinite(p64) & np.isfinite(p16)
    maxerr = float(np.max(np.abs(p16[finite] - p64[finite])))
    emit(f"mp_bf16_maxerr_n{n}", maxerr,
         f"analytic_tol={tol:.3f} (bf16 stream, f32 accum, m={m})")
    emit(f"mp_bf16_err_ratio_n{n}", maxerr / tol,
         "maxerr/profile_tolerance(gate<=1.0; value is the ratio, not us)")
    # epsilon-argmin: score bf16's CHOSEN neighbor in f64 and accept it
    # when it is within tolerance of the oracle's best — index ties on
    # smooth data flip freely under any rounding, distances must not
    ts64 = np.asarray(ts, np.float64)
    w = np.lib.stride_tricks.sliding_window_view(ts64, m)
    wz = (w - w.mean(axis=1, keepdims=True))
    wz /= np.linalg.norm(wz, axis=1, keepdims=True)
    corr = np.einsum("ij,ij->i", wz[finite], wz[np.asarray(i16)[finite]])
    d_chosen = np.sqrt(np.maximum(2.0 * m * (1.0 - corr), 0.0))
    agree = float(np.mean(d_chosen <= p64[finite] + tol))
    strict = float(np.mean(i16[finite] == i64[finite]))
    emit(f"mp_bf16_argmin_agree_n{n}", agree,
         f"eps-argmin(gate>=0.99; strict_idx={strict:.4f}; "
         f"value is a fraction, not us)")
    # planted motif: two identical windows must pair exactly at ANY stream
    # precision — the match is corr == 1 against a field of strictly worse
    # candidates, so no rounding can flip it
    ts_pl = np.array(pipeline.random_walk(4096, seed=22), np.float64)
    a_pos, b_pos = 512, 3000
    ts_pl[b_pos:b_pos + m] = ts_pl[a_pos:a_pos + m]
    r_pl = matrix_profile(ts_pl, m, precision="bf16")
    ip = np.asarray(r_pl.i)
    exact = float(ip[a_pos] == b_pos and ip[b_pos] == a_pos)
    emit("mp_bf16_planted_exact", exact,
         f"planted pair ({a_pos},{b_pos}) recovered exactly "
         f"(gate==1; value is a flag, not us)")


def bench_lm_train():
    from repro import configs
    from repro.models import steps as steps_lib
    from repro.models import transformer
    from repro.models.common import init_params
    from repro.optim import adamw
    for arch in ("llama3-8b", "olmoe-1b-7b"):
        cfg = configs.get_smoke(arch)
        params = init_params(jax.random.key(0), transformer.model_spec(cfg))
        step = jax.jit(steps_lib.make_train_step(
            cfg, None, adamw.AdamWConfig(total_steps=10)))
        state = adamw.init_state(params)
        tok = jnp.ones((2, 32), jnp.int32)
        batch = {"tokens": tok, "labels": tok}
        us = _timeit(lambda p, s, b: step(p, s, b)[2]["loss"],
                     params, state, batch)
        emit(f"lm_train_step_smoke_{arch}", us, "cpu-smoke-config")


def bench_lm_decode():
    from repro import configs
    from repro.models import steps as steps_lib
    from repro.models import transformer
    from repro.models.common import init_params
    for arch in ("qwen2-7b", "rwkv6-3b"):
        cfg = configs.get_smoke(arch)
        params = init_params(jax.random.key(0), transformer.model_spec(cfg))
        cache = transformer.init_cache(cfg, params, 2, 64)
        dec = jax.jit(steps_lib.make_decode_step(cfg, None))
        batch = {"tokens": jnp.ones((2, 1), jnp.int32),
                 "cache_len": jnp.int32(5)}
        us = _timeit(lambda p, c, b: dec(p, c, b)[0], params, cache, batch)
        emit(f"lm_decode_step_smoke_{arch}", us, "cpu-smoke-config")


def bench_serve():
    """Profile service vs one-query-at-a-time: a 64-series resident corpus
    answering 16 concurrent queries in batched vmapped sweeps, against the
    naive loop calling `ab_join` per (query, series) pair. The service
    amortizes corpus-side stats (computed once at load) and sweep dispatch
    (one batched engine call per shard group), so the gap is the whole
    point of the serving tier."""
    from repro.core.matrix_profile import ab_join
    from repro.serve import ProfileService, ShardedCorpus

    rng = np.random.default_rng(11)
    m, n_series = 64, 64
    series = [rng.normal(size=384) for _ in range(n_series)]
    queries = [rng.normal(size=192) for _ in range(16)]

    corpus = ShardedCorpus(series, m)
    svc = ProfileService(corpus, max_pending=64, max_batch=16)
    svc.serve(queries)                   # warm the batch-16 compiled variant
    t0 = time.perf_counter()
    answers = svc.serve(queries)
    t_batched = time.perf_counter() - t0
    assert all(a.status == "ok" for a in answers)
    qps_batched = len(queries) / t_batched

    # sequential baseline: the loop a user without the service writes —
    # fresh entry-point call per pair; 2 queries suffice (every call after
    # jit warmup costs the same) and keep the bench CI-sized
    ab_join(queries[0], series[0], m).p        # warm the pair path
    sample = queries[:2]
    t0 = time.perf_counter()
    for q in sample:
        for s in series:
            np.asarray(ab_join(q, s, m).p)
    t_seq = time.perf_counter() - t0
    qps_seq = len(sample) / t_seq
    speedup = qps_batched / qps_seq
    emit("serve_queries_per_sec_c64", qps_batched,
         f"value is queries/sec, not us; sequential={qps_seq:.2f}q/s "
         f"speedup={speedup:.2f}x")
    emit("serve_batched_speedup_c64", speedup,
         "value is batched/sequential qps ratio, not us")


BENCHES = {
    "baseline": bench_vs_baseline,
    "ab_join": bench_ab_join,
    "long": bench_long_series,
    "plan": bench_plan,
    "topk": bench_topk,
    "ckpt": bench_ckpt_overhead,
    "batch": bench_batch,
    "fleet": bench_fleet,
    "partition": bench_partition,
    "bytes": bench_bytes_proxy,
    "precision": bench_precision,
    "anytime": bench_anytime,
    "scaling": bench_scaling,
    "serve": bench_serve,
    "lm_train": bench_lm_train,
    "lm_decode": bench_lm_decode,
}


def main(argv: list[str] | None = None) -> None:
    """Run all benches, or a subset: python benchmarks/run.py ab_join batch"""
    from repro.launch.cache import enable_compile_cache

    names = list(argv if argv is not None else sys.argv[1:]) or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        raise SystemExit(f"unknown benches {unknown}; choose from "
                         f"{sorted(BENCHES)}")
    enable_compile_cache()
    print("name,us_per_call,derived")
    for n in names:
        BENCHES[n]()
    art = os.path.join(os.path.dirname(__file__), "..", "artifacts")
    os.makedirs(art, exist_ok=True)
    with open(os.path.join(art, "bench_results.csv"), "w") as f:
        f.write("name,us_per_call,derived\n" + "\n".join(ROWS) + "\n")
    # machine-readable mirror for CI perf gates and cross-PR comparisons —
    # keyed identically to PR9's table (plus the serving-throughput and
    # scaling-efficiency rows) so trajectory tooling diffs in place
    table = {r.split(",")[0]: float(r.split(",")[1]) for r in ROWS}
    with open(os.path.join(art, "BENCH_PR10.json"), "w") as f:
        json.dump(table, f, indent=1, sort_keys=True)


if __name__ == "__main__":
    main()
