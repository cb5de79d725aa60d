"""SweepPlan planner + executor seam tests.

Two guarantees:
  1. EQUIVALENCE — every public entry point now builds a `SweepPlan` and runs
     it through `plan.execute`; the results must be BITWISE-equal to calling
     the low-level sweeps directly with the same knobs (the pre-refactor
     entry bodies), and oracle-correct (fixtures reused from test_ab_join).
  2. PLANNER CHOICES — `plan_sweep`'s backend / orientation / col_tile
     decisions are pinned table-driven across the shapes that motivated them
     (skewed a4096/b512 AB joins, the n=16384 banked-column regime, batch).
"""

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, st
from test_ab_join import _series, oracle_ab

from repro.core import plan as plan_mod
from repro.core.matrix_profile import (
    DEFAULT_BAND, DEFAULT_RESEED, ab_join, ab_join_from_stats,
    ab_join_rowstream, batch_ab_join, batch_profile, matrix_profile,
    nonnorm_profile_from_ts, nonnorm_to_distance, profile_from_stats,
)
from repro.core.zstats import (
    compute_cross_stats_host, compute_stats_host, corr_to_dist,
)
from repro.kernels import ops


# -- 1. plan-built results == direct low-level calls (bitwise) ----------------


def test_matrix_profile_equals_direct_engine_call():
    ts = _series(400, seed=1)
    m, excl = 16, 4
    res = matrix_profile(ts, m, excl)
    stats = compute_stats_host(ts, m)
    split = profile_from_stats(stats, excl, DEFAULT_BAND, DEFAULT_RESEED)
    np.testing.assert_array_equal(np.asarray(res.p),
                                  np.asarray(split.merged.to_distance(m)))
    np.testing.assert_array_equal(np.asarray(res.i),
                                  np.asarray(split.merged.index))
    # the entry's split sides are the core's row/column harvests verbatim
    np.testing.assert_array_equal(np.asarray(res.right_p),
                                  np.asarray(split.right.to_distance(m)))
    np.testing.assert_array_equal(np.asarray(res.left_p),
                                  np.asarray(split.left.to_distance(m)))
    np.testing.assert_array_equal(
        np.minimum(np.asarray(res.left_p), np.asarray(res.right_p)),
        np.asarray(res.p))


def test_matrix_profile_nonnorm_equals_direct_engine_call():
    ts = _series(300, seed=2, kind="noise")
    m, excl = 16, 4
    res = matrix_profile(jnp.asarray(ts), m, excl, normalize=False)
    split = nonnorm_profile_from_ts(jnp.asarray(ts, jnp.float32), m, excl)
    np.testing.assert_array_equal(np.asarray(res.p),
                                  np.asarray(nonnorm_to_distance(split.merged)))
    np.testing.assert_array_equal(np.asarray(res.i),
                                  np.asarray(split.merged.index))
    np.testing.assert_array_equal(
        np.minimum(np.asarray(res.left_p), np.asarray(res.right_p)),
        np.asarray(res.p))


def test_ab_join_equals_direct_rowstream_call():
    """Skewed shape below AB_ROWSTREAM_MAX_ROWS: the planner must pick the
    row-streamed scan with the short side on rows, bit-for-bit what the
    pre-refactor dispatch produced."""
    a = _series(500, seed=3)
    b = _series(120, seed=4)
    m = 12
    res = ab_join(a, b, m, return_b=True)
    cross = compute_cross_stats_host(b, a, m)        # short side on rows
    sb, sa = ab_join_rowstream(cross, 0, DEFAULT_RESEED)
    np.testing.assert_array_equal(np.asarray(res.p),
                                  np.asarray(sa.to_distance(m)))
    np.testing.assert_array_equal(np.asarray(res.i), np.asarray(sa.index))
    np.testing.assert_array_equal(np.asarray(res.b_p),
                                  np.asarray(sb.to_distance(m)))
    np.testing.assert_array_equal(np.asarray(res.b_i), np.asarray(sb.index))


def test_engine_backend_plan_equals_direct_banded_call():
    """Forcing the band-diagonal engine through a plan == ab_join_from_stats
    direct (the path huge near-square joins and the scheduler use)."""
    a = _series(420, seed=5)
    b = _series(200, seed=6)
    m = 14
    cross = compute_cross_stats_host(a, b, m)
    plan = plan_mod.plan_sweep(m, 420 - m + 1, 200 - m + 1, backend="engine",
                               harvest="both")
    res = plan_mod.execute(plan, cross)
    sa, sb = ab_join_from_stats(cross, 0, DEFAULT_BAND, DEFAULT_RESEED,
                                True, None)
    np.testing.assert_array_equal(np.asarray(res.dist),
                                  np.asarray(sa.to_distance(m)))
    np.testing.assert_array_equal(np.asarray(res.dist_b),
                                  np.asarray(sb.to_distance(m)))
    np.testing.assert_array_equal(np.asarray(res.index_b),
                                  np.asarray(sb.index))


def test_batch_entries_equal_direct_vmap():
    import jax

    stack = np.stack([_series(260, seed=i, kind=k)
                      for i, k in enumerate(["walk", "noise", "sine"])])
    m, excl = 14, 3
    bres = batch_profile(stack, m, exclusion=excl)
    stats = [compute_stats_host(s, m) for s in stack]
    st_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *stats)
    split = jax.vmap(
        lambda s: profile_from_stats(s, excl, DEFAULT_BAND, DEFAULT_RESEED)
    )(st_stack)
    np.testing.assert_array_equal(np.asarray(bres.p),
                                  np.asarray(split.merged.to_distance(m)))
    np.testing.assert_array_equal(np.asarray(bres.i),
                                  np.asarray(split.merged.index))

    b = np.stack([_series(90, seed=10 + i, kind="sine") for i in range(3)])
    abres = batch_ab_join(stack, b, m)
    crosses = [compute_cross_stats_host(ra, rb, m)
               for ra, rb in zip(stack, b)]
    c_stack = jax.tree.map(lambda *xs: jnp.stack(xs), *crosses)
    sa, _ = jax.vmap(
        lambda c: ab_join_from_stats(c, 0, DEFAULT_BAND, DEFAULT_RESEED,
                                     False, None))(c_stack)
    np.testing.assert_array_equal(np.asarray(abres.p),
                                  np.asarray(sa.to_distance(m)))
    np.testing.assert_array_equal(np.asarray(abres.i), np.asarray(sa.index))


def test_kernel_entries_equal_direct_kernel_calls():
    ts = _series(360, seed=7)
    m, excl = 16, 4
    res = ops.natsa_matrix_profile(ts, m, exclusion=excl, it=128, dt=8)
    stats = compute_stats_host(ts, m)
    cr, ir, cc, ic = ops.rowmax_from_stats(stats, excl=excl, it=128, dt=8)
    corr, idx = ops._merge_corr(cr, ir, cc, ic)
    dist = jnp.where(corr <= ops.NEG + 1e-6, jnp.inf,
                     corr_to_dist(jnp.clip(corr, -1.0, 1.0), m))
    np.testing.assert_array_equal(np.asarray(res.p), np.asarray(dist))
    np.testing.assert_array_equal(np.asarray(res.i), np.asarray(idx))
    # the kernel's row/column halves surface as the right/left split
    np.testing.assert_array_equal(np.asarray(res.right_i), np.asarray(ir))
    np.testing.assert_array_equal(np.asarray(res.left_i), np.asarray(ic))

    b = _series(140, seed=8, kind="sine")
    abres = ops.natsa_ab_join(ts, b, m, it=64, dt=8, return_b=True)
    cross = compute_cross_stats_host(b, ts, m)       # short side on rows
    cb, ixb, ca, ixa = ops.ab_rowmax_from_stats(cross, exclusion=0,
                                                it=64, dt=8)

    def d(c):
        return jnp.where(c <= ops.NEG + 1e-6, jnp.inf,
                         corr_to_dist(jnp.clip(c, -1.0, 1.0), m))

    np.testing.assert_array_equal(np.asarray(abres.p), np.asarray(d(ca)))
    np.testing.assert_array_equal(np.asarray(abres.i), np.asarray(ixa))
    np.testing.assert_array_equal(np.asarray(abres.b_p), np.asarray(d(cb)))
    np.testing.assert_array_equal(np.asarray(abres.b_i), np.asarray(ixb))


def test_streaming_query_equals_direct_rowstream():
    from repro.core.streaming import StreamingProfile

    rng = np.random.default_rng(9)
    ref = np.cumsum(rng.normal(size=240))
    q = np.cumsum(rng.normal(size=70))
    m = 12
    sp = StreamingProfile(m, 3)
    sp.append(ref)
    qres = sp.query(q)
    cross = compute_cross_stats_host(q, ref, m)      # query side is shorter
    sa, _ = ab_join_rowstream(cross, 0, DEFAULT_RESEED)
    np.testing.assert_array_equal(qres.p,
                                  np.asarray(sa.to_distance(m), np.float64))
    np.testing.assert_array_equal(qres.i, np.asarray(sa.index, np.int64))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([8, 16, 25]),
       st.sampled_from(["walk", "noise", "sine", "flat"]))
def test_property_entry_equals_plan_execute_and_oracle(seed, m, kind):
    """For random shapes/kinds: the public entry == an explicitly planned
    execute (same plan the entry builds) == the numpy oracle."""
    na, nb = 180, 110
    a = _series(na, seed=seed, kind=kind)
    b = _series(nb, seed=seed + 1, kind=kind)
    entry = ab_join(a, b, m)
    plan = plan_mod.plan_sweep(m, na - m + 1, nb - m + 1, harvest="row")
    stats = (compute_cross_stats_host(b, a, m) if plan.swap_ab
             else compute_cross_stats_host(a, b, m))
    res = plan_mod.execute(plan, stats)
    np.testing.assert_array_equal(np.asarray(entry.p), np.asarray(res.dist))
    np.testing.assert_array_equal(np.asarray(entry.i), np.asarray(res.index))
    p_ref, _ = oracle_ab(a, b, m)
    np.testing.assert_allclose(np.asarray(entry.p), p_ref,
                               rtol=2e-3, atol=2e-3)


# -- 2. planner choices, table-driven -----------------------------------------


@pytest.mark.parametrize("kwargs,expect", [
    # skewed a4096/b512 AB join (l = n - m + 1, m = 128): rowstream, short
    # side (B) onto rows
    (dict(window=128, l_a=3969, l_b=385),
     dict(backend="rowstream", swap_ab=True, exclusion=0)),
    # mirrored skew: still rowstream, no swap needed
    (dict(window=128, l_a=385, l_b=3969),
     dict(backend="rowstream", swap_ab=False)),
    # huge near-square rectangle: band engine (row clamp handles orientation)
    (dict(window=128, l_a=8000, l_b=6000),
     dict(backend="engine", swap_ab=False)),
    # batch pins the engine even on rowstream-eligible skew (vmap path)
    (dict(window=64, l_a=961, l_b=449, batch=8),
     dict(backend="engine", batch=8)),
    # nonnorm is engine-only
    (dict(window=16, l_a=391, l_b=81, normalize=False),
     dict(backend="engine", swap_ab=False)),
    # the benchmark cells' plans, pinned so a planner change cannot move a
    # cell off its measured route: power-512k self-join on the kernel ...
    (dict(window=1024, l_a=523265, exclusion=256, backend="kernel"),
     dict(backend="kernel", col_tile=4096)),
    # ... power-512k ab-query (4096-sample query vs the recording): rowstream
    (dict(window=1024, l_a=3073, l_b=523265),
     dict(backend="rowstream", swap_ab=False, exclusion=0)),
    # ... seismology-64k through the default entry: on the CPU (the
    # kernel would run in the interpreter) the band engine
    (dict(window=256, l_a=65281),
     dict(backend="engine", exclusion=64, band=256, kind="self")),
    # ... and where the compiled kernel runs (pinned here by
    # interpret=False; on a TPU the default resolves so): the kernel
    (dict(window=256, l_a=65281, interpret=False),
     dict(backend="kernel", exclusion=64, col_tile=4096, kind="self")),
    # every other self-join keeps the band engine even so: top-k, batched,
    # 16-bit streams, f64 accumulation, nonnorm, a tuned band or reseed
    (dict(window=256, l_a=65281, interpret=False, k=4),
     dict(backend="engine", col_tile=None)),
    (dict(window=256, l_a=65281, interpret=False, batch=8),
     dict(backend="engine", batch=8)),
    (dict(window=256, l_a=65281, interpret=False, precision="bf16"),
     dict(backend="engine")),
    (dict(window=256, l_a=65281, interpret=False, precision="f64"),
     dict(backend="engine")),
    (dict(window=256, l_a=65281, interpret=False, normalize=False),
     dict(backend="engine")),
    (dict(window=256, l_a=65281, interpret=False, band=128),
     dict(backend="engine", band=128)),
    (dict(window=256, l_a=65281, interpret=False, reseed_every=None),
     dict(backend="engine", reseed_every=None)),
    # no diagonal past the exclusion zone: nothing for the kernel to sweep
    (dict(window=16, l_a=4, exclusion=4, interpret=False),
     dict(backend="engine")),
    # self-join defaults: engine, default exclusion, default band
    (dict(window=128, l_a=16257),
     dict(backend="engine", exclusion=32, band=DEFAULT_BAND, kind="self")),
    # n=16384 self-join through the kernel: column accumulator BANKED at
    # plan time (auto_col_tile policy pinned into the plan)
    (dict(window=128, l_a=16257, backend="kernel"),
     dict(backend="kernel", col_tile=4096)),
    (dict(window=128, l_a=16257, backend="kernel", it=2048, dt=64),
     dict(col_tile=2 * (2048 + 64))),
    # short self-join through the kernel: flat single bank (pinned as 0)
    (dict(window=16, l_a=500, backend="kernel"),
     dict(col_tile=0)),
    # kernel AB: orientation chosen at plan time, banking per span in ops
    (dict(window=128, l_a=3969, l_b=385, backend="kernel"),
     dict(backend="kernel", swap_ab=True, col_tile=None)),
    # top-k: the kernel's VMEM accumulators are k=1-only — a kernel request
    # with k > 1 plans the band-engine fallback (and skips kernel banking)
    (dict(window=128, l_a=16257, backend="kernel", k=4),
     dict(backend="engine", col_tile=None)),
    # the fallback must also DROP an explicit kernel banking knob — a tuned
    # kernel call (it/dt/col_tile) with k > 1 still falls back, not raises
    (dict(window=128, l_a=16257, backend="kernel", k=4,
          it=2048, dt=64, col_tile=4096),
     dict(backend="engine", col_tile=None)),
    # top-k rowstream-eligible skew still takes rowstream (k fits)
    (dict(window=128, l_a=3969, l_b=385, k=4),
     dict(backend="rowstream", swap_ab=True)),
    # k wider than the short side: rowstream ineligible, engine instead
    (dict(window=16, l_a=400, l_b=20, k=24),
     dict(backend="engine")),
])
def test_plan_sweep_choices(kwargs, expect):
    kwargs = dict(kwargs)
    window, l_a = kwargs.pop("window"), kwargs.pop("l_a")
    l_b = kwargs.pop("l_b", None)
    plan = plan_mod.plan_sweep(window, l_a, l_b, **kwargs)
    for field, want in expect.items():
        assert getattr(plan, field) == want, (field, getattr(plan, field))


def test_default_self_join_on_a_tpu_plans_the_compiled_kernel(monkeypatch):
    """With JAX on a TPU, the unpinned default resolves to the compiled
    kernel (`resolve_interpret` and the planner read the same backend);
    an explicit interpret=True keeps the band engine there."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    plan = plan_mod.plan_sweep(256, 65281)
    assert (plan.backend, plan.interpret, plan.col_tile) == ("kernel", False,
                                                            4096)
    assert plan_mod.plan_sweep(256, 65281, interpret=True).backend == "engine"
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert plan_mod.plan_sweep(256, 65281).backend == "engine"


def test_default_entry_kernel_route_equals_engine_route():
    """The default self-join's two routes — the kernel (here in the
    interpreter) and the band engine — give the same profile."""
    from repro.core.result import build_result
    from repro.core.zstats import dist_to_corr

    ts = _series(600, seed=77, kind="walk")
    m = 20
    l = ts.size - m + 1
    stats = compute_stats_host(ts, m)
    routes = {}
    for backend in ("engine", "kernel"):
        plan = plan_mod.plan_sweep(m, l, backend=backend, interpret=True)
        routes[backend] = build_result(plan, plan_mod.execute(plan, stats),
                                       stats)
    eng, ker = routes["engine"], routes["kernel"]
    assert (eng.backend, ker.backend) == ("engine", "kernel")
    assert eng.exclusion == ker.exclusion
    np.testing.assert_allclose(np.asarray(ker.p), np.asarray(eng.p),
                               atol=1e-3)
    # on the CPU the entry itself takes the engine route, bitwise
    np.testing.assert_array_equal(np.asarray(matrix_profile(ts, m).p),
                                  np.asarray(eng.p))
    ce, ck = (np.asarray(dist_to_corr(jnp.asarray(r.p), m))
              for r in (eng, ker))
    # argmax ties can differ only where correlations are ~equal
    mism = np.asarray(ker.i) != np.asarray(eng.i)
    assert np.abs(ck[mism] - ce[mism]).max(initial=0) < 5e-4


def test_plan_geometry_spans():
    p = plan_mod.plan_sweep(16, 300, exclusion=4)
    assert (p.k_min, p.k_max) == (4, 300)
    q = plan_mod.plan_sweep(16, 300, 100)
    assert (q.k_min, q.k_max) == (-299, 100)


def test_scheduler_builds_distributed_plan():
    from repro.core.scheduler import AnytimeScheduler
    from repro.launch.mesh import make_worker_mesh

    ts = _series(300, seed=11)
    sch = AnytimeScheduler(ts, 16, make_worker_mesh(1), chunks_per_worker=2,
                           band=16, exclusion=4)
    p = sch.sweep_plan
    assert p.backend == "distributed" and p.kind == "self"
    assert p.band == 16 and p.exclusion == 4 and p.n_bands == sch.n_bands
    ab = AnytimeScheduler(ts, 16, make_worker_mesh(1), ts_b=_series(150, 12),
                          chunks_per_worker=2, band=16)
    assert ab.sweep_plan.kind == "ab" and ab.sweep_plan.l_b == 150 - 16 + 1


def test_streaming_query_cache_and_plan_reuse():
    """Satellite: the corpus cache must key on the append GENERATION and
    the distance mode (a `normalize` flip must not serve stale centered
    windows; a content change at the same length must miss — see
    test_streaming_ref_cache_keyed_by_generation) and must memoize the
    plan per query shape."""
    from repro.core.streaming import StreamingProfile

    rng = np.random.default_rng(13)
    sp = StreamingProfile(8, 2)
    sp.append(rng.normal(size=80))
    gen = sp._gen
    q = rng.normal(size=30)
    sp.query(q)
    side = sp._refs._sides[(gen, True)]
    assert side.normalize is True
    assert (side.l, True, 23, 1, None) in sp._refs._plans
    sp.query(q)
    assert sp._refs._sides[(gen, True)] is side      # side + plan reused
    d_norm = sp.query(q).p
    sp.normalize = False                 # mode flip must miss the z-norm key
    d_raw = sp.query(q).p
    assert sp._refs._sides[(gen, False)].normalize is False
    assert not np.allclose(d_norm, d_raw)    # raw vs z-norm really differ
    sp.normalize = True
    np.testing.assert_array_equal(sp.query(q).p, d_norm)
    assert sp._refs._sides[(gen, True)] is side      # LRU kept both modes


# -- guard rails --------------------------------------------------------------


def test_planner_and_executor_reject_invalid_combinations():
    with pytest.raises(ValueError, match="backend"):
        plan_mod.plan_sweep(16, 100, backend="warp")
    with pytest.raises(ValueError, match="z-normalized"):
        plan_mod.plan_sweep(16, 100, 50, normalize=False, backend="kernel")
    with pytest.raises(ValueError, match="rectangle"):
        plan_mod.plan_sweep(16, 100, backend="rowstream")
    with pytest.raises(ValueError, match="batch"):
        plan_mod.plan_sweep(16, 100, 50, batch=4, backend="kernel")
    with pytest.raises(ValueError, match="z-normalized only"):
        plan_mod.plan_sweep(16, 100, batch=4, normalize=False)
    with pytest.raises(ValueError, match="cross_stats_for"):
        plan_mod.cross_stats_for(plan_mod.plan_sweep(16, 100), None, None)
    ts = _series(100, seed=14)
    stats = compute_stats_host(ts, 16)
    with pytest.raises(TypeError, match="CrossStats"):
        plan_mod.execute(plan_mod.plan_sweep(16, 50, 50), stats)
    dist_plan = plan_mod.plan_sweep(16, 85, backend="distributed")
    with pytest.raises(ValueError, match="round"):
        plan_mod.execute(dist_plan, stats)
    with pytest.raises(ValueError, match="n_bands"):
        plan_mod.round_executor(dist_plan, mesh=None)
    with pytest.raises(ValueError, match="distributed"):
        plan_mod.round_executor(plan_mod.plan_sweep(16, 85), mesh=None)
    assert dataclasses.replace(dist_plan, n_bands=4).n_bands == 4
    # top-k gates
    with pytest.raises(ValueError, match="z-normalized"):
        plan_mod.plan_sweep(16, 100, normalize=False, k=4)
    with pytest.raises(ValueError, match="band"):
        plan_mod.plan_sweep(16, 5000, k=300, band=256)
    with pytest.raises(ValueError, match="rowstream"):
        plan_mod.plan_sweep(16, 400, 20, backend="rowstream", k=24)
    with pytest.raises(ValueError, match="col_tile"):
        plan_mod.plan_sweep(16, 5000, k=4, col_tile=512)
    with pytest.raises(ValueError, match="k"):
        plan_mod.plan_sweep(16, 100, k=0)
    # exclusion=0 self-join top-k would double-count the diagonal self-match
    with pytest.raises(ValueError, match="exclusion"):
        plan_mod.plan_sweep(16, 100, exclusion=0, k=4)
