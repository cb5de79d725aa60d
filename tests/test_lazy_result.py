"""Pay-as-you-go harvests: lazy `ProfileResult` sides vs eager requests.

The contract under test (see core/result.py):

  * entry points default to the MINIMAL harvest — lazily-accessed sides
    must come back BITWISE-equal to an eager `harvest="both"` /
    `return_b=True` request on the same backend;
  * where the executed sweep already harvested the side (engine self-join
    split, rowstream B accumulator, kernel halves), first access finishes
    retained state — the `recomputes` counter must stay 0;
  * where the sweep genuinely skipped the side (band-engine AB column
    harvest), first access re-executes the SAME plan two-sided — counted,
    cached, and still bitwise-equal;
  * sides a plan can never produce stay None.
"""

import dataclasses
import os

import numpy as np
import pytest

from test_ab_join import _series

from repro.core import plan as plan_mod
from repro.core.matrix_profile import (
    ab_join, batch_profile, matrix_profile,
)
from repro.core.result import HarvestSpec, ProfileResult, build_result
from repro.core.zstats import compute_cross_stats_host
from repro.kernels import ops


def _lazy(res):
    return object.__getattribute__(res, "_lazy")


def _slot(res, name):
    return object.__getattribute__(res, "_" + name)


def _eq(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- lazy == eager, bitwise, per backend --------------------------------------


def test_engine_self_split_lazy_equals_eager_no_recompute():
    ts = _series(360, seed=1)
    lazy = matrix_profile(ts, 16, 4)
    eager = matrix_profile(ts, 16, 4, harvest="both")
    # minimal build: nothing materialized until touched
    for f in ProfileResult.LAZY_FIELDS:
        assert _slot(lazy, f) is None, f
    for f in ("left_p", "left_i", "right_p", "right_i"):
        _eq(getattr(lazy, f), getattr(eager, f))
    assert _lazy(lazy).recomputes == 0     # engine sweep harvested both sides
    # one access filled the whole split group
    for f in ("left_p", "left_i", "right_p", "right_i"):
        assert _slot(lazy, f) is not None, f
    _eq(np.minimum(np.asarray(lazy.left_p), np.asarray(lazy.right_p)), lazy.p)


def test_engine_self_topk_eager_split_lazy():
    ts = _series(360, seed=2)
    res = matrix_profile(ts, 16, 4, k=4)
    # k>1: the merged profile IS slot 0 of the top-k conversion, so topk
    # arrives materialized at zero extra cost...
    assert _slot(res, "topk_p") is not None
    _eq(res.topk_p[..., 0], res.p)
    # ...while the split stays lazy and still finishes without a re-sweep
    assert _slot(res, "left_p") is None
    eager = matrix_profile(ts, 16, 4, k=4, harvest="both")
    _eq(res.left_p, eager.left_p)
    _eq(res.right_i, eager.right_i)
    assert _lazy(res).recomputes == 0


def test_kernel_self_split_lazy_equals_eager_no_recompute():
    ts = _series(300, seed=3)
    lazy = ops.natsa_matrix_profile(ts, 16, it=64, dt=8)
    eager = ops.natsa_matrix_profile(ts, 16, it=64, dt=8, harvest="both")
    for f in ("left_p", "left_i", "right_p", "right_i"):
        _eq(getattr(lazy, f), getattr(eager, f))
    assert _lazy(lazy).recomputes == 0     # the kernel's halves ARE the split


def test_rowstream_ab_b_side_lazy_equals_eager_no_recompute():
    a, b = _series(300, seed=4), _series(120, seed=5)
    lazy = ab_join(a, b, 12)
    eager = ab_join(a, b, 12, return_b=True)
    assert lazy.backend == "rowstream"
    assert _slot(lazy, "b_p") is None
    _eq(lazy.p, eager.p)
    _eq(lazy.b_p, eager.b_p)
    _eq(lazy.b_i, eager.b_i)
    # the rowstream pass accumulates the B side anyway — no second sweep
    assert _lazy(lazy).recomputes == 0


def test_nonnorm_self_split_lazy_equals_eager_no_recompute():
    ts = _series(300, seed=6, kind="noise")
    lazy = matrix_profile(ts, 16, 4, normalize=False)
    eager = matrix_profile(ts, 16, 4, normalize=False, harvest="both")
    _eq(lazy.left_p, eager.left_p)
    _eq(lazy.right_p, eager.right_p)
    assert _lazy(lazy).recomputes == 0
    _eq(np.minimum(np.asarray(lazy.left_p), np.asarray(lazy.right_p)), lazy.p)


def test_batch_self_split_lazy_equals_eager_no_recompute():
    stack = np.stack([_series(200, seed=10 + i) for i in range(3)])
    lazy = batch_profile(stack, 14, exclusion=3)
    eager = batch_profile(stack, 14, exclusion=3, harvest="both")
    assert lazy.left_p.shape == (3, 200 - 14 + 1)
    _eq(lazy.left_p, eager.left_p)
    _eq(lazy.right_i, eager.right_i)
    assert _lazy(lazy).recomputes == 0


# -- the band engine's genuine skip: recompute fallback -----------------------


def test_band_engine_ab_b_side_recomputes_bitwise_and_caches():
    a, b = _series(300, seed=7), _series(120, seed=8)
    m = 12
    cross = compute_cross_stats_host(a, b, m)
    plan = plan_mod.plan_sweep(m, cross.l_a, cross.l_b, backend="engine")
    res = plan_mod.execute(plan, cross)
    # the minimal plan REALLY skipped the column harvest — that is the
    # entry-layer win this PR reclaims, not deferred bookkeeping
    assert res.dist_b is None and res.index_b is None
    assert not (res.raw or {}).get("b")
    wrapped = build_result(plan, res, cross)
    assert _slot(wrapped, "b_p") is None

    eager_plan = dataclasses.replace(
        plan, harvest=HarvestSpec(sides="both", k=plan.harvest.k))
    eager = plan_mod.execute(eager_plan, cross)
    _eq(wrapped.b_p, eager.dist_b)        # identical plan -> identical bits
    _eq(wrapped.b_i, eager.index_b)
    assert _lazy(wrapped).recomputes == 1
    # materialized on first touch: further access is free
    wrapped.b_p, wrapped.b_i
    assert _lazy(wrapped).recomputes == 1


def test_recompute_disabled_without_stats():
    a, b = _series(200, seed=9), _series(90, seed=10)
    cross = compute_cross_stats_host(a, b, 12)
    plan = plan_mod.plan_sweep(12, cross.l_a, cross.l_b, backend="engine")
    res = plan_mod.execute(plan, cross)
    wrapped = build_result(plan, res, stats=None)
    assert wrapped.b_p is None            # no payload retained -> stays None
    assert _lazy(wrapped).recomputes == 0


# -- sides the plan can never produce stay None -------------------------------


def test_unproducible_sides_stay_none():
    ts = _series(250, seed=11)
    self_res = matrix_profile(ts, 16, 4)            # k=1 self-join
    assert self_res.b_p is None and self_res.b_i is None
    assert self_res.topk_p is None and self_res.b_topk_p is None
    assert self_res.has_split() and not self_res.has_topk()
    ab_res = ab_join(ts, _series(90, seed=12), 16)  # k=1 AB join
    assert ab_res.left_p is None and ab_res.right_p is None
    assert ab_res.topk_p is None
    assert not ab_res.has_split()
    assert _lazy(self_res).recomputes == 0
    assert _lazy(ab_res).recomputes == 0


def test_streaming_query_sides_stay_none():
    from repro.core.streaming import StreamingProfile

    rng = np.random.default_rng(13)
    sp = StreamingProfile(8, 2)
    sp.append(np.cumsum(rng.normal(size=100)))
    res = sp.query(np.cumsum(rng.normal(size=40)))
    assert res.kind == "ab" and res.p is not None
    # no lazy provider on the serving path: untouched sides are just None
    assert res.left_p is None and res.b_p is None and res.topk_p is None


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([os.path.abspath(__file__), "-q"]))
