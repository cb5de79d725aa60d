"""Missing-data-tolerant profiles: every backend must mask subsequences
touching a NaN/Inf sample (profile inf, index -1) and compute the REMAINING
entries exactly as a numpy oracle that simply skips masked windows.

The engine carries the mask as the `invn < 0` sentinel in the existing
z-stats streams (zstats.compute_stats_host); masking applies only at
harvest time, so the diagonal cumsum recurrence still telescopes exactly
through masked cells — valid entries are unaffected, not merely close.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "src")))

from repro.core.matrix_profile import ab_join, matrix_profile  # noqa: E402
from repro.core.streaming import StreamingProfile              # noqa: E402
from repro.core.zstats import compute_stats_host               # noqa: E402


def _series(n, seed, gaps):
    rng = np.random.default_rng(seed)
    t = np.cumsum(rng.normal(size=n))
    for g, val in gaps:
        t[g] = val
    return t


def _bad_windows(t, m):
    fin = np.isfinite(t)
    nb = np.concatenate([[0], np.cumsum(~fin)])
    return (nb[m:] - nb[:-m]) > 0


def _oracle_self(t, m, excl):
    """Brute-force z-normalized self-join that skips masked windows."""
    l = len(t) - m + 1
    bad = _bad_windows(t, m)
    W = np.lib.stride_tricks.sliding_window_view(t, m).astype(np.float64)
    P = np.full(l, np.inf)
    I = np.full(l, -1, np.int64)
    for a in range(l):
        if bad[a]:
            continue
        wa = W[a] - W[a].mean()
        na = np.linalg.norm(wa)
        for b in range(l):
            if bad[b] or abs(a - b) < excl:
                continue
            wb = W[b] - W[b].mean()
            nb = np.linalg.norm(wb)
            c = 0.0 if (na == 0 or nb == 0) else float(wa @ wb / (na * nb))
            d = np.sqrt(max(2 * m * (1 - min(c, 1.0)), 0.0))
            if d < P[a]:
                P[a], I[a] = d, b
    return P, I, bad


def _oracle_ab(ta, tb, m):
    la, lb = len(ta) - m + 1, len(tb) - m + 1
    bad_a, bad_b = _bad_windows(ta, m), _bad_windows(tb, m)
    Wa = np.lib.stride_tricks.sliding_window_view(ta, m).astype(np.float64)
    Wb = np.lib.stride_tricks.sliding_window_view(tb, m).astype(np.float64)
    P = np.full(la, np.inf)
    I = np.full(la, -1, np.int64)
    for a in range(la):
        if bad_a[a]:
            continue
        wa = Wa[a] - Wa[a].mean()
        na = np.linalg.norm(wa)
        for b in range(lb):
            if bad_b[b]:
                continue
            wb = Wb[b] - Wb[b].mean()
            nb = np.linalg.norm(wb)
            c = 0.0 if (na == 0 or nb == 0) else float(wa @ wb / (na * nb))
            d = np.sqrt(max(2 * m * (1 - min(c, 1.0)), 0.0))
            if d < P[a]:
                P[a], I[a] = d, b
    return P, I, bad_a, bad_b


GAPS = [(37, np.nan), (110, np.inf), (111, -np.inf)]


def _check(p, i, P, I, bad):
    p, i = np.asarray(p, np.float64), np.asarray(i)
    assert np.isinf(p[bad]).all()
    assert (i[bad] == -1).all()
    ok = ~bad & np.isfinite(P)
    np.testing.assert_allclose(p[ok], P[ok], atol=2e-3)
    assert (i[ok] == I[ok]).mean() > 0.98  # ties may differ; values may not


def test_stats_sentinel_matches_window_mask():
    t = _series(300, 0, GAPS)
    stats = compute_stats_host(t, 16)
    bad = _bad_windows(t, 16)
    assert ((np.asarray(stats.invn) < 0) == bad).all()


def test_engine_self_join_masks_and_matches_oracle():
    t = _series(320, 1, GAPS)
    m, excl = 16, 4
    P, I, bad = _oracle_self(t, m, excl)
    r = matrix_profile(t, m, exclusion=excl)
    _check(r.p, r.i, P, I, bad)


def test_engine_masked_neighbors_never_selected():
    t = _series(320, 2, GAPS)
    r = matrix_profile(t, 16)
    i = np.asarray(r.i)
    bad = _bad_windows(t, 16)
    live = i[i >= 0]
    assert not bad[live].any()


def test_engine_topk_excludes_masked():
    t = _series(300, 3, [(60, np.nan)])
    r = matrix_profile(t, 16, k=3)
    bad = _bad_windows(t, 16)
    tki = np.asarray(r.topk_i)
    live = tki[tki >= 0]
    assert not bad[live].any()
    assert np.isinf(np.asarray(r.topk_p)[bad]).all()


def test_ab_join_band_engine_matches_oracle():
    ta = _series(260, 4, [(50, np.nan)])
    tb = _series(5200, 5, [(700, np.inf)])   # tall side: band engine
    m = 16
    P, I, bad_a, _ = _oracle_ab(ta, tb, m)
    r = ab_join(ta, tb, m)
    assert r.backend in ("engine", "rowstream")
    _check(r.p, r.i, P, I, bad_a)


def test_ab_join_rowstream_matches_oracle():
    ta = _series(150, 6, [(40, np.nan)])
    tb = _series(400, 7, [(90, -np.inf)])
    m = 16
    P, I, bad_a, bad_b = _oracle_ab(ta, tb, m)
    r = ab_join(ta, tb, m, return_b=True)
    _check(r.p, r.i, P, I, bad_a)
    Pb, Ib, _, _ = _oracle_ab(tb, ta, m)
    _check(r.b_p, r.b_i, Pb, Ib, bad_b)


def test_kernel_interp_matches_oracle():
    from repro.kernels import ops
    t = _series(280, 8, [(77, np.nan)])
    m, excl = 16, 4
    P, I, bad = _oracle_self(t, m, excl)
    r = ops.natsa_matrix_profile(t, m, exclusion=excl)
    _check(r.p, r.i, P, I, bad)


def test_scheduler_matches_oracle():
    from repro.core.scheduler import AnytimeScheduler
    import jax
    t = _series(300, 9, GAPS)
    m, excl = 16, 4
    P, I, bad = _oracle_self(t, m, excl)
    mesh = jax.make_mesh((1,), ("workers",), (jax.sharding.AxisType.Auto,))
    sch = AnytimeScheduler(t, m, mesh, exclusion=excl, chunks_per_worker=4,
                           band=16)
    sch.run()
    r = sch.result()
    _check(r.p, r.i, P, I, bad)


def test_streaming_append_masks_and_matches_batch():
    t = _series(260, 10, [(80, np.nan)])
    m = 12
    sp = StreamingProfile(m, exclusion=3)
    sp.append(t[:100])
    sp.append(t[100:])
    snap = sp.snapshot()
    d = np.asarray(snap.p, np.float64)
    i = np.asarray(snap.i)
    bad = _bad_windows(t, m)
    assert np.isinf(d[bad]).all()
    assert (i[bad] == -1).all()
    r = matrix_profile(t, m, exclusion=3)
    ok = ~bad & np.isfinite(np.asarray(r.p))
    np.testing.assert_allclose(d[ok], np.asarray(r.p, np.float64)[ok],
                               atol=2e-3)


def test_flat_windows_still_selectable_alongside_gaps():
    """A flat (constant) window is DEGENERATE (corr 0) but not MISSING —
    it must keep a finite profile entry while NaN windows are masked."""
    t = _series(220, 11, [])
    t[30:60] = 5.0          # long flat run
    t[120] = np.nan
    m = 16
    r = matrix_profile(t, m)
    p = np.asarray(r.p)
    bad = _bad_windows(t, m)
    flat = np.array([np.ptp(t[j:j + m]) == 0 and np.isfinite(t[j:j + m]).all()
                     for j in range(len(t) - m + 1)])
    assert np.isinf(p[bad]).all()
    assert np.isfinite(p[flat]).all()


def test_all_nan_series_yields_all_masked_profile():
    t = np.full(100, np.nan)
    r = matrix_profile(t, 8)
    assert np.isinf(np.asarray(r.p)).all()
    assert (np.asarray(r.i) == -1).all()


def test_nonnorm_entry_rejects_nonfinite():
    t = _series(120, 12, [(30, np.nan)])
    with pytest.raises(ValueError, match="non-finite"):
        matrix_profile(t, 8, normalize=False)


if __name__ == "__main__":
    sys.exit(pytest.main([os.path.abspath(__file__), "-q"]))
