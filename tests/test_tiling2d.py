"""2-D tiled sweep: row-clamped AB bands + banked column accumulators.

Property tests that (1) clamped AB band sweeps equal the numpy oracle
across skewed shapes, (2) the
row-streamed fast path agrees with both, (3) banked column accumulators —
engine `BankedColState` and the kernel's (n_banks, col_tile) outputs — match
the flat accumulator bit-for-bit for several col_tile sizes including
non-dividing ones, and (4) a long-series kernel self-join runs with a column
block bounded by col_tile.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.matrix_profile import (
    ab_join, ab_join_from_stats, ab_join_rowstream, ab_row_tile,
    matrix_profile, profile_from_stats,
)
from repro.core.ref import ab_join_bruteforce
from repro.core.zstats import compute_cross_stats_host, compute_stats_host
from repro.kernels import natsa_mp, ops

from _hypothesis_compat import given, settings, st


def _series(n, seed=0, kind="walk"):
    rng = np.random.default_rng(seed)
    if kind == "walk":
        return (50.0 + np.cumsum(rng.normal(size=n))).astype(np.float32)
    if kind == "noise":
        return rng.normal(size=n).astype(np.float32)
    t = np.arange(n, dtype=np.float32)
    return (np.sin(2 * np.pi * t / 30)
            + 0.05 * rng.normal(size=n)).astype(np.float32)


# -- row clamp ----------------------------------------------------------------


@pytest.mark.parametrize("na,nb,m,excl,band", [
    (700, 120, 16, 0, 64),     # l_b << l_a: the clamp's home turf
    (120, 700, 16, 0, 64),     # l_a << l_b
    (500, 140, 12, 8, 32),     # skew + exclusion gap (two spans)
    (300, 300, 20, 0, 128),    # square, band wider than l/2
    (200, 90, 8, 0, 256),      # band wider than the whole diagonal space
])
def test_clamped_band_sweep_equals_oracle(na, nb, m, excl, band):
    """The row-clamped sweep computes only the tile rows inside the
    rectangle, yet both profiles equal the brute-force oracle."""
    a = _series(na, seed=na + nb)
    b = _series(nb, seed=abs(na - nb) + 3)
    cross = compute_cross_stats_host(a, b, m)
    sa_c, sb_c = ab_join_from_stats(cross, excl, band, 512, True)
    ref_a, _ = ab_join_bruteforce(jnp.asarray(a), jnp.asarray(b), m,
                                  exclusion=excl)
    ref_b, _ = ab_join_bruteforce(jnp.asarray(b), jnp.asarray(a), m,
                                  exclusion=excl)
    np.testing.assert_allclose(np.asarray(sa_c.to_distance(m)),
                               np.asarray(ref_a), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(sb_c.to_distance(m)),
                               np.asarray(ref_b), rtol=2e-3, atol=2e-3)


@settings(max_examples=12, deadline=None)
@given(st.integers(80, 400), st.integers(80, 400), st.integers(4, 24),
       st.sampled_from([32, 64, 256]))
def test_property_clamped_equals_oracle(na, nb, m, band):
    a = _series(na, seed=na * 7 + nb)
    b = _series(nb, seed=nb * 5 + 1, kind="noise")
    cross = compute_cross_stats_host(a, b, m)
    sa, sb = ab_join_from_stats(cross, 0, band, 512, True)
    ref_a, _ = ab_join_bruteforce(jnp.asarray(a), jnp.asarray(b), m)
    ref_b, _ = ab_join_bruteforce(jnp.asarray(b), jnp.asarray(a), m)
    np.testing.assert_allclose(np.asarray(sa.to_distance(m)),
                               np.asarray(ref_a), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(sb.to_distance(m)),
                               np.asarray(ref_b), rtol=2e-3, atol=2e-3)


def test_ab_row_tile_bounds():
    """The static tile height is the worst case over every band position."""
    la, lb, band = 1000, 70, 64
    li = ab_row_tile(la, lb, band)
    assert li == min(la, lb + band - 1)
    for k0 in range(-(la - 1), lb, 17):
        lo = max(0, -(k0 + band - 1))
        hi = min(la, lb - k0)
        assert hi - lo <= li


def test_nonnorm_clamped_equals_oracle():
    """The nonnorm AB join runs the same row-clamped band tiles; both
    sides equal an f64 pairwise-distance oracle."""
    a = _series(400, seed=1, kind="noise")
    b = _series(90, seed=2, kind="noise")
    m = 10
    res_c = ab_join(a, b, m, normalize=False, return_b=True)
    da_c, db_c = res_c.p, res_c.b_p
    la, lb = 400 - m + 1, 90 - m + 1
    wa = np.stack([a[k:k + m] for k in range(la)]).astype(np.float64)
    wb = np.stack([b[k:k + m] for k in range(lb)]).astype(np.float64)
    d = np.sqrt(((wa[:, None] - wb[None, :]) ** 2).sum(-1))
    np.testing.assert_allclose(np.asarray(da_c), d.min(1), rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(db_c), d.min(0), rtol=2e-3,
                               atol=2e-3)


# -- row-streamed fast path ---------------------------------------------------


@pytest.mark.parametrize("na,nb,m,excl", [
    (600, 150, 16, 0),
    (150, 600, 16, 0),
    (400, 400, 24, 12),        # exclusion (self-join-as-AB shape)
])
def test_rowstream_matches_banded_and_oracle(na, nb, m, excl):
    a = _series(na, seed=na + 11)
    b = _series(nb, seed=nb + 13)
    cross = compute_cross_stats_host(a, b, m)
    st_a, st_b = ab_join_rowstream(cross, excl, 512)
    bd_a, bd_b = ab_join_from_stats(cross, excl, 64, 512, True)
    np.testing.assert_allclose(np.asarray(st_a.to_distance(m)),
                               np.asarray(bd_a.to_distance(m)),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(st_b.to_distance(m)),
                               np.asarray(bd_b.to_distance(m)),
                               rtol=2e-3, atol=2e-3)
    ref_a, _ = ab_join_bruteforce(jnp.asarray(a), jnp.asarray(b), m,
                                  exclusion=excl)
    np.testing.assert_allclose(np.asarray(st_a.to_distance(m)),
                               np.asarray(ref_a), rtol=2e-3, atol=2e-3)
    # indices realize their distances
    ia = np.asarray(st_a.index)
    fin = np.isfinite(np.asarray(st_a.to_distance(m)))
    assert (ia[fin] >= 0).all() and (ia[fin] < cross.l_b).all()


def test_rowstream_reseeds_long_rows():
    """Rows beyond one reseed period trigger the exact-dot reseed rows; the
    result must still match the oracle (drift stays bounded)."""
    a = _series(700, seed=42)
    b = _series(700, seed=43)
    m = 16
    cross = compute_cross_stats_host(a, b, m)
    assert min(cross.l_a, cross.l_b) > 128    # reseed machinery active
    st_a, st_b = ab_join_rowstream(cross, 0, 128)
    ref_a, _ = ab_join_bruteforce(jnp.asarray(a), jnp.asarray(b), m)
    np.testing.assert_allclose(np.asarray(st_a.to_distance(m)),
                               np.asarray(ref_a), rtol=2e-3, atol=2e-3)


def test_ab_join_orients_short_side():
    """ab_join's answer is orientation-invariant: swapping the inputs swaps
    the outputs exactly (the dispatcher streams the short side as rows
    either way)."""
    a = _series(500, seed=3)
    b = _series(120, seed=4)
    m = 12
    r1 = ab_join(a, b, m, return_b=True)
    r2 = ab_join(b, a, m, return_b=True)
    np.testing.assert_array_equal(np.asarray(r1.p), np.asarray(r2.b_p))
    np.testing.assert_array_equal(np.asarray(r1.i), np.asarray(r2.b_i))
    np.testing.assert_array_equal(np.asarray(r1.b_p), np.asarray(r2.p))
    np.testing.assert_array_equal(np.asarray(r1.b_i), np.asarray(r2.i))


# -- banked column accumulators ----------------------------------------------


@pytest.mark.parametrize("col_tile", [413, 449, 512, 1024])
def test_engine_banked_colstate_equals_flat(col_tile):
    """BankedColState accumulation is bit-identical to the flat ColState for
    bank widths at the minimum bound, non-dividing, and comfortable sizes."""
    a = _series(900, seed=5)
    b = _series(300, seed=6)
    m, band = 16, 64
    cross = compute_cross_stats_host(a, b, m)
    assert col_tile > ab_row_tile(cross.l_a, cross.l_b, band) + band
    sa0, sb0 = ab_join_from_stats(cross, 0, band, 512, True, None)
    sa1, sb1 = ab_join_from_stats(cross, 0, band, 512, True, col_tile)
    np.testing.assert_array_equal(np.asarray(sb0.corr), np.asarray(sb1.corr))
    np.testing.assert_array_equal(np.asarray(sb0.index), np.asarray(sb1.index))
    np.testing.assert_array_equal(np.asarray(sa0.corr), np.asarray(sa1.corr))


def test_engine_banked_rejects_too_small_tile():
    from repro.core.matrix_profile import BankedColState
    with pytest.raises(ValueError):
        BankedColState.empty(1000, 64, 64)


@pytest.mark.parametrize("col_tile", [300, 512, 777])
def test_kernel_banked_cols_match_flat(col_tile):
    """Kernel banked accumulators (several col_tile sizes incl. non-dividing)
    reduce to exactly the single-bank flat accumulator."""
    ts = _series(1500, seed=8)
    m = 24
    stats = compute_stats_host(ts, m)
    excl = 6
    it, dt = 128, 8
    df, dg, invn, cov0p, n_rows, n_diags, l = ops._pad_streams(
        stats, it, dt, excl)
    args = (df[:n_rows * it], dg[:n_rows * it], invn[:n_rows * it],
            df, dg, invn, cov0p)
    kw = dict(it=it, dt=dt, k_start=excl, k_end=l, l_i=l, l_j=l, jpad=0)
    c0, i0, f0c, f0i = natsa_mp.rowmax_profile_ab(*args, **kw, col_tile=None)
    c1, i1, bc, bi, stride = natsa_mp.rowmax_profile_ab(
        *args, **kw, col_tile=col_tile, return_banked=True)
    # the banked blocks are bounded by col_tile — the VMEM guarantee
    assert bc.shape[1] == col_tile
    rc, ri = natsa_mp.reduce_col_banks(bc, bi, stride, f0c.shape[0])
    np.testing.assert_array_equal(np.asarray(f0c), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(f0i), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(c0), np.asarray(c1))


def test_kernel_long_series_banked_col_block():
    """n=16384 self-join through the kernel with a banked column accumulator:
    the per-step column block is no larger than col_tile (asserted on the
    banked output), and the merged profile matches the band engine."""
    n, m = 16384, 128
    ts = _series(n, seed=9)
    it, dt = 2048, 64
    col_tile = 4096
    stats = compute_stats_host(ts, m)
    excl = 32
    df, dg, invn, cov0p, n_rows, n_diags, l = ops._pad_streams(
        stats, it, dt, excl)
    c, ix, bc, bi, stride = natsa_mp.rowmax_profile_ab(
        df[:n_rows * it], dg[:n_rows * it], invn[:n_rows * it],
        df, dg, invn, cov0p, it=it, dt=dt, k_start=excl, k_end=l,
        l_i=l, l_j=l, jpad=0, col_tile=col_tile, return_banked=True)
    assert bc.shape[1] == col_tile          # block bound, not O(l)
    assert bc.shape[1] < l                  # strictly smaller than flat
    cc, ci = natsa_mp.reduce_col_banks(bc, bi, stride, max(
        n_rows * it + excl + n_diags * dt, l))
    corr, idx = ops._merge_corr(c[:l], ix[:l], cc[:l], ci[:l])
    merged = profile_from_stats(stats, excl).merged
    np.testing.assert_allclose(np.asarray(corr), np.asarray(merged.corr),
                               rtol=2e-3, atol=2e-3)


def test_auto_col_tile_policy():
    assert ops.auto_col_tile(4096, 256, 16, None) is None       # short: flat
    assert ops.auto_col_tile(100_000, 256, 16, None) == 4096    # long: banked
    assert ops.auto_col_tile(100_000, 2048, 64, None) == 2 * (2048 + 64)
    assert ops.auto_col_tile(100_000, 256, 16, 0) is None       # forced flat
    assert ops.auto_col_tile(4096, 256, 16, 999) == 999         # explicit


def test_natsa_profile_auto_banked_matches_engine():
    """The public kernel entry auto-banks past the threshold and still
    matches the band engine."""
    n, m = 9000, 64
    ts = _series(n, seed=10)
    p_k = ops.natsa_matrix_profile(ts, m, it=1024, dt=32).p
    p_e = matrix_profile(ts, m).p
    np.testing.assert_allclose(np.asarray(p_k), np.asarray(p_e),
                               rtol=2e-3, atol=2e-3)
