"""The NATSA kernel skips the grid steps whose tile holds no valid cell.

`natsa_mp.live_diag_tiles` decides, per row tile, which diag tiles hold a
cell the kernel's geometric mask keeps; the kernel computes only those, and
the plan's counters (`plan.tile_cells`, `plan.skipped_tiles`) count them.
Here the predicate is checked against a brute-force enumeration of cells,
and every launch shape with dead tiles against the pure-jnp oracles of
`kernels/ref.py` (interpret mode), at `test_kernel_matches_oracle`'s
tolerances.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import plan as plan_mod
from repro.core.zstats import compute_cross_stats_host, compute_stats_host
from repro.kernels import natsa_mp, ops
from repro.kernels.ref import rowmax_profile_ab_ref, rowmax_profile_ref

TOL = dict(rtol=1e-4, atol=1e-4)


def _walk(n, seed, gaps=()):
    x = np.cumsum(np.random.default_rng(seed).normal(size=n))
    for g in gaps:
        x[g] = np.nan
    return x


def _brute_live(n_rows, n_diags, *, it, dt, k_start, k_end, l_i, l_j):
    """(n_rows, n_diags) bool: tile holds a cell of the kernel's mask."""
    i = np.arange(n_rows * it)[None, :]
    k = k_start + np.arange(n_diags * dt)[:, None]
    j = i + k
    valid = (j >= 0) & (j < l_j) & (i < l_i) & (k < k_end)
    return valid.reshape(n_diags, dt, n_rows, it).any(axis=(1, 3)).T


def _plans(it, dt):
    """Kernel plans over small self and AB geometries, exclusions included:
    both tile edges dividing and not dividing the sizes."""
    for l in (1, 2, 7, 33, 100):
        for excl in (0, 1, 5, 40):
            yield plan_mod.plan_sweep(4, l, exclusion=excl, backend="kernel",
                                      it=it, dt=dt)
    for la in (1, 9, 50, 130):
        for lb in (1, 9, 50, 130):
            for excl in (0, 3, 20):
                yield plan_mod.plan_sweep(4, la, lb, exclusion=excl,
                                          backend="kernel", it=it, dt=dt)


@pytest.mark.parametrize("it,dt", [(8, 4), (16, 8), (32, 2), (128, 8)])
def test_live_tiles_match_bruteforce(it, dt):
    for plan in _plans(it, dt):
        live = skipped = 0
        for n_rows, n_diags, g in plan_mod._kernel_launches(plan):
            want = _brute_live(n_rows, n_diags, **g)
            d_lo, d_end = natsa_mp.live_diag_tiles(np.arange(n_rows),
                                                   n_diags=n_diags, **g)
            d = np.arange(n_diags)[None, :]
            got = (d >= d_lo[:, None]) & (d < d_end[:, None])
            np.testing.assert_array_equal(got, want, err_msg=str(g))
            assert natsa_mp.live_tiles(n_rows, n_diags, **g) == want.sum()
            live += int(want.sum())
            skipped += int((~want).sum())
        assert plan_mod.tile_cells(plan) == live * it * dt
        assert plan_mod.skipped_tiles(plan) == skipped


def _check_side(corr, idx, corr_ref, idx_ref):
    corr, corr_ref = np.asarray(corr), np.asarray(corr_ref)
    np.testing.assert_allclose(corr, corr_ref, **TOL)
    # argmax ties can differ only where correlations are ~equal
    mism = np.asarray(idx) != np.asarray(idx_ref)
    assert np.abs(corr[mism] - corr_ref[mism]).max(initial=0) < 1e-4


@pytest.mark.parametrize("n,m,excl,it,dt,col_tile,gaps,dtype", [
    # l = 385 = 3*128 + 1: the whole last row tile is dead, d == 0 too
    (400, 16, 4, 128, 8, None, (), jnp.float32),
    (400, 16, 1, 128, 8, None, (), jnp.float32),
    (400, 16, 37, 128, 8, None, (), jnp.float32),
    (400, 16, 130, 64, 16, None, (), jnp.float32),
    # banks of stride 128: swaps on both sides of each row's dead stretch
    (330, 10, 3, 64, 8, 200, (), jnp.float32),
    # missing-data sentinel rows inside live and dead tiles
    (400, 16, 4, 64, 8, None, (30, 250, 390), jnp.float32),
    # bf16 streams, compared with the oracle on the same rounded streams
    (400, 16, 4, 64, 8, None, (), jnp.bfloat16),
])
def test_self_kernel_with_dead_tiles_matches_oracle(n, m, excl, it, dt,
                                                    col_tile, gaps, dtype):
    stats = compute_stats_host(_walk(n, n + m + excl, gaps), m,
                               out_dtype=dtype)
    plan = plan_mod.plan_sweep(m, stats.n_subsequences, exclusion=excl,
                               backend="kernel", it=it, dt=dt)
    assert plan_mod.skipped_tiles(plan) > 0
    ck, ik, cck, cik = ops.rowmax_from_stats(stats, excl=excl, it=it, dt=dt,
                                             col_tile=col_tile)
    df, dg, invn, cov0p, _, _, l = ops._pad_streams(stats, it, dt, excl)
    f32 = [x.astype(jnp.float32) for x in (df, dg, invn)]
    cr, ir, ccr, cir = rowmax_profile_ref(*f32, cov0p, excl=excl, l=l)
    _check_side(ck, ik, cr[:l], ir[:l])
    _check_side(cck, cik, ccr[:l], cir[:l])


def _ab_ref(cross, s0, s1):
    """The oracle's one-span harvest as whole (l_a,) and (l_b,) profiles."""
    la, lb = cross.l_a, cross.l_b
    rw, ri, w, wi, i0 = rowmax_profile_ab_ref(cross, s0, s1)
    i0 = int(i0)
    corr = np.full(la, natsa_mp.NEG, np.float32)
    idx = np.full(la, -1, np.int32)
    n = min(len(rw), la - i0)
    corr[i0:i0 + n], idx[i0:i0 + n] = np.asarray(rw)[:n], np.asarray(ri)[:n]
    t = np.arange(lb) - i0 - s0                  # window entry of column j
    inside = (t >= 0) & (t < len(w))
    corr_b = np.full(lb, natsa_mp.NEG, np.float32)
    idx_b = np.full(lb, -1, np.int32)
    corr_b[inside] = np.asarray(w)[t[inside]]
    idx_b[inside] = np.asarray(wi)[t[inside]]
    return corr, idx, corr_b, idx_b


@pytest.mark.parametrize("na,nb,m,excl,it,dt", [
    # exclusion 0, one launch with prefix and suffix dead tiles
    (300, 180, 16, 0, 64, 8),
    (180, 300, 16, 0, 64, 8),
    # an exclusion band: two launches, the negative span's last diag tile
    # cut by k_end
    (260, 220, 12, 5, 32, 8),
])
def test_ab_kernel_with_dead_tiles_matches_oracle(na, nb, m, excl, it, dt):
    cross = compute_cross_stats_host(_walk(na, na), _walk(nb, nb + 1), m)
    la, lb = cross.l_a, cross.l_b
    spans = plan_mod._ab_spans(la, lb, excl)
    assert len(spans) == (2 if excl else 1)
    prefix = suffix = False
    for s0, s1 in spans:
        (df_i, dg_i, invn_i, df_j, dg_j, invn_j, cov0p,
         n_rows, n_diags, jpad) = ops._pad_streams_ab(cross, it, dt, s0, s1)
        g = dict(it=it, dt=dt, k_start=s0, k_end=s1, l_i=la, l_j=lb)
        d_lo, d_end = natsa_mp.live_diag_tiles(np.arange(n_rows),
                                               n_diags=n_diags, **g)
        prefix |= bool((d_lo > 0).any())
        suffix |= bool((d_end < n_diags).any())
        c, ix, cc, ci = natsa_mp.rowmax_profile_ab(
            df_i, dg_i, invn_i, df_j, dg_j, invn_j, cov0p, jpad=jpad, **g)
        corr, idx, corr_b, idx_b = _ab_ref(cross, s0, s1)
        _check_side(c[:la], ix[:la], corr, idx)
        _check_side(cc[jpad:jpad + lb], ci[jpad:jpad + lb], corr_b, idx_b)
    assert prefix and suffix
