"""Matrix-profile engine vs brute-force oracle + anytime/property tests."""

import numpy as np
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.matrix_profile import (
    ProfileState, matrix_profile, profile_from_stats, top_discords, top_motif,
)
from repro.core.ref import matrix_profile_bruteforce
from repro.core.zstats import compute_stats_host, corr_to_dist, dist_to_corr


def _series(n, seed=0, kind="walk"):
    rng = np.random.default_rng(seed)
    if kind == "walk":
        return np.cumsum(rng.normal(size=n)).astype(np.float32)
    if kind == "noise":
        return rng.normal(size=n).astype(np.float32)
    if kind == "sine":
        t = np.arange(n, dtype=np.float32)
        return (np.sin(2 * np.pi * t / 50) + 0.05 * rng.normal(size=n)).astype(np.float32)
    raise ValueError(kind)


@pytest.mark.parametrize("n,m,kind", [
    (300, 16, "walk"),
    (500, 8, "noise"),
    (400, 32, "sine"),
    (257, 10, "walk"),      # sizes not aligned to band
    (2048, 64, "walk"),     # seismology-64k's shape, 32x shorter
])
def test_engine_matches_bruteforce(n, m, kind):
    ts = _series(n, seed=n + m, kind=kind)
    res = matrix_profile(ts, m)
    p, i = res.p, res.i
    p_ref, i_ref = matrix_profile_bruteforce(jnp.asarray(ts), m)
    np.testing.assert_allclose(np.asarray(p), np.asarray(p_ref),
                               rtol=2e-3, atol=2e-3)
    # indices may differ on near-ties; distances at chosen indices must match
    assert (np.asarray(i) >= 0).all()


def test_planted_motif_found():
    rng = np.random.default_rng(42)
    ts = rng.normal(size=800).astype(np.float32)
    # non-periodic chirp so partial/phase-shifted overlaps can't compete
    t = np.linspace(0, 1, 50)
    pattern = (np.sin(2 * np.pi * (2 * t + 6 * t ** 2)) * 4).astype(np.float32)
    ts[100:150] += pattern
    ts[600:650] += pattern
    res = matrix_profile(ts, 50)
    a, b = top_motif(res.p, res.i)
    pair = sorted([int(a), int(b)])
    assert abs(pair[0] - 100) <= 3 and abs(pair[1] - 600) <= 3, pair


def test_planted_discord_found():
    ts = _series(1200, seed=9, kind="sine")
    ts[700:730] += np.linspace(0, 8, 30).astype(np.float32)  # anomaly
    res = matrix_profile(ts, 40)
    excl = 10
    picks = np.asarray(top_discords(res.p, res.i, 1, excl))
    assert abs(int(picks[0]) - 700) <= 40


def test_exclusion_zone_respected():
    ts = _series(300, seed=3)
    m = 16
    i = matrix_profile(ts, m).i
    pos = np.arange(len(np.asarray(i)))
    assert (np.abs(np.asarray(i) - pos) >= max(1, -(-m // 4))).all()


def test_band_size_invariance():
    ts = _series(350, seed=5)
    p1 = matrix_profile(ts, 20, None, 16).p
    p2 = matrix_profile(ts, 20, None, 64).p
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), atol=1e-4)


def test_reseed_tightens_or_keeps_error():
    ts = _series(2000, seed=11)
    p_ref, _ = matrix_profile_bruteforce(jnp.asarray(ts), 32)
    p_rs = matrix_profile(ts, 32, None, 64, 256).p
    err_rs = np.abs(np.asarray(p_rs) - np.asarray(p_ref)).max()
    assert err_rs < 1e-3


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from([8, 16, 25]),
       st.sampled_from(["walk", "noise", "sine"]))
def test_property_profile_valid(seed, m, kind):
    """Profile entries are realizable distances: each (i, index[i]) pair's
    true distance equals profile[i]; exclusion respected; symmetry of the
    best pair holds (profile[i] <= dist(i, j) for any sampled j)."""
    n = 260
    ts = _series(n, seed=seed, kind=kind)
    res = matrix_profile(ts, m)
    p, idx = np.asarray(res.p), np.asarray(res.i)
    l = n - m + 1
    rng = np.random.default_rng(seed)
    for i in rng.integers(0, l, size=5):
        j = int(idx[i])
        a = ts[i:i + m].astype(np.float64)
        b = ts[j:j + m].astype(np.float64)
        a, b = a - a.mean(), b - b.mean()
        na, nb = np.linalg.norm(a), np.linalg.norm(b)
        if na < 1e-9 or nb < 1e-9:
            continue
        c = np.clip(a @ b / (na * nb), -1, 1)
        d = np.sqrt(2 * m * (1 - c))
        assert abs(d - p[i]) < 5e-3, (i, j, d, p[i])


def test_profile_state_merge_monotone():
    a = ProfileState(jnp.asarray([0.5, -0.2, 0.9]), jnp.asarray([1, 2, 3], jnp.int32))
    b = ProfileState(jnp.asarray([0.7, -0.5, 0.1]), jnp.asarray([4, 5, 6], jnp.int32))
    m = a.merge(b)
    np.testing.assert_allclose(np.asarray(m.corr), [0.7, -0.2, 0.9])
    assert list(np.asarray(m.index)) == [4, 2, 3]


def test_corr_dist_roundtrip():
    c = jnp.asarray([-1.0, 0.0, 0.5, 1.0])
    np.testing.assert_allclose(np.asarray(dist_to_corr(corr_to_dist(c, 10), 10)),
                               np.asarray(c), atol=1e-6)


def test_flat_windows_no_nan():
    ts = np.ones(300, np.float32)
    ts[:50] = _series(50, seed=1)
    p = matrix_profile(ts, 16).p
    assert not np.isnan(np.asarray(p)).any()
