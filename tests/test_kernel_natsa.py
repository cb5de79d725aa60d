"""Pallas NATSA kernel: shape/dtype sweeps vs the pure-jnp oracle + brute force.

The kernel runs with interpret=True (CPU executes the kernel body) — the
compiled path targets TPU Mosaic with identical semantics.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.ref import matrix_profile_bruteforce
from repro.core.zstats import compute_stats_host
from repro.kernels import ops
from repro.kernels.ref import rowmax_profile_ref


def _series(n, seed=0, kind="walk"):
    rng = np.random.default_rng(seed)
    if kind == "walk":
        return np.cumsum(rng.normal(size=n)).astype(np.float32)
    if kind == "noise":
        return rng.normal(size=n).astype(np.float32)
    t = np.arange(n, dtype=np.float32)
    return (np.sin(2 * np.pi * t / 40) + 0.1 * rng.normal(size=n)).astype(np.float32)


@pytest.mark.parametrize("n,m,it,dt,kind", [
    (400, 16, 128, 8, "walk"),
    (400, 16, 64, 16, "noise"),
    (513, 24, 128, 8, "sine"),     # l not divisible by IT
    (300, 8, 256, 4, "walk"),      # single row tile
    (260, 50, 32, 8, "noise"),     # tiny tiles, big window
    (1024, 32, 128, 32, "walk"),
])
def test_kernel_matches_oracle(n, m, it, dt, kind):
    ts = _series(n, seed=n + m + it, kind=kind)
    stats = compute_stats_host(ts, m)
    excl = max(1, m // 4)
    ck, ik, cck, cik = ops.rowmax_from_stats(stats, excl=excl, it=it, dt=dt)
    df, dg, invn, cov0p, _, _, l = ops._pad_streams(stats, it, dt, excl)
    cr, ir, ccr, cir = rowmax_profile_ref(df, dg, invn, cov0p, excl=excl, l=l)
    np.testing.assert_allclose(np.asarray(ck), np.asarray(cr[:l]),
                               rtol=1e-4, atol=1e-4)
    # the fused column half must match the oracle's anti-offset harvest too
    np.testing.assert_allclose(np.asarray(cck), np.asarray(ccr[:l]),
                               rtol=1e-4, atol=1e-4)
    # argmax ties can differ only where correlations are ~equal
    mism = np.asarray(ik) != np.asarray(ir[:l])
    assert np.abs(np.asarray(ck)[mism] - np.asarray(cr[:l])[mism]).max(initial=0) < 1e-4
    mismc = np.asarray(cik) != np.asarray(cir[:l])
    assert np.abs(np.asarray(cck)[mismc]
                  - np.asarray(ccr[:l])[mismc]).max(initial=0) < 1e-4


@pytest.mark.parametrize("n,m", [(400, 16), (700, 24), (350, 12)])
def test_full_profile_matches_bruteforce(n, m):
    ts = _series(n, seed=n, kind="walk")
    p = ops.natsa_matrix_profile(ts, m, it=128, dt=8).p
    p_ref, _ = matrix_profile_bruteforce(jnp.asarray(ts), m)
    np.testing.assert_allclose(np.asarray(p), np.asarray(p_ref),
                               rtol=2e-3, atol=2e-3)


def test_kernel_vs_core_engine_agree():
    from repro.core.matrix_profile import matrix_profile
    ts = _series(600, seed=77, kind="sine")
    p1 = ops.natsa_matrix_profile(ts, 20).p
    p2 = matrix_profile(ts, 20).p
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), atol=1e-3)


def test_kernel_float32_inputs_required_shapes():
    ts = _series(300, seed=1).astype(np.float64)  # f64 input OK (host prep)
    res = ops.natsa_matrix_profile(ts, 16)
    p, i = res.p, res.i
    assert p.dtype == jnp.float32 and i.dtype == jnp.int32
    assert not np.isnan(np.asarray(p)[np.isfinite(np.asarray(p))]).any()


def test_bytes_per_cell_model_sane():
    # streaming model: amortized HBM traffic per cell << one f32 per cell
    b = ops.hbm_bytes_per_cell(l=65536, excl=32, it=512, dt=32)
    assert 0 < b < 4.0, b
    # at the kernel's default tiling, 16-bit streams model less traffic
    f32 = ops.hbm_bytes_per_cell(l=65536, excl=64)
    half = ops.hbm_bytes_per_cell(l=65536, excl=64, stream_bytes=2)
    assert 0 < half < f32, (half, f32)
