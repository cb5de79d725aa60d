"""The host window pass in row blocks (`zstats._window_pass`): the streamed
stats equal a materialized two-pass f64 computation on series that span
many blocks, and a resident corpus side (stored window matrix) gives the
same seeds as a fresh build, bit for bit."""

import jax
import numpy as np
import pytest

from repro.core import plan as plan_mod
from repro.core import zstats
from repro.core.resident import build_side
from repro.core.zstats import compute_cross_stats_host, compute_stats_host


def _walk(n, seed):
    return np.cumsum(np.random.default_rng(seed).normal(size=n))


def _perturb(x, case, m):
    """Put the case's feature across block edges of `x`: the first edge at
    least 2m rows in, and for gaps the edge after it."""
    x = x.copy()
    rows = zstats._block_rows(m)
    edge = rows * (1 + 2 * m // rows)
    if case == "offset":             # a 1e6 level step inside edge windows
        x[edge + m // 2:] += 1.0e6
    elif case == "flat":             # flat windows on both sides of the edge
        x[edge - m:edge + 2 * m] = x[edge]
    elif case == "nan":              # gaps masking windows of three blocks
        x[edge + 3] = np.nan
        x[edge + rows - 2:edge + rows + 1] = np.nan
    return x


def _reference(x, m):
    """The materialized two-pass f64 streams: (l, m) centered windows, their
    norms and invn, as the pass computed them before it was blocked."""
    t = np.asarray(x, np.float64)
    finite = np.isfinite(t)
    masked = None
    if not finite.all():
        t = np.where(finite, t, t[finite].mean())
        nbad = np.concatenate([[0], np.cumsum(~finite)])
        masked = (nbad[m:] - nbad[:-m]) > 0
    t = t - t.mean()
    csum = np.concatenate([[0.0], np.cumsum(t)])
    mu = (csum[m:] - csum[:-m]) / m
    w = np.lib.stride_tricks.sliding_window_view(t, m) - mu[:, None]
    norm2 = np.einsum("lm,lm->l", w, w)
    norm = np.sqrt(norm2)
    scale2 = norm * norm + m * mu * mu
    flat = norm * norm <= 1e-16 * np.maximum(scale2, 1e-300)
    invn = np.where(~flat & (norm > 0), 1.0 / np.maximum(norm, 1e-300), 0.0)
    if masked is not None:
        invn = np.where(masked, -1.0, invn)
    return t, mu, norm2, invn, w


def _close_to_max(got, ref):
    err = np.max(np.abs(np.asarray(got, np.float64) - ref))
    return err <= 1e-15 * np.max(np.abs(ref)), err


@pytest.mark.parametrize("case", ["offset", "flat", "nan"])
@pytest.mark.parametrize("m, n", [(16, 40000), (1024, 2 ** 15)])
def test_blocked_pass_equals_materialized_two_pass(m, n, case):
    rows = zstats._block_rows(m)
    a = _perturb(_walk(n, seed=m + n), case, m)
    b = _perturb(_walk(rows * (3 + 2 * m // rows) + m, seed=m + n + 1),
                 case, m)
    t, mu, norm2, invn, wa = _reference(a, m)
    _, _, _, _, wb = _reference(b, m)
    l = t.size - m + 1
    assert l > 2 * rows                   # spans several blocks

    view = np.lib.stride_tricks.sliding_window_view(t, m)
    got2, (d0,) = zstats._window_pass(view, [wa[0]], mu)
    np.testing.assert_array_equal(got2, norm2)

    with zstats.x64_scope():
        s64 = compute_stats_host(a, m, out_dtype=np.float64)
        c64 = compute_cross_stats_host(a, b, m, out_dtype=np.float64)
        assert np.asarray(c64.cov0s).dtype == np.float64
    np.testing.assert_array_equal(np.asarray(s64.invn), invn)
    if case == "flat":
        assert (invn == 0).any()
    if case == "nan":
        assert (invn == -1).any()
    np.testing.assert_array_equal(np.asarray(s64.cov0), d0)
    ok, err = _close_to_max(s64.cov0, wa @ wa[0])
    assert ok, err

    seeds = np.concatenate([(wa[1:] @ wb[0])[::-1], wb @ wa[0]])
    ok, err = _close_to_max(c64.cov0s, seeds)
    assert ok, err

    # the f32 emission is the blocked pass's f64 streams rounded once; the
    # streams without dots equal the materialized reference's rounding too
    # (a seed's last f64 bits follow the GEMV's summation order, and a flat
    # window's ~1e-24 seed can round either way)
    s32 = compute_stats_host(a, m)
    for name in ("ts", "mu", "invn", "df", "dg", "cov0"):
        np.testing.assert_array_equal(
            np.asarray(getattr(s32, name)),
            np.asarray(getattr(s64, name), np.float64).astype(np.float32))
    for got, ref in ((s32.ts, t), (s32.mu, mu), (s32.invn, invn)):
        np.testing.assert_array_equal(np.asarray(got), ref.astype(np.float32))
    c32 = compute_cross_stats_host(a, b, m)
    np.testing.assert_array_equal(
        np.asarray(c32.cov0s),
        np.asarray(c64.cov0s, np.float64).astype(np.float32))


@pytest.mark.parametrize("corpus_longer, m", [
    pytest.param(True, 16, id="True"), pytest.param(False, 16, id="False"),
    pytest.param(True, 128, id="True-128"),
    pytest.param(False, 128, id="False-128")])
def test_resident_side_equals_fresh_build_above_one_block(corpus_longer, m):
    """A corpus side built once (`build_side`, stored window matrix, its
    blocks spread over the thread pool) gives the payload of a fresh
    `compute_cross_stats_host` (blocks in order) bit for bit, on a corpus
    several row blocks long, in both orientations of the plan."""
    long_n = 3 * zstats._block_rows(m) + 50
    corpus = _walk(long_n if corpus_longer else 300, seed=5) + 3.0e5
    query = _walk(300 if corpus_longer else long_n, seed=6)
    lq, lc = query.size - m + 1, corpus.size - m + 1
    plan = plan_mod.plan_sweep(m, lq, lc, exclusion=0, harvest="row",
                               backend="rowstream")
    assert plan.swap_ab is not corpus_longer
    got = plan_mod.resident_stats(plan, query, build_side(corpus, m))
    fresh = plan_mod.cross_stats_for(plan, query, corpus)
    np.testing.assert_array_equal(np.asarray(got.cov0s),
                                  np.asarray(fresh.cov0s))
    for x, y in zip(jax.tree.leaves(got), jax.tree.leaves(fresh)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_seed_dots_batch_equals_each_fresh_pair():
    """Every pair of a batch over stored matrices (A sides of two lengths,
    B sides several blocks long, cut into runs on the thread pool) carries
    the f64 seeds of a fresh `compute_cross_stats_host` of that pair."""
    m = 16
    rows = zstats._block_rows(m)
    qs = [_walk(n, seed=20 + n) for n in (300, 2 * rows + 40)]
    cs = [_walk(3 * rows + 90 + k, seed=30 + k) for k in range(3)]

    def w(x):
        return compute_stats_host(x, m, min_subsequences=1,
                                  return_centered_windows=True)[1]

    got = zstats.seed_dots_batch([w(q) for q in qs], [w(c) for c in cs])
    assert len(got) == len(qs) * len(cs)
    with zstats.x64_scope():
        for k, (q, c) in enumerate((q, c) for q in qs for c in cs):
            fresh = compute_cross_stats_host(q, c, m, out_dtype=np.float64)
            np.testing.assert_array_equal(got[k], np.asarray(fresh.cov0s))
