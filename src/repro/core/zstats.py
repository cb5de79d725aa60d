"""Sliding-window z-normalization statistics for matrix-profile computation.

These are the O(n) precomputed streams that NATSA keeps resident next to its
processing units. Every implementation in this repo (brute-force oracle,
vectorized JAX engine, Pallas kernel) consumes the same streams, so numerical
discrepancies between implementations are attributable to the diagonal
recurrence alone.

Streams (SCAMP formulation, Zhu et al. ICDM'18):
    mu[i]    = mean(T[i:i+m])
    sig2[i]  = population variance of T[i:i+m]
    invn[i]  = 1 / ||T[i:i+m] - mu[i]||           (inverse centered norm)
    df[0]=dg[0]=0
    df[i]    = (T[i+m-1] - T[i-1]) / 2
    dg[i]    = (T[i+m-1] - mu[i]) + (T[i-1] - mu[i-1])
    cov0[k]  = <T[0:m]-mu[0], T[k:k+m]-mu[k]>     (first row of covariances)

The centered-update identity used everywhere downstream:
    cov(i, j) = cov(i-1, j-1) + df[i]*dg[j] + df[j]*dg[i]
    corr(i,j) = cov(i, j) * invn[i] * invn[j]
    dist(i,j) = sqrt(2 m (1 - corr(i, j)))

Degenerate-window conventions, carried entirely IN the invn stream so every
backend (band engine, rowstream, Pallas kernel, distributed chunks) inherits
them without schema changes:

  * invn = 0  — flat window (zero variance): corr 0, dist sqrt(2m),
    conventionally non-matching rather than NaN;
  * invn = -1 — MISSING-DATA sentinel (`compute_stats_host` only): the
    subsequence touches a NaN/Inf sample. Engines extend their validity
    masks with `invn >= 0`, so every pair touching a masked subsequence is
    excluded like an out-of-range cell — masked rows end at NEG/-1, i.e.
    +inf distance and index -1, and masked columns can never be selected as
    neighbors. The non-finite samples themselves are REPLACED by the finite
    mean before the stream cumsums, which keeps df/dg/cov finite; a valid
    window's statistics depend only on its own (finite) samples, so they are
    bit-identical to the all-finite computation.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ZStats:
    """Precomputed streams for a series of length n with window m."""

    ts: jax.Array      # (n,)   the raw series (kernel needs it for row restarts)
    mu: jax.Array      # (l,)
    invn: jax.Array    # (l,)
    df: jax.Array      # (l,)
    dg: jax.Array      # (l,)
    cov0: jax.Array    # (l,)   cov of subsequence 0 against every k
    window: int = dataclasses.field(metadata=dict(static=True))

    @property
    def n_subsequences(self) -> int:
        return self.mu.shape[0]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CrossStats:
    """Streams for an AB join of series A against series B.

    The implicit distance matrix is the full (l_a, l_b) RECTANGLE; its
    diagonals are indexed by a SIGNED offset k = j - i in [-(l_a-1), l_b).
    `cov0s[k + l_a - 1]` is the exact centered covariance at the FIRST cell of
    diagonal k — (0, k) for k >= 0, (-k, 0) for k < 0 — the seed of the same
    O(1)-update recurrence the self-join streams, now with per-series df/dg:

        cov(i, j) = cov(i-1, j-1) + df_a[i]*dg_b[j] + df_b[j]*dg_a[i]

    A self-join is the special case a is b (see `self_cross`); the exclusion
    band |k| < excl is then applied by the engine, not baked into the streams.
    """

    a: ZStats
    b: ZStats
    cov0s: jax.Array   # (l_a + l_b - 1,) seed covariances, index k + l_a - 1

    @property
    def l_a(self) -> int:
        return self.a.n_subsequences

    @property
    def l_b(self) -> int:
        return self.b.n_subsequences

    @property
    def k_min(self) -> int:
        return -(self.l_a - 1)

    @property
    def k_max(self) -> int:
        return self.l_b

    @property
    def window(self) -> int:
        return self.a.window


def self_cross(stats: ZStats) -> CrossStats:
    """View a self-join's streams as the AB rectangle A == B.

    cov(i, j) is symmetric, so the negative-diagonal seeds cov(-k, 0) are just
    the mirrored first row: cov0s = [cov0[l-1] .. cov0[1], cov0[0..l-1]].
    """
    cov0s = jnp.concatenate([stats.cov0[1:][::-1], stats.cov0])
    return CrossStats(a=stats, b=stats, cov0s=cov0s)


def cross_stats_from_parts(stats_a: ZStats, wa, stats_b: ZStats, wb,
                           out_dtype=None, seed_dtype=None) -> CrossStats:
    """Assemble a `CrossStats` from per-series parts — the `(stats, centered
    windows)` pairs `compute_stats_host(..., return_centered_windows=True)`
    yields. This is the seam that lets a RESIDENT side be computed once and
    reused across joins (StreamingProfile.query caches its corpus side this
    way); `compute_cross_stats_host` is the build-both-sides convenience.

    The seeds are exact f64 centered-window dots, so the device recurrence
    restarts from well-conditioned values on every diagonal. Each stats pass
    centers its series around its own mean; the seeds are dot products of
    PER-WINDOW-centered rows, which that global shift cannot change.

    `seed_dtype` is the EMITTED dtype of the seed array (`PrecisionSpec`'s
    `seed_dot` role); it defaults to `out_dtype`. The dots themselves are
    always computed in f64 and rounded exactly once at the end.
    """
    import numpy as np

    wa = np.asarray(wa, np.float64)
    wb = np.asarray(wb, np.float64)
    neg = wa[1:] @ wb[0]            # k = -1 .. -(l_a-1), start cells (-k, 0)
    pos = wb @ wa[0]                # k = 0 .. l_b-1,     start cells (0, k)
    if seed_dtype is None:
        seed_dtype = out_dtype
    dt = jnp.float32 if seed_dtype is None else seed_dtype
    cov0s = jnp.asarray(np.concatenate([neg[::-1], pos]), dt)
    return CrossStats(a=stats_a, b=stats_b, cov0s=cov0s)


def compute_cross_stats_host(ts_a, ts_b, window: int, out_dtype=None,
                             seed_dtype=None) -> CrossStats:
    """Build AB-join streams host-side in f64 (same rationale as
    `compute_stats_host`), then assemble via `cross_stats_from_parts`.

    The seed dots reuse the centered-window matrices the stats pass already
    built (`return_centered_windows=True`), so each series' (l, m) window
    matrix is materialized exactly ONCE — half the AB host-prep time and
    peak memory of building it again for the seeds.

    Either side may be as short as one window (n >= m): query-against-corpus
    joins legitimately use a short side in both orientations (short query vs
    corpus, long stream vs small reference set).
    """
    m = int(window)
    sa, wa = compute_stats_host(ts_a, m, out_dtype=out_dtype,
                                seed_dtype=seed_dtype,
                                min_subsequences=1,
                                return_centered_windows=True)
    sb, wb = compute_stats_host(ts_b, m, out_dtype=out_dtype,
                                seed_dtype=seed_dtype,
                                min_subsequences=1,
                                return_centered_windows=True)
    return cross_stats_from_parts(sa, wa, sb, wb, out_dtype=out_dtype,
                                  seed_dtype=seed_dtype)


def moving_mean_var(ts: jax.Array, m: int) -> tuple[jax.Array, jax.Array]:
    """Sliding mean and population variance over windows of length m.

    Uses cumulative sums; variance clamped at 0 against cancellation.
    """
    n = ts.shape[0]
    csum = jnp.concatenate([jnp.zeros((1,), ts.dtype), jnp.cumsum(ts)])
    csq = jnp.concatenate([jnp.zeros((1,), ts.dtype), jnp.cumsum(ts * ts)])
    s = csum[m:] - csum[:-m]          # (l,)
    sq = csq[m:] - csq[:-m]
    mu = s / m
    var = jnp.maximum(sq / m - mu * mu, 0.0)
    del n
    return mu, var


def sliding_dot(query: jax.Array, ts: jax.Array) -> jax.Array:
    """dot(query, ts[k:k+m]) for every k — correlation via direct windows.

    O(n·m) but fully vectorized; only used once per engine invocation (first
    row of covariances), so it never dominates.
    """
    m = query.shape[0]
    l = ts.shape[0] - m + 1
    # (l, m) windows via gather on a strided index grid.
    idx = jnp.arange(l)[:, None] + jnp.arange(m)[None, :]
    windows = ts[idx]
    return windows @ query


def compute_stats(ts: jax.Array, window: int) -> ZStats:
    """Build all NATSA input streams for `ts` (1-D) and window length.

    In-graph variant; assumes FINITE input (use `compute_stats_host` for
    series with NaN/Inf gaps — it masks affected subsequences)."""
    ts = jnp.asarray(ts)
    if ts.ndim != 1:
        raise ValueError(f"time series must be 1-D, got shape {ts.shape}")
    m = int(window)
    n = ts.shape[0]
    if n < 2 * m:
        raise ValueError(f"series too short: n={n} < 2*window={2 * m}")
    mu, var = moving_mean_var(ts, m)
    # Guard flat windows (sig=0): invn -> 0 gives corr 0 which maps to
    # dist sqrt(2m); flat-vs-flat pairs are conventionally treated as
    # non-matching rather than NaN.
    norm = jnp.sqrt(var * m)
    invn = jnp.where(norm > 0, 1.0 / jnp.maximum(norm, 1e-30), 0.0)

    l = n - m + 1
    tail = ts[m:]            # T[i+m-1] for i in [1, l)
    head = ts[: l - 1]       # T[i-1]   for i in [1, l)
    df = jnp.concatenate([jnp.zeros((1,), ts.dtype), (tail[: l - 1] - head) / 2.0])
    dg = jnp.concatenate(
        [jnp.zeros((1,), ts.dtype), (tail[: l - 1] - mu[1:]) + (head - mu[:-1])]
    )
    qt0 = sliding_dot(ts[:m], ts)                 # raw dot of window0 vs all
    cov0 = qt0 - m * mu[0] * mu                   # centered
    return ZStats(ts=ts, mu=mu, invn=invn, df=df, dg=dg, cov0=cov0, window=m)


def cov_row(stats: ZStats, row: int) -> jax.Array:
    """cov(row, row+k) for all k in [0, l-row) — direct evaluation.

    Used by the engine to restart the diagonal recurrence at an arbitrary row
    block (the TPU analogue of NATSA PUs seeding their private diagonal
    registers), and by tests as an independent check of the recurrence.
    """
    m = stats.window
    ts = stats.ts
    q = jax.lax.dynamic_slice(ts, (row,), (m,))
    qt = sliding_dot(q, ts[row:])
    return qt - m * stats.mu[row] * stats.mu[row:]


def corr_to_dist(corr: jax.Array, window: int) -> jax.Array:
    """Pearson correlation -> z-normalized Euclidean distance."""
    return jnp.sqrt(jnp.maximum(2.0 * window * (1.0 - corr), 0.0))


def dist_to_corr(dist: jax.Array, window: int) -> jax.Array:
    return 1.0 - dist * dist / (2.0 * window)


@partial(jax.jit, static_argnames=("window",))
def compute_stats_jit(ts: jax.Array, window: int) -> ZStats:
    return compute_stats(ts, window)


def compute_stats_host(ts, window: int, out_dtype=None, seed_dtype=None,
                       min_subsequences: int | None = None, *,
                       return_centered_windows: bool = False):
    """Build the NATSA streams in float64 on the HOST, emit `out_dtype`
    streams (default f32).

    The in-graph `compute_stats` suffers catastrophic cancellation in f32
    (E[x^2]-E[x]^2 and qt0 - m*mu0*muk) whenever the series has a large
    offset/level — e.g. random walks. z-normalized distance only depends on
    per-window deviations, so the O(n) precompute is done once in f64 numpy
    (stream preparation = data ingestion; TPUs never see f64) and the device
    recurrence consumes well-conditioned reduced-precision streams.

    `out_dtype` is the emitted STREAM dtype (`PrecisionSpec.stream`): every
    array is computed in f64 and rounded exactly ONCE to it — the default
    f32 emission is bitwise-identical to the historical behavior, and a
    16-bit request never double-rounds through f32. `seed_dtype` overrides
    the dtype of the `cov0` seed array only (`PrecisionSpec.seed_dot`);
    seeds tolerate less rounding than the O(1)-magnitude centered streams
    because they carry full covariance magnitudes.

    `min_subsequences` relaxes the self-join-oriented n >= 2m check: the B
    side of an AB join only needs n >= m + min_subsequences - 1.

    `return_centered_windows=True` returns `(stats, w)` where `w` is the f64
    (l, m) centered-window matrix the pass built anyway — callers needing
    exact window dots (AB seed covariances) reuse it instead of
    re-materializing O(l*m) memory.

    NaN/Inf samples are accepted: every subsequence touching one is masked
    via the invn = -1 sentinel (see module docstring) — its profile entries
    come back +inf / index -1 and it is never selected as a neighbor — while
    all-finite subsequences keep bit-identical statistics (the non-finite
    samples are filled with the finite mean before the cumsums, and a valid
    window's stats depend only on its own samples).
    """
    import numpy as np

    t = np.asarray(ts, np.float64)
    if t.ndim != 1:
        raise ValueError(f"time series must be 1-D, got shape {t.shape}")
    m = int(window)
    n = t.shape[0]
    min_n = 2 * m if min_subsequences is None else m + int(min_subsequences) - 1
    if n < min_n:
        raise ValueError(f"series too short: n={n} < {min_n} "
                         f"(window={m}, min_subsequences={min_subsequences})")
    finite = np.isfinite(t)
    masked = None
    if not finite.all():
        # fill gaps with the finite mean so every downstream cumsum/dot is
        # finite; windows touching a gap are flagged and get the invn = -1
        # sentinel below (their other stream values are don't-cares — every
        # engine masks their cells before any harvest)
        fill = t[finite].mean() if finite.any() else 0.0
        t = np.where(finite, t, fill)
        nbad = np.concatenate([[0], np.cumsum(~finite)])
        masked = (nbad[m:] - nbad[:-m]) > 0            # (l,) touches a gap
    t = t - t.mean()                      # shift-invariant; improves f32 casts
    l = n - m + 1
    csum = np.concatenate([[0.0], np.cumsum(t)])
    mu = (csum[m:] - csum[:-m]) / m
    # zero-copy window view instead of an (l, m) index-gather: stats prep is
    # on the timed serving path, so the only O(l*m) materialization is the
    # centered matrix itself
    view = np.lib.stride_tricks.sliding_window_view(t, m)
    w = view - mu[:, None]                # exact two-pass centering
    norm = np.sqrt(np.einsum("lm,lm->l", w, w))
    # flat-window guard must be RELATIVE: cumsum roundoff in mu leaves
    # ~1e-15-relative residues in w for constant windows, and an exact
    # norm > 0 test would then emit invn ~ 1e15 instead of the corr-0
    # convention. Windows whose deviation is below 1e-8 of their magnitude
    # are z-norm-degenerate either way. scale^2 = sum(t[idx]^2) is
    # norm^2 + m*mu^2 (sum of deviations is ~0), so no second window pass.
    scale2 = norm * norm + m * mu * mu
    flat = norm * norm <= 1e-16 * np.maximum(scale2, 1e-300)
    invn = np.where(~flat & (norm > 0), 1.0 / np.maximum(norm, 1e-300), 0.0)
    if masked is not None:
        invn = np.where(masked, -1.0, invn)   # missing-data sentinel
    tail, head = t[m:], t[: l - 1]
    df = np.concatenate([[0.0], (tail[: l - 1] - head) / 2.0])
    dg = np.concatenate([[0.0], (tail[: l - 1] - mu[1:]) + (head - mu[:-1])])
    cov0 = w @ w[0]
    dt = jnp.float32 if out_dtype is None else out_dtype
    sdt = dt if seed_dtype is None else seed_dtype
    # single rounding f64 -> target dtype (never through an f32 staging cast)
    f = lambda x, d=dt: jnp.asarray(np.asarray(x, np.float64), d)
    stats = ZStats(ts=f(t), mu=f(mu), invn=f(invn), df=f(df), dg=f(dg),
                   cov0=f(cov0, sdt), window=m)
    if return_centered_windows:
        return stats, w
    return stats


# -- shared streaming/fleet block distances -----------------------------------
#
# The incremental surfaces (`core.streaming.StreamingProfile`,
# `core.fleet.StreamingFleet`) evaluate squared-distance BLOCKS between raw
# f64 window matrices instead of running the f32 diagonal recurrence: appends
# are exact, drift-free, and a fleet tenant must be BITWISE-equal to a
# per-series `StreamingProfile` replay. That equality is only attainable if
# both run the identical arithmetic, so the block evaluator lives here — one
# op sequence, called eagerly (host shapes, per-series) and from inside the
# fleet's jitted/vmapped update alike. Two deliberate choices keep it
# shape-independent and replayable:
#
#   * every dot product is an elementwise multiply + a left-to-right fold
#     over the window axis (`_fold_sum`). NO matmul: BLAS/XLA gemm tilings
#     round differently per shape, a (1, m) fleet row would not match a
#     (p, m) bulk-append block. No `jnp.sum` either: a reduce's grouping is
#     the compiler's choice, and on a TPU it changes with the array's shape
#     (a TPU v5e fleet and its replay stopped agreeing bitwise);
#   * BOTH surfaces call the kernels under jit (`sqdist_block_jit` for the
#     per-series path, the fleet's own jitted update for the other), and
#     each FP intermediate carries a `_pin` fence so no product is
#     contracted into the add that consumes it (exact ops —
#     where/clip/max/compare — need none). Without the fence XLA's CPU
#     backend contracts mul->add into FMAs in some loop shapes and not in
#     others (jax 0.9: a one-row block rounded differently from a
#     many-row one), and the fleet round and the single-point append no
#     longer agree;
#   * f64 throughout — callers outside jit wrap calls in `x64_scope()`.
#
# Degenerate-window conventions mirror the historical `StreamingProfile`
# block path (flat windows correlate with nothing -> corr 0; missing data is
# masked by the CALLER with the `invn < 0`-style finite-window mask from
# `window_finite_mask`), not `compute_stats_host`'s relative flat guard:
# these blocks never enter the f32 recurrence, so the cumsum-residue rationale
# for the relative guard does not apply.


def x64_scope():
    """Context manager enabling f64 jax ops for the streaming block kernels
    (the repo's engines are f32 and the global flag stays off; the
    incremental surfaces opt in per call — jit traces/calls made inside the
    scope are cached under it, so fleet state stays f64 end to end)."""
    return jax.enable_x64(True)


def _pin(x: jax.Array) -> jax.Array:
    """Round `x` where it stands: the fence that keeps a product from being
    contracted into the add that consumes it (an FMA rounds once where the
    unfused pair rounds twice). `lax.optimization_barrier` no longer does
    this — XLA's CPU pipeline expands it before fusion, and LLVM then
    contracts mul->add in some loop shapes and not in others. A select on
    `x == x` is an identity (NaN in, NaN out) that neither XLA nor LLVM
    folds, and it sits between the two operations."""
    return jnp.where(x == x, x, jnp.nan)


def _fold_sum(x: jax.Array) -> jax.Array:
    """Sum over the last axis, strictly left to right with every partial
    sum pinned: the same rounding at every array shape (see the section
    comment)."""
    acc = x[..., 0]
    for k in range(1, x.shape[-1]):
        acc = _pin(acc + x[..., k])
    return acc


def centered_block(w: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(..., q, m) raw windows -> (centered windows, centered norms).
    Mean is spelled sum-then-divide so each rounding step is its own
    pinned primitive."""
    s = _fold_sum(w)[..., None]
    mu = _pin(s / w.shape[-1])
    c = _pin(w - mu)
    sq = _pin(c * c)
    ss = _fold_sum(sq)
    return c, _pin(jnp.sqrt(ss))


def window_finite_mask(w: jax.Array) -> jax.Array:
    """(..., q, m) -> (..., q) bool: True where the window touches only
    finite samples — the block-path analogue of the `invn = -1` missing-data
    sentinel (same semantics: masked windows emit inf/-1 and can never be
    selected as neighbors; the caller applies the mask AFTER the block, so
    NaNs propagating through it are overwritten, never compared)."""
    return jnp.isfinite(w).all(axis=-1)


def sqdist_znorm_from_parts(ac, an, bc, bn, *, window: int) -> jax.Array:
    """Z-normalized squared distances from precomputed centered parts:
    `ac` (..., p, m) / `an` (..., p) vs `bc` (..., q, m) / `bn` (..., q)
    -> (..., p, q). Split out so the fleet can keep B-side centered windows
    resident and still share the A-side arithmetic bitwise."""
    prod = _pin(ac[..., :, None, :] * bc[..., None, :, :])
    cross = _fold_sum(prod)
    nn = _pin(an[..., :, None] * bn[..., None, :])
    denom = jnp.maximum(nn, 1e-300)
    ratio = _pin(cross / denom)
    corr = jnp.where((an[..., :, None] > 0) & (bn[..., None, :] > 0),
                     ratio, 0.0)
    om = _pin(1.0 - jnp.clip(corr, -1.0, 1.0))
    return _pin((2.0 * int(window)) * om)


def window_sumsq(w: jax.Array) -> jax.Array:
    """(..., q, m) raw windows -> (..., q) sum of squares, pinned — the
    non-normalized path's precomputable part."""
    sq = _pin(w * w)
    return _fold_sum(sq)


def sqdist_nonnorm_from_parts(wa, sa, wb, sb) -> jax.Array:
    """Non-normalized squared distances from raw windows and their
    precomputed squared norms (`sa = sum(wa^2)`, `sb = sum(wb^2)`):
    ||a - b||^2 by expansion, no (p, q, m) gemm."""
    prod = _pin(wa[..., :, None, :] * wb[..., None, :, :])
    cross = _fold_sum(prod)
    ssum = _pin(sa[..., :, None] + sb[..., None, :])
    c2 = _pin(2.0 * cross)
    return _pin(ssum - c2)


def sqdist_block(wa: jax.Array, wb: jax.Array, *, window: int,
                 normalize: bool = True) -> jax.Array:
    """Squared distances between window matrices, (..., p, m) x (..., q, m)
    -> (..., p, q) — the one block evaluator every incremental surface
    shares (see the section comment for why)."""
    if normalize:
        ac, an = centered_block(wa)
        bc, bn = centered_block(wb)
        return sqdist_znorm_from_parts(ac, an, bc, bn, window=window)
    sa = window_sumsq(wa)
    sb = window_sumsq(wb)
    return sqdist_nonnorm_from_parts(wa, sa, wb, sb)


@lru_cache(maxsize=None)
def _sqdist_block_jitted(window: int, normalize: bool):
    def f(wa, wb):
        return sqdist_block(wa, wb, window=window, normalize=normalize)

    return jax.jit(f)


def sqdist_block_jit(wa, wb, *, window: int, normalize: bool = True):
    """`sqdist_block` through a cached jit — REQUIRED (not an
    optimization) for any caller that must agree bitwise with the fleet:
    see the section comment. Jit cache is keyed per (window, normalize)
    here and per shape by jax; callers bound retraces by padding shapes.
    Call under `x64_scope()`."""
    return _sqdist_block_jitted(int(window), bool(normalize))(wa, wb)
