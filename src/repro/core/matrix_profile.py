"""Vectorized diagonal-band matrix-profile engine (pure JAX), ONE-PASS two-sided.

This is the paper-faithful algorithm, re-thought for vector hardware:

NATSA gives each processing unit a *set of diagonals* of the (implicit)
distance matrix and streams the O(1)-update covariance recurrence along each
diagonal — and, as in the original matrix-profile formulation, every evaluated
cell (i, j) updates *both* P[i] and P[j]. A scalar chain wastes a TPU's 8x128
VPU, so we re-associate the recurrence into a *cumulative sum along the
diagonal* and process a whole BAND of `band` adjacent diagonals at once:

    cov_k(i) = cov0[k] + sum_{t<=i} delta_k(t)
    delta_k(t) = df[t]*dg[t+k] + df[t+k]*dg[t]        (delta_k(0) = 0)

Row-profile updates (P[i] over j > i) fall out as a max over the band axis.
Column updates (P[j] over i < j) are harvested FROM THE SAME TILE: the band's
(D, l) correlation block already holds every cell of column j that the band
touches, at positions corr[d, j - k0 - d] — an anti-offset gather realized as
a static skew (pad + reshape) plus one dynamic slice, i.e. scatter-free (TPUs
have no cheap scatter-min). One streamed sweep of the upper triangle
(k >= excl) therefore yields the COMPLETE profile; the old scheme — a second
row-min pass over the REVERSED series — doubled streamed bytes, FLOPs, and
stats precompute for the same answer, and is gone from every exact path.

The band loop doubles as the ANYTIME unit of work: each (k0, k1) diagonal
chunk updates a running profile with both its row and column harvests, and
after any chunk the merged profile is a valid interruptible answer
(monotonically improving — property-tested).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.zstats import CrossStats, ZStats, compute_stats, corr_to_dist

NEG = -2.0  # corr lives in [-1, 1]; NEG marks "not yet computed"


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ProfileState:
    """Running anytime profile in correlation space (max corr == min dist)."""

    corr: jax.Array   # (l,) running max correlation (accum dtype, f32 default)
    index: jax.Array  # (l,) i32 argmax position j (or -1)

    @classmethod
    def empty(cls, l: int, fill: float = NEG,
              dtype=jnp.float32) -> "ProfileState":
        return cls(corr=jnp.full((l,), fill, dtype),
                   index=jnp.full((l,), -1, jnp.int32))

    def merge(self, other: "ProfileState") -> "ProfileState":
        take = other.corr > self.corr
        return ProfileState(corr=jnp.where(take, other.corr, self.corr),
                            index=jnp.where(take, other.index, self.index))

    def to_distance(self, window: int) -> jax.Array:
        d = corr_to_dist(jnp.clip(self.corr, -1.0, 1.0), window)
        return jnp.where(self.corr <= NEG + 1e-6, jnp.inf, d)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SplitProfile:
    """A self-join sweep's harvest with the two sides kept SEPARATE.

    The row harvest of the upper-triangle sweep covers exactly the cells
    j > i — it IS the RIGHT profile (nearest neighbor strictly after each
    position); the column harvest covers j < i — the LEFT profile. The old
    entry points merged them into one array and threw the split away;
    `ProfileResult` (core.result) now carries all three. `merged` is
    computed as `right.merge(left)` — the exact reduction order the
    pre-split engine used, so the classic profile is bit-identical.
    """

    merged: ProfileState
    right: ProfileState   # row harvest: nearest neighbor at j > t
    left: ProfileState    # column harvest: nearest neighbor at j < t


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TopKState:
    """Running exact top-k profile: `(L, k)` best-first corr + neighbor.

    The k > 1 analogue of `ProfileState`/`ColState` in one class: `merge`
    is the insertion-merge of two best-first sets (concat + `lax.top_k` —
    exact for the UNION because every sweep evaluates each cell exactly
    once, so no neighbor is ever offered twice), and `merge_window` is the
    scatter-free windowed variant over a padded index space (one 2-D
    dynamic slice, same alignment rules as `ColState.merge_window`).
    Unfilled slots are (NEG, -1); ties resolve to the accumulator side, so
    masked all-NEG windows merge as no-ops.
    """

    corr: jax.Array    # (L, k) accum dtype, best-first along the last axis
    index: jax.Array   # (L, k) i32 neighbor (or -1)

    @classmethod
    def empty(cls, l: int, k: int, fill: float = NEG,
              dtype=jnp.float32) -> "TopKState":
        return cls(corr=jnp.full((l, k), fill, dtype),
                   index=jnp.full((l, k), -1, jnp.int32))

    @property
    def k(self) -> int:
        return self.corr.shape[-1]

    def merge(self, other: "TopKState") -> "TopKState":
        c, i = _topk_union(self.corr, self.index, other.corr, other.index,
                           self.k)
        return TopKState(c, i)

    def merge_window(self, win: jax.Array, win_i: jax.Array,
                     start) -> "TopKState":
        w = win.shape[0]
        seg_c = jax.lax.dynamic_slice(self.corr, (start, 0), (w, self.k))
        seg_i = jax.lax.dynamic_slice(self.index, (start, 0), (w, self.k))
        c, i = _topk_union(seg_c, seg_i, win, win_i, self.k)
        return TopKState(
            corr=jax.lax.dynamic_update_slice(self.corr, c, (start, 0)),
            index=jax.lax.dynamic_update_slice(self.index, i, (start, 0)))

    def to_state(self, pad_left: int, l_out: int) -> "TopKState":
        return TopKState(corr=self.corr[pad_left:pad_left + l_out],
                         index=self.index[pad_left:pad_left + l_out])

    @property
    def best(self) -> ProfileState:
        """Slot 0 — identical VALUES to the k = 1 profile (max == top-1)."""
        return ProfileState(corr=self.corr[..., 0], index=self.index[..., 0])

    def to_distance(self, window: int) -> jax.Array:
        d = corr_to_dist(jnp.clip(self.corr, -1.0, 1.0), window)
        return jnp.where(self.corr <= NEG + 1e-6, jnp.inf, d)


def _topk_union(c1: jax.Array, i1: jax.Array, c2: jax.Array, i2: jax.Array,
                k: int) -> tuple[jax.Array, jax.Array]:
    """Exact best-first union of two neighbor sets along the last axis."""
    c = jnp.concatenate([c1, c2], axis=-1)
    i = jnp.concatenate([i1, i2], axis=-1)
    vals, pos = jax.lax.top_k(c, k)
    return vals, jnp.take_along_axis(i, pos, axis=-1)


def default_exclusion(window: int) -> int:
    return max(1, -(-int(window) // 4))


def _skew(x: jax.Array, start, rows: int, cols: int) -> jax.Array:
    """(rows, cols) matrix M[d, t] = x[start + d + t] (zero past the end of
    `x`): one dynamic slice and a static pad/reshape skew — no gather, which
    a TPU executes one scalar at a time."""
    n = rows + cols
    win = jax.lax.dynamic_slice(jnp.pad(x, (0, n)), (start,), (n,))
    # `rows` copies of the window read at row length n + 1: row d starts d
    # elements further into its copy
    flat = jnp.pad(jnp.tile(win, rows), (0, rows))
    return flat.reshape(rows, n + 1)[:, :cols]


def centered_windows(stats: ZStats) -> jax.Array:
    """(l, m) matrix of centered subsequences — used only for reseeding."""
    m = stats.window
    l = stats.n_subsequences
    return _skew(stats.ts, 0, m, l).T - stats.mu[:, None]


def reseed_table(windows_c: jax.Array, reseed_every: int,
                 band: int) -> jax.Array:
    """Exact reseed dots of every segment-start row r_s = s*R, laid out so
    one band's seeds are one slice: `table[s, k] = <Wc[r_s], Wc[r_s + k]>`
    for r_s + k < l (other entries only ever seed masked cells). ONE
    (S, m) x (m, l) matmul per sweep at full f32 precision (a TPU's default
    f32 matmul rounds through bf16), instead of re-gathering (band, S, m)
    windows per band. Columns cover k < l + band, every start a band of
    width `band` can take."""
    l = windows_c.shape[0]
    R = int(reseed_every)
    rows = windows_c[::R]                                       # (S, m)
    s = rows.shape[0]
    exact = jnp.dot(rows, windows_c.T,
                    precision=jax.lax.Precision.HIGHEST)        # (S, l)
    # read row s at row length L + R, so it starts s*R further in: the
    # flat index s*(L + R) + k is exact[s, s*R + k] while s*R + k < L
    width = l + band
    flat = jnp.pad(jnp.pad(exact, ((0, 0), (0, band))).reshape(-1),
                   (0, s * R))
    return flat.reshape(s, width + R)


def _row_harvest(tile: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Reduce a (D, n) band tile over the band axis: best value per position
    and the winning band offset d. Plain max + equality-recovered arg (two
    SIMD reduces) instead of a variadic argmax — ~2.5x faster on XLA CPU;
    ties resolve to the largest d, which any downstream consumer treats as
    an equally valid neighbour."""
    D = tile.shape[0]
    best = jnp.max(tile, axis=0)
    dd = jnp.arange(D, dtype=jnp.int32)[:, None]
    d_win = jnp.max(jnp.where(tile == best[None, :], dd, -1), axis=0)
    return best, d_win


def _col_window(corr: jax.Array, fill: float) -> tuple[jax.Array, jax.Array]:
    """Column-side harvest of one band tile — the anti-offset gather.

    `corr[d, i]` holds the value at cell (i, j = i + k0 + d); the best value
    ENDING at column j = k0 + t is max_d corr[d, t - d]. The per-diagonal
    shift d is STATIC, so it is realized as a skew: pad each row by D+1,
    flatten, re-wrap one element shorter — skew[d, t] = corr[d, t - d]. No
    scatter anywhere, which is what lets the TPU path keep the same
    structure. Cells masked to `fill` in `corr` stay masked.

    Returns (win (li+D,), win_i (li+D,)): the band's column-profile WINDOW —
    entry t belongs to column j = k0 + t — and the winning row index i (or
    -1). The window is merged into a running padded column state with one
    dynamic slice (see `ColState`), so per-band work stays O(li + D) instead
    of materializing an l_out-wide array per band.
    """
    D, li = corr.shape
    W = li + D
    p = jnp.pad(corr, ((0, 0), (0, D + 1)), constant_values=fill)
    skew = p.reshape(-1)[:-D].reshape(D, W)          # skew[d, t] = corr[d, t-d]
    win, d_win = _row_harvest(skew)
    win_i = (jnp.arange(W) - d_win).astype(jnp.int32)  # i = t - d_best
    win_i = jnp.where(win > fill, win_i, -1)
    return win, win_i


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ColState:
    """Running column-side profile over a PADDED index space.

    Real column j lives at position j + pad_left; the pads absorb band
    windows that start before column 0 (negative AB diagonals) or run past
    the last column, so merging a band's (li+D,) window is one aligned
    dynamic_slice + max + dynamic_update_slice — scatter-free and O(window).
    Slices whose start would fall outside are auto-clamped by JAX; that only
    happens for bands entirely outside the diagonal space, whose windows are
    all `fill`, so the (misaligned) merge is a no-op.
    """

    corr: jax.Array    # (pad_left + l_out + pad_right,)
    index: jax.Array

    @classmethod
    def empty(cls, pad_left: int, l_out: int, pad_right: int,
              fill: float = NEG, dtype=jnp.float32) -> "ColState":
        n = pad_left + l_out + pad_right
        return cls(corr=jnp.full((n,), fill, dtype),
                   index=jnp.full((n,), -1, jnp.int32))

    def merge_window(self, win: jax.Array, win_i: jax.Array,
                     start) -> "ColState":
        w = win.shape[0]
        seg_c = jax.lax.dynamic_slice(self.corr, (start,), (w,))
        seg_i = jax.lax.dynamic_slice(self.index, (start,), (w,))
        take = win > seg_c
        return ColState(
            corr=jax.lax.dynamic_update_slice(
                self.corr, jnp.where(take, win, seg_c), (start,)),
            index=jax.lax.dynamic_update_slice(
                self.index, jnp.where(take, win_i, seg_i), (start,)))

    def to_profile(self, pad_left: int, l_out: int) -> ProfileState:
        return ProfileState(corr=self.corr[pad_left:pad_left + l_out],
                            index=self.index[pad_left:pad_left + l_out])


@dataclasses.dataclass
class BankedColState:
    """`ColState` with the flat accumulator split into overlapping BANKS —
    the engine-side mirror of the Pallas kernel's banked column accumulator
    (kernels/natsa_mp.py), so interpret mode and XLA agree on the scheme.

    Rows of `corr` cover the flat space at stride `stride = width - w_max`
    (w_max the widest window ever merged): window start s lands wholly in
    bank s // stride at local offset s mod stride, so a merge is ONE 2-D
    dynamic-slice read-modify-max of a (1, w)-block — the working set per
    merge is one bank, whatever the flat length. `to_flat` max-merges the
    overlaps back (static unrolled slices, scatter-free)."""

    corr: jax.Array    # (n_banks, width)
    index: jax.Array
    stride: int

    @classmethod
    def empty(cls, flat_len: int, width: int, w_max: int,
              fill: float = NEG, dtype=jnp.float32) -> "BankedColState":
        if width <= w_max:
            raise ValueError(f"bank width {width} must exceed the merge "
                             f"window bound {w_max}")
        stride = width - w_max
        n_banks = max(1, max(flat_len - w_max, 0) // stride + 1)
        return cls(corr=jnp.full((n_banks, width), fill, dtype),
                   index=jnp.full((n_banks, width), -1, jnp.int32),
                   stride=stride)

    def merge_window(self, win: jax.Array, win_i: jax.Array,
                     start) -> "BankedColState":
        w = win.shape[0]
        bank = start // self.stride
        local = start - bank * self.stride
        seg_c = jax.lax.dynamic_slice(self.corr, (bank, local), (1, w))[0]
        seg_i = jax.lax.dynamic_slice(self.index, (bank, local), (1, w))[0]
        take = win > seg_c
        return BankedColState(
            corr=jax.lax.dynamic_update_slice(
                self.corr, jnp.where(take, win, seg_c)[None], (bank, local)),
            index=jax.lax.dynamic_update_slice(
                self.index, jnp.where(take, win_i, seg_i)[None],
                (bank, local)),
            stride=self.stride)

    def to_flat(self, flat_len: int,
                fill: float = NEG) -> tuple[jax.Array, jax.Array]:
        n_banks, width = self.corr.shape
        flat_c = jnp.full((flat_len,), fill, self.corr.dtype)
        flat_i = jnp.full((flat_len,), -1, jnp.int32)
        for b in range(n_banks):
            s = b * self.stride
            e = min(s + width, flat_len)
            if e <= s:
                break
            bc, bi = self.corr[b, :e - s], self.index[b, :e - s]
            take = bc > flat_c[s:e]
            flat_c = flat_c.at[s:e].set(jnp.where(take, bc, flat_c[s:e]))
            flat_i = flat_i.at[s:e].set(jnp.where(take, bi, flat_i[s:e]))
        return flat_c, flat_i

    def to_profile(self, pad_left: int, l_out: int,
                   fill: float = NEG) -> ProfileState:
        flat_c, flat_i = self.to_flat(pad_left + l_out, fill)
        return ProfileState(corr=flat_c[pad_left:],
                            index=flat_i[pad_left:])


jax.tree_util.register_dataclass(BankedColState,
                                 data_fields=["corr", "index"],
                                 meta_fields=["stride"])


def _band_corr(stats: ZStats, k0, band: int,
               reseed_every: int | None = None,
               seed_table: jax.Array | None = None,
               accum_dtype=jnp.float32) -> jax.Array:
    """The (D, l) correlation tile of the diagonal band [k0, k0+band) —
    the shared substrate of `band_rowmax` (k = 1 harvest) and `band_topk`
    (top-k harvest). Invalid cells (j >= l) are masked to NEG.

    `reseed_every=R` bounds f32 drift of the cumulative-sum recurrence: the
    covariance is recomputed EXACTLY (direct centered dot, read from
    `seed_table` = `reseed_table(...)`, built here when not passed) every R
    rows and the running sum corrected per segment — the TPU analogue of
    NATSA PUs re-seeding their diagonal registers per work unit. SCAMP
    solves the same drift with fp64, which the TPU VPU does not have.
    The j-side streams of the band are skewed slices (`_skew`), not
    gathers.
    """
    l = stats.n_subsequences
    acc = jnp.dtype(accum_dtype)
    ks = k0 + jnp.arange(band)                     # (D,)
    i = jnp.arange(l)                              # (l,)
    j = i[None, :] + ks[:, None]                   # (D, l)
    valid = j < l

    # streams arrive in the plan's (possibly reduced) stream dtype; every
    # product/cumsum below runs in the accum dtype (no-op upcast when both
    # are f32 — the default path is bitwise-unchanged). Cells past the end
    # (j >= l) read zeros and are masked below.
    dfa = stats.df.astype(acc)
    dga = stats.dg.astype(acc)
    invna = stats.invn.astype(acc)
    dfj = _skew(dfa, k0, band, l)
    dgj = _skew(dga, k0, band, l)
    invnj = _skew(invna, k0, band, l)
    cov0b = jnp.take(stats.cov0.astype(acc), jnp.minimum(ks, l - 1))

    delta = dfa[None, :] * dgj + dfj * dga[None, :]
    delta = jnp.where(valid & (i[None, :] >= 1), delta, 0.0)
    cov = cov0b[:, None] + jnp.cumsum(delta, axis=1)

    if reseed_every is not None:
        R = int(reseed_every)
        if seed_table is None:
            seed_table = reseed_table(centered_windows(stats).astype(acc), R,
                                      band)
        n_seg = seed_table.shape[0]
        # exact cov at segment-start rows r = s*R: <Wc[r], Wc[r+k]>
        seeds = jax.lax.dynamic_slice(seed_table, (0, k0),
                                      (n_seg, band)).T            # (D, S)
        drift = seeds - cov[:, ::R]                               # (D, S)
        cov = cov + jnp.repeat(drift, R, axis=1)[:, :l]

    corr = cov * invna[None, :] * invnj
    # invn < 0 is the missing-data sentinel (zstats): pairs touching a
    # masked subsequence are excluded like out-of-range cells. Applied only
    # HERE, never to the delta mask — the cumsum recurrence must still pass
    # through masked cells to reach later valid cells on the diagonal.
    keep = valid & (invna >= 0)[None, :] & (invnj >= 0)
    return jnp.where(keep, corr, jnp.asarray(NEG, acc))


def band_rowmax(stats: ZStats, k0, band: int, *,
                reseed_every: int | None = None,
                seed_table: jax.Array | None = None,
                accum_dtype=jnp.float32
                ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Two-sided harvest of the diagonal band [k0, k0+band).

    Returns (row_corr (l,), row_idx, win (l+band,), win_i): row entries are
    the best correlation STARTING at row i (index = matching j); (win, win_i)
    is the band's column-profile WINDOW — entry t is the best value ENDING at
    column j = k0 + t with its winning row — read off the same (D, l)
    correlation tile (`_band_corr`), so every cell is computed exactly once
    (see `_col_window` / `ColState` for the scatter-free merge). `k0` may be
    traced (dynamic), `band` is static. Diagonals >= l contribute nothing.
    """
    l = stats.n_subsequences
    corr = _band_corr(stats, k0, band, reseed_every, seed_table, accum_dtype)
    i = jnp.arange(l)
    corr_best, d_win = _row_harvest(corr)
    idx_best = (i + k0 + d_win).astype(jnp.int32)
    idx_best = jnp.where(corr_best > NEG, idx_best, -1)
    win, win_i = _col_window(corr, NEG)
    return corr_best, idx_best, win, win_i


def _topk_rows(tile: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Top-k reduce of a (D, L) band tile over the band axis: `(L, k)`
    best-first values and winning band offsets d. Requires k <= D (the
    planner enforces k <= band)."""
    vals, d = jax.lax.top_k(tile.T, k)
    return vals, d.astype(jnp.int32)


def _topk_col_window(corr: jax.Array, k: int,
                     fill: float = NEG) -> tuple[jax.Array, jax.Array]:
    """Top-k column-side harvest of one band tile — `_col_window`'s skew
    (pad + reshape, scatter-free) followed by a top-k instead of a max.
    Returns ((li+D, k) win, win_i): entry t is the best-k set ENDING at
    column j = k0 + t with the winning row indices i = t - d (or -1)."""
    D, li = corr.shape
    W = li + D
    p = jnp.pad(corr, ((0, 0), (0, D + 1)), constant_values=fill)
    skew = p.reshape(-1)[:-D].reshape(D, W)          # skew[d, t] = corr[d, t-d]
    win, d_win = _topk_rows(skew, k)
    win_i = (jnp.arange(W)[:, None] - d_win).astype(jnp.int32)
    win_i = jnp.where(win > fill, win_i, -1)
    return win, win_i


def band_topk(stats: ZStats, k0, band: int, k: int, *,
              reseed_every: int | None = None,
              seed_table: jax.Array | None = None,
              accum_dtype=jnp.float32
              ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """`band_rowmax` widened to exact top-k: (row (l, k), row_idx, win
    ((l+band, k)), win_i) off the same correlation tile. Within one tile a
    position's candidates live on distinct diagonals, so the per-tile top-k
    is exact and the cross-band `TopKState` union stays exact."""
    l = stats.n_subsequences
    corr = _band_corr(stats, k0, band, reseed_every, seed_table, accum_dtype)
    vals, d = _topk_rows(corr, k)
    idx = (jnp.arange(l)[:, None] + k0 + d).astype(jnp.int32)
    idx = jnp.where(vals > NEG, idx, -1)
    win, win_i = _topk_col_window(corr, k)
    return vals, idx, win, win_i


DEFAULT_RESEED = 512
# 256 diagonals per sub-band amortizes the per-band fixed costs (gather set-up,
# argmax, merge) ~4x better than the old 64 while the (band, l) working set
# stays a few MB; exactness is band-size-invariant (tested).
DEFAULT_BAND = 256


def chunk_rowmax_split(stats: ZStats, k0, k1_static: int, band: int,
                       reseed_every: int | None = DEFAULT_RESEED,
                       accum_dtype=jnp.float32
                       ) -> tuple[ProfileState, ProfileState]:
    """Two-sided harvest over diagonals [k0, k1) with the sides kept
    SEPARATE — (row_state, col_profile): the row harvest is the RIGHT
    profile of the swept span, the column harvest the LEFT profile.

    Iterates `band`-wide sub-bands with lax.scan so the working set stays
    (band, l) regardless of chunk size; each sub-band merges BOTH its row
    harvest (into the row state) and its column window (into a padded
    running `ColState`), so together the returned states hold every profile
    update the chunk's cells imply (no reversed pass owed).
    """
    l = stats.n_subsequences
    acc = jnp.dtype(accum_dtype)
    n_bands = -(-k1_static // band)
    # reseed seeds accumulate m-term dots: upcast the (possibly reduced)
    # centered windows to the accum dtype before the matmul
    table = (reseed_table(centered_windows(stats).astype(acc), reseed_every,
                          band) if reseed_every is not None else None)
    # self-join diagonals are non-negative: no left pad; the right pad
    # absorbs the last window (start <= l-1) and overshooting all-fill bands
    pad_r = l + band

    def body(carry, b):
        state, col = carry
        start = k0 + b * band
        rc, ri, win, wi = band_rowmax(stats, start, band,
                                      reseed_every=reseed_every,
                                      seed_table=table, accum_dtype=acc)
        state = state.merge(ProfileState(rc, ri))
        col = col.merge_window(win, wi, start)
        return (state, col), None

    init = (ProfileState.empty(l, dtype=acc),
            ColState.empty(0, l, pad_r, dtype=acc))
    (state, col), _ = jax.lax.scan(body, init, jnp.arange(n_bands))
    return state, col.to_profile(0, l)


def chunk_rowmax(stats: ZStats, k0, k1_static: int, band: int,
                 reseed_every: int | None = DEFAULT_RESEED,
                 accum_dtype=jnp.float32) -> ProfileState:
    """Merged two-sided profile over diagonals [k0, k1) — the anytime unit
    of work (`chunk_rowmax_split` with the sides folded back together)."""
    rows, col = chunk_rowmax_split(stats, k0, k1_static, band, reseed_every,
                                   accum_dtype)
    return rows.merge(col)


def chunk_topk(stats: ZStats, k0, k1_static: int, band: int, k: int,
               reseed_every: int | None = DEFAULT_RESEED,
               accum_dtype=jnp.float32) -> tuple[TopKState, TopKState]:
    """Top-k analogue of `chunk_rowmax_split`: (right (l, k), left (l, k))
    exact best-first neighbor sets over diagonals [k0, k1)."""
    l = stats.n_subsequences
    acc = jnp.dtype(accum_dtype)
    n_bands = -(-k1_static // band)
    table = (reseed_table(centered_windows(stats).astype(acc), reseed_every,
                          band) if reseed_every is not None else None)

    def body(carry, b):
        rows, col = carry
        start = k0 + b * band
        rc, ri, win, wi = band_topk(stats, start, band, k,
                                    reseed_every=reseed_every,
                                    seed_table=table, accum_dtype=acc)
        rows = rows.merge(TopKState(rc, ri))
        col = col.merge_window(win, wi, start)
        return (rows, col), None

    init = (TopKState.empty(l, k, dtype=acc),
            TopKState.empty(2 * l + band, k, dtype=acc))
    (rows, col), _ = jax.lax.scan(body, init, jnp.arange(n_bands))
    return rows, col.to_state(0, l)


@partial(jax.jit, static_argnums=(1, 2, 3),
         static_argnames=("accum_dtype",))
def profile_from_stats(stats: ZStats, exclusion: int,
                       band: int = DEFAULT_BAND,
                       reseed_every: int | None = DEFAULT_RESEED, *,
                       accum_dtype: str = "float32") -> SplitProfile:
    """Jitted exact-profile core: ONE streamed sweep of k in [excl, l).

    Each cell (i, j) of the upper triangle updates both P[i] (row harvest)
    and P[j] (column harvest), so no reversed-series second pass exists —
    half the streamed bytes, FLOPs, and stats precompute of the old
    forward+reversed scheme for the identical answer. The two sides are no
    longer thrown away after merging: the returned `SplitProfile` carries
    `merged` (== the old return, bit-identical — same reduction order) plus
    `right` (row harvest) and `left` (column harvest).
    """
    l = stats.n_subsequences
    span = l - exclusion
    rows, col = chunk_rowmax_split(stats, jnp.int32(exclusion), span, band,
                                   reseed_every, accum_dtype)
    return SplitProfile(merged=rows.merge(col), right=rows, left=col)


@partial(jax.jit, static_argnums=(1, 2, 3, 4),
         static_argnames=("accum_dtype",))
def profile_topk_from_stats(stats: ZStats, exclusion: int,
                            band: int = DEFAULT_BAND,
                            reseed_every: int | None = DEFAULT_RESEED,
                            k: int = 4, *,
                            accum_dtype: str = "float32"
                            ) -> tuple[TopKState, TopKState, TopKState]:
    """Jitted exact top-k self-join core -> (merged, right, left) `(l, k)`
    best-first neighbor sets from the same single sweep. Slot 0 of `merged`
    carries the same VALUES as the k = 1 profile (max == top-1); with
    `exclusion >= 1` (the planner rejects 0 for top-k) the row and column
    candidate sets are disjoint (j > i vs j < i) and each cell is evaluated
    once, so the union is an exact top-k — at exclusion 0 the diagonal's
    self-match would sit in BOTH sides and the union would double-count
    it."""
    l = stats.n_subsequences
    span = l - exclusion
    rows, col = chunk_topk(stats, jnp.int32(exclusion), span, band, k,
                           reseed_every, accum_dtype)
    return rows.merge(col), rows, col


# Matmul-tile edge for the reduced-precision sweep: 512 reduced-dtype window
# rows per GEMM operand measured fastest at n = 16384 on XLA CPU (256 and
# 1024 within ~10%); any positive edge is valid, multiples of 128 keep the
# operands lane-aligned on TPU.
TILE_EDGE = 512


@partial(jax.jit, static_argnums=(1,),
         static_argnames=("tile", "stream_dtype", "accum_dtype"))
def tile_profile_from_stats(stats: ZStats, exclusion: int, *,
                            tile: int = TILE_EDGE,
                            stream_dtype: str = "bfloat16",
                            accum_dtype: str = "float32") -> SplitProfile:
    """Reduced-precision self-join sweep: QT by blocked GEMM, no recurrence.

    The diagonal O(1)-update recurrence exists to avoid the 2m FLOPs of a
    direct dot per cell — the right trade at f32, where bytes and FLOPs are
    both scarce. Under a 16-bit stream the trade flips the NATSA way: FLOPs
    are abundant (reduced-dtype GEMM throughput) while bytes stay scarce, so
    this path computes every QT(i, j) tile DIRECTLY as a (tile, m) x
    (m, tile) product of reduced-dtype centered windows with wide
    accumulation (`preferred_element_type`). What that buys over threading
    bf16 through the recurrence:

      * NO drift — each cell is one m-term dot in the accum dtype, so the
        error bound is the closed-form `precision.corr_tolerance` (absolute,
        by Cauchy-Schwarz), with no O(diagonal-length) growth and none of
        the reseed machinery (`reseed_every` does not apply here);
      * the streamed traffic is the (l, m) centered-window matrix in the
        stream dtype — half the f32 bytes at bf16, which is the entire
        NATSA thesis applied at the dtype level. No benchmark cell runs a
        16-bit stream yet, so its speed on the chip is not measured.

    Harvests both sides of each upper-triangle (r, c) tile pair — the row
    max is the RIGHT profile, the column max the LEFT — merged into running
    (l,) states at static offsets, so the output `SplitProfile` is
    interchangeable with `profile_from_stats`'s. Windows are centered at
    the stats' full precision FIRST and rounded once to the stream dtype
    (rounding raw ts would scale the error by the series level, not the
    window deviation). Missing-data (invn < 0) and flat-window (invn = 0)
    conventions are inherited unchanged; tile padding reuses the invn = -1
    sentinel so padded rows can never be selected.
    """
    import numpy as np

    acc = jnp.dtype(accum_dtype)
    sdt = jnp.dtype(stream_dtype)
    m = stats.window
    l = stats.n_subsequences
    excl = int(exclusion)
    neg = jnp.asarray(NEG, acc)

    wc = centered_windows(stats).astype(sdt)         # (l, m) streamed reduced
    invn = stats.invn.astype(acc)                    # O(l), stays wide

    nt = -(-l // tile)
    lp = nt * tile
    wcp = jnp.zeros((lp, m), sdt).at[:l].set(wc)
    invp = jnp.full((lp,), -1.0, acc).at[:l].set(invn)
    # upper-triangle tile pairs, row-major — trace-time schedule
    pairs = jnp.asarray([(r, c) for r in range(nt) for c in range(r, nt)],
                        jnp.int32)
    la = jnp.arange(tile, dtype=jnp.int32)
    del np

    def merge_at(prof_c, prof_i, vals, idxs, off):
        seg_c = jax.lax.dynamic_slice(prof_c, (off,), (tile,))
        seg_i = jax.lax.dynamic_slice(prof_i, (off,), (tile,))
        take = vals > seg_c
        return (jax.lax.dynamic_update_slice(
                    prof_c, jnp.where(take, vals, seg_c), (off,)),
                jax.lax.dynamic_update_slice(
                    prof_i, jnp.where(take, idxs, seg_i), (off,)))

    def body(carry, pair):
        rc_, ri_, cc_, ci_ = carry
        i0 = pair[0] * tile
        j0 = pair[1] * tile
        # literal 0 would promote to int64 under an x64 scope — indices to
        # dynamic_slice must share one integer type
        z = jnp.zeros((), i0.dtype)
        a = jax.lax.dynamic_slice(wcp, (i0, z), (tile, m))
        b = jax.lax.dynamic_slice(wcp, (j0, z), (tile, m))
        ia = jax.lax.dynamic_slice(invp, (i0,), (tile,))
        ib = jax.lax.dynamic_slice(invp, (j0,), (tile,))
        qt = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                 preferred_element_type=acc)
        corr = qt * ia[:, None] * ib[None, :]
        ig = i0 + la
        jg = j0 + la
        ok = ((jg[None, :] - ig[:, None]) >= excl) \
            & (ia[:, None] >= 0) & (ib[None, :] >= 0)
        corr = jnp.where(ok, corr, neg)
        # plain max + equality-recovered arg, as everywhere in this engine
        rbest = jnp.max(corr, axis=1)
        rarg = jnp.max(jnp.where(corr == rbest[:, None], jg[None, :], -1),
                       axis=1)
        rarg = jnp.where(rbest > neg, rarg, -1).astype(jnp.int32)
        cbest = jnp.max(corr, axis=0)
        carg = jnp.max(jnp.where(corr == cbest[None, :], ig[:, None], -1),
                       axis=0)
        carg = jnp.where(cbest > neg, carg, -1).astype(jnp.int32)
        rc_, ri_ = merge_at(rc_, ri_, rbest, rarg, i0)
        cc_, ci_ = merge_at(cc_, ci_, cbest, carg, j0)
        return (rc_, ri_, cc_, ci_), None

    init = (jnp.full((lp,), NEG, acc), jnp.full((lp,), -1, jnp.int32),
            jnp.full((lp,), NEG, acc), jnp.full((lp,), -1, jnp.int32))
    (rc_, ri_, cc_, ci_), _ = jax.lax.scan(body, init, pairs)
    rows = ProfileState(rc_[:l], ri_[:l])
    col = ProfileState(cc_[:l], ci_[:l])
    return SplitProfile(merged=rows.merge(col), right=rows, left=col)


def matrix_profile(ts, window: int, exclusion: int | None = None,
                   band: int = DEFAULT_BAND,
                   reseed_every: int | None = DEFAULT_RESEED, *,
                   k: int = 1, harvest: str = "merged",
                   normalize: bool = True,
                   precision=None) -> "ProfileResult":
    """Full exact matrix profile -> `ProfileResult`.

    `result.p` / `result.i` are the classic merged profile (bit-identical
    to the old tuple's arrays). Harvests are PAY-AS-YOU-GO: by default the
    sweep finishes only the merged profile; the LEFT/RIGHT split profiles
    (`result.left_p` / `result.right_p` — the sweep's column/row harvests)
    finish lazily from the retained sweep state on first access, bitwise
    what `harvest="both"` materializes eagerly. With `k > 1`, exact
    `(l, k)` top-k neighbor sets ride along in `result.topk_p/topk_i`.

    `normalize=False` selects plain euclidean distances (the ONE entry
    point for both modes since the `matrix_profile_nonnorm` alias retired):
    same `ProfileResult`, nonnorm self-join plan underneath. The nonnorm
    sweep requires finite samples, ignores `reseed_every` (its recurrence
    reseeds implicitly), and supports only `k=1`.

    `precision` — None, a preset name ("bf16", "f16", "f64"), or a
    `PrecisionSpec` — selects the stream/accumulator dtype policy; it is
    FROZEN into the plan (see core.precision). The default reproduces the
    all-f32 pipeline bitwise; "bf16" halves the streamed bytes per cell and
    switches the sweep to the recurrence-free dot-product tile path.

    Route: on a TPU the default call — z-normalized, k = 1, the default
    f32 precision, `band` and `reseed_every` left at their defaults — runs
    the NATSA Pallas kernel (`kernels.natsa_mp`). Everywhere else (the CPU,
    top-k, nonnorm, 16-bit or f64-accumulating precision, a tuned `band` or
    `reseed_every`) it runs the band-diagonal engine; `band` and
    `reseed_every` are the engine's knobs and apply to it only.

    Thin entry: builds a `SweepPlan` (core.plan) and runs it through the
    executor — the backend choice, exclusion default, harvest wiring and
    precision policy all live in the planner. Stream precompute happens
    host-side in f64 (see zstats.compute_stats_host — f32 cancellation is
    catastrophic on offset data); the O(l^2) diagonal sweep runs on device
    streaming the plan's stream dtype, touching each upper-triangle cell
    once and harvesting both profile sides from it.
    """
    from repro.core import plan as plan_mod
    from repro.core.result import build_result
    from repro.core.validate import validate_series
    from repro.core.zstats import compute_stats_host

    m = int(window)
    if not normalize:
        if k != 1:
            raise ValueError(f"normalize=False supports only k=1, got k={k}")
        validate_series(ts, m, require_finite=True)
        plan = plan_mod.plan_sweep(m, jnp.asarray(ts).shape[0] - m + 1,
                                   exclusion=exclusion, normalize=False,
                                   band=band, harvest=harvest,
                                   precision=precision)
        arr = jnp.asarray(ts, plan.precision.stream_dtype)
        res = plan_mod.execute(plan, arr)
        return build_result(plan, res, arr)
    arr = validate_series(ts, m)
    plan = plan_mod.plan_sweep(m, arr.shape[0] - m + 1, exclusion=exclusion,
                               band=band, reseed_every=reseed_every, k=k,
                               harvest=harvest, precision=precision)
    stats = compute_stats_host(arr, m, **plan_mod.stats_dtypes_for(plan))
    res = plan_mod.execute(plan, stats)
    return build_result(plan, res, stats)


# -- AB join: rectangular diagonal space -------------------------------------
#
# The self-join engine above streams the upper triangle (k >= excl) and gets
# the lower triangle from the column harvest. For two DIFFERENT series the
# rectangle has no such symmetry, so the AB engine streams the SIGNED
# diagonal space k = j - i in [-(l_a-1), l_b) directly: diagonal k starts at
# cell (max(0,-k), max(0,k)), its seed covariance is CrossStats.cov0s, and
# deltas are masked to zero before the start — the cumsum recurrence then
# holds the seed until the diagonal enters the rectangle. The row harvest is
# A's profile; the column harvest of the very same tiles is B's profile,
# obtained for free from the single sweep (`ab_join(..., return_b=True)`).
# Self-join == the case A is B with the band |k| < excl excluded
# (property-tested).
#
# The sweep is tiled in BOTH dimensions: besides the `band`-wide diagonal
# axis, each band tile's ROW range is clamped to the rows actually inside
# the signed rectangle — i in [max(0, -(k0+band-1)), ...) with a STATIC
# height `ab_row_tile(l_a, l_b, band) = min(l_a, l_b + band - 1)` — so a
# skewed join (l_b << l_a) streams ~l_b*l_a cells instead of l_a^2. Row and
# column harvests are both bounded WINDOWS merged into padded running states
# with one dynamic slice each; the j-side strips are loaded as one dynamic
# slice plus a static skew (`_unskew`) instead of a 2-D gather.


def ab_row_tile(l_a: int, l_b: int, band: int) -> int:
    """Static height of a row-clamped AB band tile.

    A band [k0, k0+band) only touches rows i in
    [max(0, -(k0+band-1)), min(l_a, l_b - k0)) — at most
    min(l_a, l_b + band - 1) of them, whatever k0 is. Shapes must be static
    under jit/scan, so every band computes this worst-case height at a
    dynamic offset i0; for l_b << l_a that is the whole row-clamping win
    (~l_b + band rows instead of l_a)."""
    return int(min(l_a, l_b + band - 1))


def _unskew(w: jax.Array, rows: int, li: int) -> jax.Array:
    """Diagonal strip loads without a 2-D gather: out[d, t] = w[t + d].

    `w` is one (li + rows,) contiguous window of a stream; row d needs the
    same window shifted by its (STATIC) diagonal offset d. Broadcast + pad +
    reshape realizes all `rows` shifts in one reshape — the inverse of
    `_col_window`'s skew, and the engine analogue of the kernel's per-sublane
    strip loads."""
    W = w.shape[0]                 # li + rows
    p = jnp.broadcast_to(w, (rows, W)).reshape(-1)
    return jnp.pad(p, (0, rows)).reshape(rows, W + 1)[:, :li]


def _ab_padded_streams(cross: CrossStats, band: int, li: int):
    """Zero-pad both series' streams so every row slice (offset i0) and every
    j-side window slice (offset i0 + k0 + pad_left, width li + band) is in
    bounds for any diagonal a chunk scan can visit, including overshooting
    all-masked bands. Zero df/dg pads contribute nothing to the cumsum; pad
    reads are additionally masked to NEG before any harvest.

    Returns (pad_left, streams...). With the row clamp, i0 + k0 is at least
    1 - band, so a `band`-wide left pad suffices."""
    pad_left = band
    pa = lambda x: jnp.pad(x, (0, li))                      # noqa: E731
    pb = lambda x: jnp.pad(x, (pad_left, li + 2 * band))    # noqa: E731
    sa, sb = cross.a, cross.b
    return (pad_left, pa(sa.df), pa(sa.dg), pa(sa.invn),
            pb(sb.df), pb(sb.dg), pb(sb.invn))


def ab_reseed(l_a: int, l_b: int, reseed_every: int | None) -> int | None:
    """Reseeding exists to bound f32 cumsum drift to `reseed_every` rows from
    an exact seed. An AB diagonal accumulates at most min(l_a, l_b) deltas
    (outside the rectangle they are masked to zero), so when the longest
    diagonal is shorter than one reseed segment the seeds already give the
    same bound for free — skip the reseed machinery entirely."""
    if reseed_every is not None and min(l_a, l_b) <= int(reseed_every):
        return None
    return reseed_every


def _band_corr_ab(cross: CrossStats, k0, band: int, *,
                  k_hi=None, reseed_every: int | None = None,
                  wa: jax.Array | None = None,
                  wb: jax.Array | None = None,
                  padded=None, accum_dtype=jnp.float32
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The (D, li) correlation tile of signed diagonals [k0, k0+band) of the
    AB rectangle, row-clamped — the shared substrate of `band_rowmax_ab`
    and `band_topk_ab`. Returns (corr, i (li,) absolute A rows, i0).
    Streams arrive in the stats' (possibly reduced) dtype and are upcast to
    `accum_dtype` right after the slice loads, so the cumsum recurrence and
    harvest comparisons always run wide."""
    acc = jnp.dtype(accum_dtype)
    sa, sb = cross.a, cross.b
    la, lb = sa.n_subsequences, sb.n_subsequences
    li = ab_row_tile(la, lb, band)
    i0 = jnp.maximum(0, -(k0 + band - 1)).astype(jnp.int32)
    if padded is None:
        padded = _ab_padded_streams(cross, band, li)
    pad_left, dfa_p, dga_p, invna_p, dfb_p, dgb_p, invnb_p = padded

    ks = k0 + jnp.arange(band)                     # (D,) signed
    i = i0 + jnp.arange(li)                        # (li,) absolute rows of A
    j = i[None, :] + ks[:, None]                   # (D, li)
    valid = (j >= 0) & (j < lb) & (i < la)[None, :]
    if k_hi is not None:
        valid = valid & (ks < k_hi)[:, None]

    def row(x):                                    # (li,) contiguous A slice
        return jax.lax.dynamic_slice(x, (i0,), (li,)).astype(acc)

    dfi, dgi, invni = row(dfa_p), row(dga_p), row(invna_p)

    off = i0 + k0 + pad_left
    W = li + band

    def strips(x):                                 # (D, li) skewed B windows
        return _unskew(jax.lax.dynamic_slice(x, (off,), (W,)),
                       band, li).astype(acc)

    dfj, dgj, invnj = strips(dfb_p), strips(dgb_p), strips(invnb_p)
    cov0b = jnp.take(cross.cov0s.astype(acc),
                     jnp.clip(ks + la - 1, 0, la + lb - 2))

    delta = dfi[None, :] * dgj + dfj * dgi[None, :]
    # predecessor cell (i-1, j-1) must exist; before a negative diagonal's
    # start (j <= 0) the masked cumsum simply carries the seed forward. The
    # clamp start i0 is <= every band diagonal's start row, so no live cell
    # precedes the tile.
    delta = jnp.where(valid & (i[None, :] >= 1) & (j >= 1), delta, 0.0)
    cov = cov0b[:, None] + jnp.cumsum(delta, axis=1)

    if reseed_every is not None:
        if wa is None:
            wa = centered_windows(sa)
        if wb is None:
            wb = centered_windows(sb)
        R = int(reseed_every)
        n_seg = -(-li // R)
        rows_rel = jnp.minimum(jnp.arange(n_seg) * R, li - 1)     # (S,) local
        rows_abs = i0 + rows_rel
        rows_c = jnp.minimum(rows_abs, la - 1)
        jrow = rows_abs[None, :] + ks[:, None]                    # (D, S)
        jr = jnp.clip(jrow, 0, lb - 1)
        w_r = wa[rows_c].astype(acc)                              # (S, m)
        w_j = wb[jr].astype(acc)                                  # (D, S, m)
        seeds = jnp.einsum("sm,dsm->ds", w_r, w_j,
                           precision=jax.lax.Precision.HIGHEST)   # (D, S)
        drift = seeds - jnp.take(cov, rows_rel, axis=1)           # (D, S)
        # segments whose start cell is outside the rectangle keep the raw
        # cumsum (bounded by R rows of drift, same as the baseline bound)
        drift = jnp.where((jrow >= 0) & (jrow < lb)
                          & (rows_abs < la)[None, :], drift, 0.0)
        seg = jnp.minimum(jnp.arange(li) // R, n_seg - 1)         # (li,)
        cov = cov + jnp.take(drift, seg, axis=1)

    corr = cov * invni[None, :] * invnj
    # missing-data sentinel (invn < 0): exclude masked pairs at harvest time
    # only — the delta mask above must not change, or the recurrence would
    # break for valid cells past a masked stretch of the diagonal
    keep = valid & (invni >= 0)[None, :] & (invnj >= 0)
    return jnp.where(keep, corr, jnp.asarray(NEG, acc)), i, i0


def band_rowmax_ab(cross: CrossStats, k0, band: int, *,
                   k_hi=None, reseed_every: int | None = None,
                   wa: jax.Array | None = None,
                   wb: jax.Array | None = None, harvest_cols: bool = True,
                   padded=None, accum_dtype=jnp.float32
                   ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                              jax.Array]:
    """Two-sided harvest of A vs B over signed diagonals [k0, k0+band).

    Returns (row_win (li,), row_idx, win (li+band,), win_i, i0): the row
    harvest is a WINDOW over rows [i0, i0+li) of A (entry t = best corr of
    row i0+t, row_idx its j in B), with li = `ab_row_tile(l_a, l_b, band)`
    and i0 = max(0, -(k0+band-1)) — the row clamp that keeps a skewed join
    from computing l_a cells per diagonal. (win, win_i) is B's column-profile
    window (entry t = best value ending at B's column j = i0 + k0 + t, win_i
    the winning row i in A), read off the same (D, li) correlation tile
    (`_band_corr_ab`).
    `k0` may be traced and NEGATIVE; `band` is static. `k_hi` additionally
    masks diagonals >= k_hi (chunk ends that are not band-aligned).
    `harvest_cols=False` skips the column window when B's profile is not
    wanted (win, win_i come back None). Stream loads are dynamic slices +
    static skews (`_unskew`), not 2-D gathers.
    """
    corr, i, i0 = _band_corr_ab(cross, k0, band, k_hi=k_hi,
                                reseed_every=reseed_every, wa=wa, wb=wb,
                                padded=padded, accum_dtype=accum_dtype)
    corr_best, d_win = _row_harvest(corr)
    idx_best = (i + k0 + d_win).astype(jnp.int32)
    idx_best = jnp.where(corr_best > NEG, idx_best, -1)
    win = win_i = None
    if harvest_cols:
        win, win_i = _col_window(corr, NEG)
        win_i = jnp.where(win > NEG, win_i + i0, -1)  # local row -> absolute
    return corr_best, idx_best, win, win_i, i0


def band_topk_ab(cross: CrossStats, k0, band: int, k: int, *,
                 k_hi=None, reseed_every: int | None = None,
                 wa: jax.Array | None = None,
                 wb: jax.Array | None = None, harvest_cols: bool = True,
                 padded=None, accum_dtype=jnp.float32
                 ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array,
                            jax.Array]:
    """`band_rowmax_ab` widened to exact top-k — ((li, k) row window,
    row_idx, (li+band, k) col window, win_i, i0) off the same tile."""
    corr, i, i0 = _band_corr_ab(cross, k0, band, k_hi=k_hi,
                                reseed_every=reseed_every, wa=wa, wb=wb,
                                padded=padded, accum_dtype=accum_dtype)
    vals, d = _topk_rows(corr, k)
    idx = (i[:, None] + k0 + d).astype(jnp.int32)
    idx = jnp.where(vals > NEG, idx, -1)
    win = win_i = None
    if harvest_cols:
        win, win_i = _topk_col_window(corr, k)
        win_i = jnp.where(win > NEG, win_i + i0, -1)
    return vals, idx, win, win_i, i0


def chunk_rowmax_ab(cross: CrossStats, k0, width_static: int, band: int,
                    reseed_every: int | None = DEFAULT_RESEED,
                    k_hi=None, two_sided: bool = True,
                    col_tile: int | None = None, accum_dtype=jnp.float32
                    ) -> tuple[ProfileState, ProfileState | None]:
    """Two-sided states over signed diagonals [k0, k0+width), band-scanned.

    Returns (state_a (l_a,), state_b (l_b,)) — A's row harvest and B's
    column harvest of the same swept cells. BOTH sides accumulate as bounded
    windows in padded `ColState`s (per-band work O(li + band), li the
    clamped row tile): the row side merges each band's (li,) window at its
    dynamic offset i0, the column side its (li+band,) window at
    i0 + k0 + pad_l. `two_sided=False` skips the column state entirely
    (state_b is None) — A's profile is already exact from the row harvest
    alone. `col_tile` accumulates the column side in a `BankedColState`
    of that bank width instead of one flat vector — the engine twin of the
    kernel's banked accumulator (must exceed li + band).
    """
    acc = jnp.dtype(accum_dtype)
    la, lb = cross.l_a, cross.l_b
    n_bands = -(-width_static // band)
    reseed_every = ab_reseed(la, lb, reseed_every)
    wa = centered_windows(cross.a) if reseed_every is not None else None
    wb = centered_windows(cross.b) if reseed_every is not None else None
    li = ab_row_tile(la, lb, band)
    padded = _ab_padded_streams(cross, band, li)
    pad_l = la - 1                 # most negative valid diagonal start
    pad_r = li + 2 * band          # last window + overshooting bands

    def body(carry, b):
        rows, col = carry
        start = k0 + b * band
        ra, ia, win, wi, i0 = band_rowmax_ab(cross, start, band, k_hi=k_hi,
                                             reseed_every=reseed_every,
                                             wa=wa, wb=wb,
                                             harvest_cols=two_sided,
                                             padded=padded,
                                             accum_dtype=acc)
        rows = rows.merge_window(ra, ia, i0)
        if two_sided:
            col = col.merge_window(win, wi, start + i0 + pad_l)
        return (rows, col), None

    if two_sided:
        # ColState and BankedColState share merge_window/to_profile, so the
        # scan body is agnostic to which accumulator layout is in play
        init_col = (BankedColState.empty(pad_l + lb + li + band, col_tile,
                                         li + band, dtype=acc)
                    if col_tile is not None
                    else ColState.empty(pad_l, lb, pad_r, dtype=acc))
    init = (ColState.empty(0, la, li, dtype=acc),
            init_col if two_sided else None)
    (rows, col), _ = jax.lax.scan(body, init, jnp.arange(n_bands))
    return (rows.to_profile(0, la),
            col.to_profile(pad_l, lb) if two_sided else None)


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5),
         static_argnames=("accum_dtype",))
def ab_join_from_stats(cross: CrossStats, exclusion: int = 0,
                       band: int = DEFAULT_BAND,
                       reseed_every: int | None = DEFAULT_RESEED,
                       two_sided: bool = True,
                       col_tile: int | None = None, *,
                       accum_dtype: str = "float32"
                       ) -> tuple[ProfileState, ProfileState | None]:
    """Jitted AB-join core: BOTH profiles of the rectangle from one sweep.

    Returns (state_a, state_b). `exclusion` > 0 removes the band
    |j - i| < exclusion — only meaningful when A is B, where it makes the AB
    join IDENTICAL to the self-join. With exclusion == 0 the whole signed
    space is ONE span, so diagonal k = 0 is evaluated exactly once (the old
    two-span split visited it twice). A's profile is exact from the row
    harvest alone (the signed span covers every cell of each row), so
    `two_sided=False` skips the column harvest and returns state_b=None —
    the cheap path when B's profile is not wanted.
    """
    acc = jnp.dtype(accum_dtype)
    la, lb = cross.l_a, cross.l_b
    excl = int(exclusion)
    state_a = ProfileState.empty(la, dtype=acc)
    state_b = ProfileState.empty(lb, dtype=acc) if two_sided else None

    def merge(sa, sb):
        nonlocal state_a, state_b
        state_a = state_a.merge(sa)
        if two_sided:
            state_b = state_b.merge(sb)

    if excl == 0:
        merge(*chunk_rowmax_ab(cross, jnp.int32(-(la - 1)), la - 1 + lb,
                               band, reseed_every, k_hi=lb,
                               two_sided=two_sided,
                               col_tile=col_tile, accum_dtype=acc))
        return state_a, state_b
    neg_width = la - excl          # diagonals [-(l_a-1), -excl]
    pos_width = lb - excl          # diagonals [excl, l_b)
    if neg_width > 0:
        merge(*chunk_rowmax_ab(cross, jnp.int32(-(la - 1)), neg_width, band,
                               reseed_every, k_hi=-excl + 1,
                               two_sided=two_sided,
                               col_tile=col_tile, accum_dtype=acc))
    if pos_width > 0:
        merge(*chunk_rowmax_ab(cross, jnp.int32(excl), pos_width, band,
                               reseed_every, k_hi=lb, two_sided=two_sided,
                               col_tile=col_tile, accum_dtype=acc))
    return state_a, state_b


def chunk_topk_ab(cross: CrossStats, k0, width_static: int, band: int, k: int,
                  reseed_every: int | None = DEFAULT_RESEED,
                  k_hi=None, two_sided: bool = True,
                  accum_dtype=jnp.float32
                  ) -> tuple[TopKState, TopKState | None]:
    """Top-k analogue of `chunk_rowmax_ab`: (state_a (l_a, k), state_b
    (l_b, k)) exact best-first neighbor sets over signed diagonals
    [k0, k0+width), band-scanned with row-clamped tiles. Both sides
    accumulate as bounded `(w, k)` windows in padded `TopKState`s (the
    banked column accumulator stays k = 1-only, so `col_tile` has no
    top-k variant — the planner pins flat accumulation for k > 1)."""
    acc = jnp.dtype(accum_dtype)
    la, lb = cross.l_a, cross.l_b
    n_bands = -(-width_static // band)
    reseed_every = ab_reseed(la, lb, reseed_every)
    wa = centered_windows(cross.a) if reseed_every is not None else None
    wb = centered_windows(cross.b) if reseed_every is not None else None
    li = ab_row_tile(la, lb, band)
    padded = _ab_padded_streams(cross, band, li)
    pad_l = la - 1                 # most negative valid diagonal start

    def body(carry, b):
        rows, col = carry
        start = k0 + b * band
        ra, ia, win, wi, i0 = band_topk_ab(cross, start, band, k, k_hi=k_hi,
                                           reseed_every=reseed_every,
                                           wa=wa, wb=wb,
                                           harvest_cols=two_sided,
                                           padded=padded,
                                           accum_dtype=acc)
        rows = rows.merge_window(ra, ia, i0)
        if two_sided:
            col = col.merge_window(win, wi, start + i0 + pad_l)
        return (rows, col), None

    init = (TopKState.empty(la + li, k, dtype=acc),
            TopKState.empty(pad_l + lb + li + 2 * band, k, dtype=acc)
            if two_sided else None)
    (rows, col), _ = jax.lax.scan(body, init, jnp.arange(n_bands))
    return (rows.to_state(0, la),
            col.to_state(pad_l, lb) if two_sided else None)


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5),
         static_argnames=("accum_dtype",))
def ab_join_topk_from_stats(cross: CrossStats, exclusion: int = 0,
                            band: int = DEFAULT_BAND,
                            reseed_every: int | None = DEFAULT_RESEED,
                            two_sided: bool = True, k: int = 4, *,
                            accum_dtype: str = "float32"
                            ) -> tuple[TopKState, TopKState | None]:
    """Jitted exact top-k AB-join core: `(l_a, k)` (and `(l_b, k)` with
    `two_sided`) best-first neighbor sets from one signed-diagonal sweep.
    Same span structure as `ab_join_from_stats` (an exclusion band splits
    the signed space in two; with exclusion == 0 diagonal k = 0 is
    evaluated exactly once, keeping the union top-k exact)."""
    acc = jnp.dtype(accum_dtype)
    la, lb = cross.l_a, cross.l_b
    excl = int(exclusion)
    state_a = TopKState.empty(la, k, dtype=acc)
    state_b = TopKState.empty(lb, k, dtype=acc) if two_sided else None

    def merge(sa, sb):
        nonlocal state_a, state_b
        state_a = state_a.merge(sa)
        if two_sided:
            state_b = state_b.merge(sb)

    if excl == 0:
        merge(*chunk_topk_ab(cross, jnp.int32(-(la - 1)), la - 1 + lb,
                             band, k, reseed_every, k_hi=lb,
                             two_sided=two_sided, accum_dtype=acc))
        return state_a, state_b
    neg_width = la - excl          # diagonals [-(l_a-1), -excl]
    pos_width = lb - excl          # diagonals [excl, l_b)
    if neg_width > 0:
        merge(*chunk_topk_ab(cross, jnp.int32(-(la - 1)), neg_width, band, k,
                             reseed_every, k_hi=-excl + 1,
                             two_sided=two_sided, accum_dtype=acc))
    if pos_width > 0:
        merge(*chunk_topk_ab(cross, jnp.int32(excl), pos_width, band, k,
                             reseed_every, k_hi=lb, two_sided=two_sided,
                             accum_dtype=acc))
    return state_a, state_b


# How many rows the short side of a rectangle may have before the
# row-streamed AB sweep (sequential lax.scan over rows) stops paying off and
# the planner (core.plan.plan_sweep) falls back to the band-diagonal engine:
# per-step dispatch overhead is ~microseconds, so a few thousand steps is
# noise while the vectorized per-row work stays wide.
AB_ROWSTREAM_MAX_ROWS = 4096


@partial(jax.jit, static_argnums=(1, 2), static_argnames=("accum_dtype",))
def ab_join_rowstream(cross: CrossStats, exclusion: int = 0,
                      reseed_every: int | None = DEFAULT_RESEED, *,
                      accum_dtype: str = "float32"
                      ) -> tuple[ProfileState, ProfileState]:
    """Row-streamed AB join: ONE lax.scan over A's rows, each step a fully
    vectorized O(l_b) update — the rectangle's other natural 2-D tiling
    (rows x full-width strips) and the fastest exact path when one side is
    short.

    Per row i the carried covariance vector obeys the same O(1)-update
    recurrence the band engine streams per diagonal —
    QT(i, j) = QT(i-1, j-1) + df_a[i] dg_b[j] + df_b[j] dg_a[i] — with the
    j = 0 cell re-seeded exactly from `cov0s` (it starts diagonal k = -i),
    so every cell is touched once with NO masking, skewing, or windowing at
    all; that is what lets it beat a dense-matmul oracle on skewed shapes.
    Both profiles come from the same sweep: the per-row max is A's profile,
    the running elementwise max over rows is B's. Drift control: rows at
    multiples of `reseed_every` replace the whole carry with exact centered
    dots (a small precomputed (S, l_b) matrix); a diagonal accumulates at
    most min(l_a, l_b) deltas, so `ab_reseed` skips that machinery when the
    seeds alone already bound drift tighter.

    The planner dispatches here (orienting the SHORT side onto rows via
    `swap_ab`) when the row count is at most AB_ROWSTREAM_MAX_ROWS; the
    band-diagonal engine remains the path for huge near-square rectangles
    and for every partitioned/anytime/distributed schedule.
    """
    acc = jnp.dtype(accum_dtype)
    sa, sb = cross.a, cross.b
    la, lb = cross.l_a, cross.l_b
    excl = int(exclusion)
    R = ab_reseed(la, lb, reseed_every)
    # streams upcast to the accum dtype at load — the carried recurrence,
    # reseeds and harvests never run reduced
    dfb, dgb, invnb = (sb.df.astype(acc), sb.dg.astype(acc),
                       sb.invn.astype(acc))
    cov0s = cross.cov0s.astype(acc)
    row0 = cov0s[la - 1:]                              # cov(0, j), (l_b,)
    seeds_neg = cov0s[:la][::-1]                       # cov(i, 0), (l_a,)
    if R is not None:
        wa = centered_windows(sa).astype(acc)
        wb = centered_windows(sb).astype(acc)
        import numpy as np
        rows = np.arange(0, la, int(R))                # static row ids
        exact = jnp.einsum("sm,lm->sl", wa[rows], wb,  # (S, l_b) reseed rows
                           precision=jax.lax.Precision.HIGHEST)
    jj = jnp.arange(lb)
    neg = jnp.asarray(NEG, acc)

    def step(carry, xs):
        qt, pb, ib = carry
        dfi, dgi, invni, seed0, i = xs
        dfi, dgi, invni = dfi.astype(acc), dgi.astype(acc), invni.astype(acc)
        seed0 = seed0.astype(acc)
        delta = dfi * dgb + dfb * dgi
        qt = jnp.concatenate([seed0[None], qt[:-1] + delta[1:]])
        if R is not None:
            qt = jnp.where(i % R == 0,
                           jax.lax.dynamic_index_in_dim(exact, i // R, 0,
                                                        keepdims=False), qt)
        else:
            qt = jnp.where(i == 0, row0, qt)
        corr = qt * invnb * invni
        # missing-data sentinel (invn < 0): masked pairs lose unconditionally
        corr = jnp.where((invni >= 0) & (invnb >= 0), corr, neg)
        if excl > 0:
            corr = jnp.where(jnp.abs(jj - i) >= excl, corr, neg)
        take = corr > pb
        pb = jnp.where(take, corr, pb)
        ib = jnp.where(take, i, ib)
        # plain max + equality-recovered arg, as everywhere in this engine:
        # variadic argmax is ~1.7x the whole step's cost on XLA CPU
        mx = jnp.max(corr)
        am = jnp.max(jnp.where(corr >= mx, jj, -1))
        return (qt, pb, ib), (mx, am)

    init = (jnp.zeros((lb,), acc),
            jnp.full((lb,), NEG, acc),
            jnp.full((lb,), -1, jnp.int32))
    xs = (sa.df, sa.dg, sa.invn, seeds_neg,
          jnp.arange(la, dtype=jnp.int32))
    (_, pb, ib), (pa, ja) = jax.lax.scan(step, init, xs)
    ja = jnp.where(pa > NEG, ja, -1).astype(jnp.int32)
    return (ProfileState(pa, ja), ProfileState(pb, ib))


@partial(jax.jit, static_argnums=(1, 2, 3), static_argnames=("accum_dtype",))
def ab_join_rowstream_topk(cross: CrossStats, exclusion: int = 0,
                           reseed_every: int | None = DEFAULT_RESEED,
                           k: int = 4, *, accum_dtype: str = "float32"
                           ) -> tuple[TopKState, TopKState]:
    """Row-streamed AB join with exact top-k on BOTH sides — the same ONE
    lax.scan over A's rows as `ab_join_rowstream` (identical carried
    covariance recurrence and reseeds), but each row keeps its k best
    columns (`lax.top_k` of the full-width row — exact, every candidate of
    that row is present) and the B side runs the `(l_b, k)` insertion
    merge: each row offers every column exactly one new candidate, so
    union-with-one-candidate per step is an exact running top-k."""
    acc = jnp.dtype(accum_dtype)
    sa, sb = cross.a, cross.b
    la, lb = cross.l_a, cross.l_b
    excl = int(exclusion)
    R = ab_reseed(la, lb, reseed_every)
    dfb, dgb, invnb = (sb.df.astype(acc), sb.dg.astype(acc),
                       sb.invn.astype(acc))
    cov0s = cross.cov0s.astype(acc)
    row0 = cov0s[la - 1:]                              # cov(0, j), (l_b,)
    seeds_neg = cov0s[:la][::-1]                       # cov(i, 0), (l_a,)
    if R is not None:
        wa = centered_windows(sa).astype(acc)
        wb = centered_windows(sb).astype(acc)
        import numpy as np
        rows = np.arange(0, la, int(R))                # static row ids
        exact = jnp.einsum("sm,lm->sl", wa[rows], wb,  # (S, l_b) reseed rows
                           precision=jax.lax.Precision.HIGHEST)
    jj = jnp.arange(lb)
    neg = jnp.asarray(NEG, acc)

    def step(carry, xs):
        qt, pbc, pbi = carry
        dfi, dgi, invni, seed0, i = xs
        dfi, dgi, invni = dfi.astype(acc), dgi.astype(acc), invni.astype(acc)
        seed0 = seed0.astype(acc)
        delta = dfi * dgb + dfb * dgi
        qt = jnp.concatenate([seed0[None], qt[:-1] + delta[1:]])
        if R is not None:
            qt = jnp.where(i % R == 0,
                           jax.lax.dynamic_index_in_dim(exact, i // R, 0,
                                                        keepdims=False), qt)
        else:
            qt = jnp.where(i == 0, row0, qt)
        corr = qt * invnb * invni
        # missing-data sentinel (invn < 0): masked pairs lose unconditionally
        corr = jnp.where((invni >= 0) & (invnb >= 0), corr, neg)
        if excl > 0:
            corr = jnp.where(jnp.abs(jj - i) >= excl, corr, neg)
        # B side: one new candidate per column, insertion-merged
        cand_i = jnp.where(corr > NEG, i, -1).astype(jnp.int32)
        pbc, pbi = _topk_union(pbc, pbi, corr[:, None], cand_i[:, None], k)
        # A side: the row's k best columns
        vals, pos = jax.lax.top_k(corr, k)
        ja = jnp.where(vals > NEG, pos, -1).astype(jnp.int32)
        return (qt, pbc, pbi), (vals, ja)

    init = (jnp.zeros((lb,), acc),
            jnp.full((lb, k), NEG, acc),
            jnp.full((lb, k), -1, jnp.int32))
    xs = (sa.df, sa.dg, sa.invn, seeds_neg,
          jnp.arange(la, dtype=jnp.int32))
    (_, pbc, pbi), (pa, ja) = jax.lax.scan(step, init, xs)
    return (TopKState(pa, ja), TopKState(pbc, pbi))


def ab_join(ts_a, ts_b, window: int, *, exclusion: int | None = None,
            band: int = DEFAULT_BAND,
            reseed_every: int | None = DEFAULT_RESEED,
            normalize: bool = True, return_b: bool = False,
            k: int = 1, precision=None) -> "ProfileResult":
    """AB join: for every subsequence of A, its nearest neighbour in B.

    Returns a `ProfileResult`: `result.p[i]` the distance, `result.i[i]`
    the matching start position in B. With `return_b=True` the sweep also
    eagerly harvests B's profile against A (`result.b_p` / `result.b_i`)
    from the SAME single sweep, not a second join; without it, `result.b_p`
    still answers lazily on first access (from retained sweep state where
    the backend computed it anyway, else via one two-sided re-execute of
    the same plan). `k > 1` adds exact top-k neighbor sets
    (`result.topk_p`, and `result.b_topk_p` with `return_b`). No exclusion
    zone by default (cross-series matches at
    equal offsets are legitimate); `exclusion` exists so that
    ab_join(ts, ts, m, exclusion=e) == matrix_profile(ts, m, exclusion=e).
    Stream precompute is host-side f64, the O(l_a*l_b) engine device f32.

    Scheduling lives in the planner (core.plan.plan_sweep): the rectangle is
    swept with its SHORT side on rows (`swap_ab`) via `ab_join_rowstream`
    whenever that side fits AB_ROWSTREAM_MAX_ROWS; huge near-square joins
    and nonnorm sweeps take the band-diagonal engine, whose tiles are
    row-clamped to the rectangle.
    """
    from repro.core import plan as plan_mod
    from repro.core.result import build_result
    from repro.core.validate import validate_series

    m = int(window)
    # nonnorm distances cannot mask non-finite samples (no invn sentinel)
    a = validate_series(ts_a, m, name="ts_a", require_finite=not normalize)
    b = validate_series(ts_b, m, name="ts_b", require_finite=not normalize)
    plan = plan_mod.plan_sweep(m, a.shape[0] - m + 1, b.shape[0] - m + 1,
                               exclusion=exclusion, normalize=normalize,
                               harvest="both" if return_b else "merged",
                               band=band, reseed_every=reseed_every, k=k,
                               precision=precision)
    if not normalize:
        sdt = plan.precision.stream_dtype
        stats = (jnp.asarray(a, sdt), jnp.asarray(b, sdt))
    else:
        stats = plan_mod.cross_stats_for(plan, a, b)
    res = plan_mod.execute(plan, stats)
    return build_result(plan, res, stats)


def batch_profile(series, window: int, *, exclusion: int | None = None,
                  band: int = DEFAULT_BAND,
                  reseed_every: int | None = DEFAULT_RESEED,
                  k: int = 1, harvest: str = "merged",
                  precision=None) -> "ProfileResult":
    """Self-join matrix profiles for a (B, n) stack in ONE vmapped program.

    Per-series host f64 stream prep (forward only — the fused sweep needs no
    reversed streams), then a single vmap of the jitted band engine (a
    batched plan — the planner pins the engine backend; rowstream/kernel
    don't vmap) — the multi-tenant serving path (one dispatch, B profiles).
    Returns a `ProfileResult` whose every field is stacked (B, l[, k]).
    """
    import numpy as np

    from repro.core import plan as plan_mod
    from repro.core.result import build_result
    from repro.core.validate import validate_series
    from repro.core.zstats import compute_stats_host

    arr = np.asarray(series)
    if arr.ndim != 2 or arr.shape[0] == 0:
        raise ValueError(f"expected a non-empty (batch, n) stack, got "
                         f"shape {arr.shape}")
    m = int(window)
    # rows share dtype and length, so validating one validates the stack
    validate_series(arr[0], m, name="series[0]")
    plan = plan_mod.plan_sweep(m, arr.shape[1] - m + 1, exclusion=exclusion,
                               band=band, reseed_every=reseed_every,
                               batch=arr.shape[0], k=k, harvest=harvest,
                               precision=precision)
    dt_kw = plan_mod.stats_dtypes_for(plan)
    stats = [compute_stats_host(s, m, **dt_kw) for s in arr]
    stack = jax.tree.map(lambda *xs: jnp.stack(xs), *stats)
    res = plan_mod.execute(plan, stack)
    return build_result(plan, res, stack)


def batch_ab_join(stack_a, stack_b, window: int, *,
                  exclusion: int | None = None, band: int = DEFAULT_BAND,
                  reseed_every: int | None = DEFAULT_RESEED,
                  return_b: bool = False, k: int = 1,
                  precision=None) -> "ProfileResult":
    """Vmapped AB joins: row b of (B, n_a) against row b of (B, n_b).

    Returns a stacked `ProfileResult`; with `return_b=True` the (B, l_b)
    B-side profiles from the same sweep ride along in `.b_p`/`.b_i`.
    """
    import numpy as np

    from repro.core import plan as plan_mod
    from repro.core.result import build_result
    from repro.core.zstats import compute_cross_stats_host

    from repro.core.validate import validate_series

    a, b = np.asarray(stack_a), np.asarray(stack_b)
    if (a.ndim != 2 or b.ndim != 2 or a.shape[0] != b.shape[0]
            or a.shape[0] == 0):
        raise ValueError(f"expected matching non-empty (batch, n) stacks, "
                         f"got {a.shape} vs {b.shape}")
    m = int(window)
    validate_series(a[0], m, name="stack_a[0]")
    validate_series(b[0], m, name="stack_b[0]")
    plan = plan_mod.plan_sweep(m, a.shape[1] - m + 1, b.shape[1] - m + 1,
                               exclusion=exclusion, band=band,
                               reseed_every=reseed_every,
                               harvest="both" if return_b else "merged",
                               batch=a.shape[0], k=k, precision=precision)
    dt_kw = plan_mod.stats_dtypes_for(plan)
    crosses = [compute_cross_stats_host(ra, rb, m, **dt_kw)
               for ra, rb in zip(a, b)]
    stack = jax.tree.map(lambda *xs: jnp.stack(xs), *crosses)
    res = plan_mod.execute(plan, stack)
    return build_result(plan, res, stack)


def band_rowmin_nonnorm(ts: jax.Array, window: int, k0, band: int):
    """Non-normalized squared-Euclidean two-sided harvest of [k0, k0+band).

    Same NATSA diagonal-streaming structure, different recurrence:
        D2(i+1, j+1) = D2(i, j) + (T[i+m]-T[j+m])^2 - (T[i]-T[j])^2
    Level shifts are NOT normalized away — this is the telemetry-monitor
    distance (z-norm MP is blind to amplitude anomalies on flat traces).
    Returns (neg_d2 (l,), idx, win (l+band,), win_i): negated so merge()
    max-semantics work; (win, win_i) is the tile's column-profile window
    (see `_col_window` / `ColState`).
    """
    m = int(window)
    n = ts.shape[0]
    l = n - m + 1
    ks = k0 + jnp.arange(band)                          # (D,)
    i = jnp.arange(l)
    j = i[None, :] + ks[:, None]                        # (D, l)
    valid = j < l

    # D2(0, k) for the band: ssq windows + sliding dot
    csq = jnp.concatenate([jnp.zeros((1,), ts.dtype), jnp.cumsum(ts * ts)])
    ssq = csq[m:] - csq[:-m]                            # (l,)
    from repro.core.zstats import sliding_dot
    qt0 = sliding_dot(ts[:m], ts)                       # (l,)
    kc = jnp.minimum(ks, l - 1)
    d20 = ssq[0] + jnp.take(ssq, kc) - 2 * jnp.take(qt0, kc)   # (D,)

    def g(a):                                           # safe gather of ts
        return jnp.take(ts, jnp.minimum(a, n - 1))

    tim = g(i[None, :] + m - 1)                         # T[i+m-1]
    tjm = g(j + m - 1)                                  # T[j+m-1]
    tip = g(jnp.maximum(i[None, :] - 1, 0))             # T[i-1]
    tjp = g(jnp.maximum(j - 1, 0))                      # T[j-1]
    delta = (tim - tjm) ** 2 - (tip - tjp) ** 2
    delta = jnp.where(valid & (i[None, :] >= 1), delta, 0.0)
    d2 = d20[:, None] + jnp.cumsum(delta, axis=1)
    neg = jnp.where(valid, -jnp.maximum(d2, 0.0), -jnp.inf)

    neg_best, d_win = _row_harvest(neg)
    idx = jnp.where(jnp.isfinite(neg_best),
                    (i + k0 + d_win).astype(jnp.int32), -1)
    win, win_i = _col_window(neg, -jnp.inf)
    return neg_best, idx, win, win_i


def nonnorm_to_distance(state: ProfileState) -> jax.Array:
    """Finish a nonnorm state (corr = negated squared distance) to euclid
    distance — inf where the side never saw a cell."""
    dist = jnp.sqrt(jnp.maximum(-state.corr, 0.0))
    return jnp.where(jnp.isfinite(state.corr), dist, jnp.inf)


@partial(jax.jit, static_argnums=(1, 2, 3), static_argnames=("accum_dtype",))
def nonnorm_profile_from_ts(ts: jax.Array, window: int, exclusion: int,
                            band: int = DEFAULT_BAND, *,
                            accum_dtype: str = "float32") -> SplitProfile:
    """Jitted nonnorm self-join core: one two-sided sweep of k in [excl, l).
    Executor-facing (core.plan); `exclusion` is concrete here — defaults are
    the planner's job. Returns a `SplitProfile` of states in NEGATED
    squared-distance space (merge max-semantics); finish each side with
    `nonnorm_to_distance`. Raw squared distances have no [-1, 1] bound, so
    reduced streams are rejected at plan time for nonnorm sweeps — the whole
    computation runs in `accum_dtype`."""
    m = int(window)
    excl = int(exclusion)
    acc = jnp.dtype(accum_dtype)
    ts = jnp.asarray(ts, acc)
    l = ts.shape[0] - m + 1
    span = l - excl
    n_bands = -(-span // band)

    def body(carry, b):
        state, col = carry
        rneg, ridx, win, wi = band_rowmin_nonnorm(ts, m, excl + b * band,
                                                  band)
        state = state.merge(ProfileState(rneg, ridx))
        col = col.merge_window(win, wi, excl + b * band)
        return (state, col), None

    init = (ProfileState.empty(l, -jnp.inf, dtype=acc),
            ColState.empty(0, l, l + band, -jnp.inf, dtype=acc))
    (rows, col), _ = jax.lax.scan(body, init, jnp.arange(n_bands))
    left = col.to_profile(0, l)
    return SplitProfile(merged=rows.merge(left), right=rows, left=left)


def band_rowmin_nonnorm_ab(ts_a: jax.Array, ts_b: jax.Array, d20s: jax.Array,
                           window: int, k0, band: int, k_hi=None,
                           harvest_cols: bool = True, padded=None):
    """Non-normalized squared-Euclidean AB harvest over signed diagonals
    [k0, k0+band). `d20s` are the seed distances at each diagonal's start
    cell (index k + l_a - 1). Returns (neg_d2 (li,), idx, win (li+band,),
    win_i, i0) — A's row-profile WINDOW over rows [i0, i0+li) and B's
    column-profile window of the same row-clamped tile (win/win_i None with
    `harvest_cols=False`); li is `ab_row_tile(l_a, l_b, band)`."""
    m = int(window)
    na, nb = ts_a.shape[0], ts_b.shape[0]
    la, lb = na - m + 1, nb - m + 1
    li = ab_row_tile(la, lb, band)
    i0 = jnp.maximum(0, -(k0 + band - 1)).astype(jnp.int32)
    if padded is None:
        padded = _nonnorm_padded_series(ts_a, ts_b, band, li)
    pad_left, tsa_p, tsb_p = padded

    ks = k0 + jnp.arange(band)                          # (D,) signed
    i = i0 + jnp.arange(li)                             # (li,) absolute rows
    j = i[None, :] + ks[:, None]                        # (D, li)
    valid = (j >= 0) & (j < lb) & (i < la)[None, :]
    if k_hi is not None:
        valid = valid & (ks < k_hi)[:, None]

    d20 = jnp.take(d20s, jnp.clip(ks + la - 1, 0, la + lb - 2))

    # A is left-padded by 1 (the i-1 read at i = 0, masked anyway) and B by
    # pad_left + 1; strips are one contiguous slice + static skew, no gather.
    def arow(offset):                                   # (li,) A slice
        return jax.lax.dynamic_slice(tsa_p, (i0 + 1 + offset,), (li,))

    W = li + band

    def bstrips(offset):                                # (D, li) B windows
        w = jax.lax.dynamic_slice(tsb_p,
                                  (i0 + k0 + pad_left + 1 + offset,), (W,))
        return _unskew(w, band, li)

    tim = arow(m - 1)[None, :]                          # A[i+m-1]
    tip = arow(-1)[None, :]                             # A[i-1]
    tjm = bstrips(m - 1)                                # B[j+m-1]
    tjp = bstrips(-1)                                   # B[j-1]
    delta = (tim - tjm) ** 2 - (tip - tjp) ** 2
    delta = jnp.where(valid & (i[None, :] >= 1) & (j >= 1), delta, 0.0)
    d2 = d20[:, None] + jnp.cumsum(delta, axis=1)
    neg = jnp.where(valid, -jnp.maximum(d2, 0.0), -jnp.inf)

    neg_best, d_win = _row_harvest(neg)
    idx = jnp.where(jnp.isfinite(neg_best),
                    (i + k0 + d_win).astype(jnp.int32), -1)
    win = win_i = None
    if harvest_cols:
        win, win_i = _col_window(neg, -jnp.inf)
        win_i = jnp.where(jnp.isfinite(win), win_i + i0, -1)
    return neg_best.astype(jnp.float32), idx, win, win_i, i0


def _nonnorm_padded_series(ts_a, ts_b, band: int, li: int):
    """Pad raw series so the nonnorm band's slices (rows at i0 - 1, strips at
    i0 + k0 - 1 .. + m - 1 + li + band) stay in bounds; pad reads are masked
    before any harvest. Returns (pad_left, A_padded, B_padded); as in
    `_ab_padded_streams`, the row clamp keeps i0 + k0 >= 1 - band."""
    pad_left = band
    return (pad_left, jnp.pad(ts_a, (1, li + 1)),
            jnp.pad(ts_b, (pad_left + 1, li + 2 * band + 1)))


@partial(jax.jit, static_argnums=(2, 3, 4), static_argnames=("two_sided",))
def ab_join_nonnorm(ts_a: jax.Array, ts_b: jax.Array, window: int,
                    exclusion: int = 0, band: int = DEFAULT_BAND, *,
                    two_sided: bool = True):
    """Exact non-normalized AB join -> (dist_a (l_a,), idx_a, dist_b (l_b,),
    idx_b) — both sides from one signed-diagonal sweep (dist_b/idx_b are
    None with `two_sided=False`, which skips the column harvest; A's
    profile needs only the row side).

    Same signed-diagonal streaming as the z-normalized AB engine — including
    the row clamp — with the raw-distance recurrence of
    `band_rowmin_nonnorm`. With exclusion == 0 the whole signed space is one
    span (diagonal k = 0 evaluated once).
    """
    from repro.core.zstats import sliding_dot

    m = int(window)
    excl = int(exclusion)
    ts_a = jnp.asarray(ts_a, jnp.float32)
    ts_b = jnp.asarray(ts_b, jnp.float32)
    # distances are invariant under a COMMON shift of both series; removing
    # the shared level keeps the f32 seeds (ssq + ssq - 2*qt) well-conditioned
    # on offset-heavy data (per-series shifts would change the answer).
    c = 0.5 * (jnp.mean(ts_a) + jnp.mean(ts_b))
    ts_a = ts_a - c
    ts_b = ts_b - c
    la = ts_a.shape[0] - m + 1
    lb = ts_b.shape[0] - m + 1

    def ssq(ts):
        csq = jnp.concatenate([jnp.zeros((1,), ts.dtype), jnp.cumsum(ts * ts)])
        return csq[m:] - csq[:-m]

    ssq_a, ssq_b = ssq(ts_a), ssq(ts_b)
    qt_pos = sliding_dot(ts_a[:m], ts_b)                # <A_0, B_k>, (l_b,)
    qt_neg = sliding_dot(ts_b[:m], ts_a)                # <A_i, B_0>, (l_a,)
    d20_pos = ssq_a[0] + ssq_b - 2.0 * qt_pos           # k >= 0 seeds
    d20_neg = ssq_a[1:] + ssq_b[0] - 2.0 * qt_neg[1:]   # k = -1..-(l_a-1)
    d20s = jnp.concatenate([d20_neg[::-1], d20_pos])

    pad_l = la - 1
    li = ab_row_tile(la, lb, band)
    padded = _nonnorm_padded_series(ts_a, ts_b, band, li)

    def span(k_lo, width, k_hi):
        n_bands = -(-width // band)

        def body(carry, b):
            rows, col = carry
            start = k_lo + b * band
            ra, ia, win, wi, i0 = band_rowmin_nonnorm_ab(
                ts_a, ts_b, d20s, m, start, band, k_hi=k_hi,
                harvest_cols=two_sided, padded=padded)
            rows = rows.merge_window(ra, ia, i0)
            if two_sided:
                col = col.merge_window(win, wi, start + i0 + pad_l)
            return (rows, col), None

        init = (ColState.empty(0, la, li, -jnp.inf),
                ColState.empty(pad_l, lb, li + 2 * band, -jnp.inf)
                if two_sided else None)
        (rows, col), _ = jax.lax.scan(body, init, jnp.arange(n_bands))
        return (rows.to_profile(0, la),
                col.to_profile(pad_l, lb) if two_sided else None)

    merged_a = ProfileState.empty(la, -jnp.inf)
    merged_b = ProfileState.empty(lb, -jnp.inf) if two_sided else None

    def merge(sa, sb):
        nonlocal merged_a, merged_b
        merged_a = merged_a.merge(sa)
        if two_sided:
            merged_b = merged_b.merge(sb)

    if excl == 0:
        merge(*span(jnp.int32(-(la - 1)), la - 1 + lb, lb))
    else:
        if la - excl > 0:
            merge(*span(jnp.int32(-(la - 1)), la - excl, -excl + 1))
        if lb - excl > 0:
            merge(*span(jnp.int32(excl), lb - excl, lb))

    def finish(st):
        dist = jnp.sqrt(jnp.maximum(-st.corr, 0.0))
        return jnp.where(jnp.isfinite(st.corr), dist, jnp.inf), st.index

    da, ia = finish(merged_a)
    db, ib = finish(merged_b) if two_sided else (None, None)
    return da, ia, db, ib


def top_discords(profile: jax.Array, index: jax.Array, k: int,
                 exclusion: int) -> jax.Array:
    """Indices of the k largest profile entries, greedily non-overlapping."""
    p = jnp.where(jnp.isfinite(profile), profile, -jnp.inf)
    picks = []
    for _ in range(k):
        i = jnp.argmax(p)
        picks.append(i)
        lo = jnp.maximum(i - exclusion, 0)
        span = 2 * exclusion + 1
        mask = (jnp.arange(p.shape[0]) >= lo) & (jnp.arange(p.shape[0]) < lo + span)
        p = jnp.where(mask, -jnp.inf, p)
    return jnp.stack(picks)


def top_motif(profile: jax.Array, index: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(i, j) of the best-matching pair (global min of the profile)."""
    i = jnp.argmin(profile)
    return i, index[i]
