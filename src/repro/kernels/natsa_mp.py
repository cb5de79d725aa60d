"""NATSA diagonal-streaming matrix-profile kernel (Pallas TPU), two-sided.

TPU adaptation of NATSA's in-HBM-logic processing unit:

  * the O(n) streams (df/dg/invn) are staged HBM→VMEM once per call and every
    per-cell update happens at VREG distance — the data-movement structure the
    paper builds silicon for;
  * NATSA's scalar covariance pipeline is re-associated into a lane-parallel
    CUMULATIVE SUM along the diagonal (a serial chain would idle the 8x128
    VPU);
  * a VMEM scratch carries the covariance of every diagonal across row tiles,
    so each stream element is touched exactly once per diagonal band — the
    kernel analogue of NATSA PUs' private diagonal registers;
  * the kernel emits BOTH profile sides from the single sweep: the row-max
    (+ argmax) per row tile, and the column-max harvested from the very same
    (DT, IT) correlation tile via an in-tile diagonal re-gather — each
    sublane's row is a STATIC shift by its diagonal offset, so the gather is
    a stack of concatenations, and the (IT+DT)-wide column window is folded
    into a column accumulator with one dynamic-slice read-modify-max
    (scatter-free; TPUs have no cheap scatter-min).

The column accumulator is BANKED: instead of one full-length VMEM block
(which cannot scale past VMEM for long series), the output is a
(n_banks, col_tile) array whose rows cover the flat column space at stride
`col_tile - (IT+DT)` — overlapping just enough that every tile's (IT+DT)-wide
window fits entirely inside the single bank `s // stride` (s the window's
flat start). The banks stay in HBM; the kernel keeps ONE bank in a VMEM
scratch and swaps it with explicit DMAs whenever a grid step's window moves
to another bank (write the old bank back, read the new one). A blocked
output could not do this: the grid revisits a bank after the row loop wraps,
and a revisited output block is not re-read from HBM. A host-side reduction
(`reduce_col_banks`) max-merges the overlapped banks back into the flat
profile. Banks start as NEG/-1 through input/output aliasing.
`col_tile=None` degenerates to a single full-length bank (small series).

The kernel is TWO-SERIES: the i side (rows, series A) and the j side
(diagonal strips, series B) are independent stream sets, and the diagonal
offset `k_start` is SIGNED, covering the rectangular AB diagonal space
k = j - i in [-(l_a-1), l_b). Negative diagonals need no special recurrence:
the j-side streams are zero-PREPADDED by `jpad`, so df_j/dg_j gathers before
a diagonal's start cell return 0, the masked cumsum carries the seed
covariance (CrossStats.cov0s) forward unchanged, and validity masking
(jpos >= 0) hides the dead cells. The column outputs use the same shifted
indexing: column j of the rectangle accumulates at flat position j + jpad.
A self-join is the case where both stream sets alias the same arrays,
k_start = excl and jpad = 0 — its column harvest IS the lower triangle, so
one launch yields the complete profile.

Grid: (n_row_tiles, n_diag_tiles), diag innermost so the output row block is
revisited consecutively (the d == 0 step writes NEG/-1, then read-modify-max
accumulation), while the covariance scratch row for each diag tile persists
across the outer row loop. A column bank is revisited consecutively within
one row tile and re-fetched when the row loop wraps the bank index back down
(correct on the sequential TPU grid; the wrap costs one HBM round-trip per
bank per row tile).

Only LIVE grid steps do work. A tile is dead when none of its cells passes
the geometric mask: it lies wholly past column l_j - 1 (the self-join's
lower-right triangle, half its grid, and the AB suffix) or wholly before
column 0 (the AB prefix over the zero prepad). `live_diag_tiles` gives each
row tile's one run of live diag tiles. A dead step computes nothing, leaves
the diagonal registers and the resident bank alone, and its j-side index
maps are clamped to the nearest live tile of its row, so the pipeline sees
an unchanged block index and issues no DMA. Skipping is exact: past the end,
no later row tile has a live cell on that diag tile (columns only grow with
the row), so its registers are never read again; before column 0, computing
would add exact zeros from the prepad to the seeds; and a dead tile offers
only NEG/-1, which the strict `>` accumulators never take.

The j-side streams are DOUBLE-BUFFERED diagonal slabs, not whole-array VMEM
residents: each of df_j/dg_j/invn_j is passed TWICE with (1, JB)-blocked
specs whose index maps select consecutive blocks `s // JB` and
`s // JB + 1` (s the tile's flat window start, JB = it+dt rounded up to the
lane width). The pair concatenates in-kernel into one contiguous 2*JB
window covering every strip of the tile, so the VMEM working set of the j
side is two JB blocks however long the series grows — and Pallas's grid
pipeline prefetches the NEXT tile's blocks while the current tile computes
(multi-buffered BlockSpecs), the software shape of NATSA's
stream-while-compute PU front end. Consecutive diag steps mostly revisit
the same block pair, which the pipeline recognizes and skips re-fetching.

Layout note: every stream is a (1, N) row — Mosaic tiles 1-D refs at 1024
elements and refuses unaligned dynamic 1-D loads, while a (1, it) block of
a (1, N) array is legal (its sublane dim is the full array's). Tiles are
(DT, IT) with diagonals on sublanes (in reversed order, DT a power of two)
and rows on lanes; IT is a multiple of 128. Per-sublane shifts (the j-side
strips, the column re-gather) are ONE stride-1 lane rotate each
(`pltpu.roll(..., stride=1)`), never unaligned slices; the column
re-gather first restores natural sublane order. The diagonal registers live in a (DT, n_diags) VMEM scratch —
diagonal tile d is lane d — seeded from HBM by one DMA at the first step.
Streams may arrive in a REDUCED dtype (the plan's stream precision, e.g.
bf16 — that is the HBM traffic the roofline model charges); every
arithmetic step upcasts to f32 right after the VMEM loads, and the
covariance scratch/outputs stay f32 (the plan layer rejects
accum="float64" for this backend). `interpret=None` (the default) runs the
interpreter on the CPU backend only and the compiled kernel on a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG = -2.0  # correlations live in [-1, 1]

LANE = 128  # TPU lane width; JB blocks are multiples of this


def _round_up(x: int, k: int) -> int:
    return -(-x // k) * k


def j_block(it: int, dt: int) -> int:
    """Width of one j-side stream block: the (it+dt)-wide tile window,
    rounded up to the lane width so blocked loads stay aligned. Any tile's
    window [s, s+it+dt) then spans at most the two consecutive blocks
    s // JB and s // JB + 1."""
    return _round_up(it + dt, LANE)


def rmw_width(it: int, dt: int) -> int:
    """Lanes of the lane-aligned column window one grid step
    read-modify-maxes: the (it+dt)-wide candidates may start anywhere
    inside a 128-lane tile."""
    return _round_up(it + dt + LANE - 1, LANE)


def _cumsum_lanes(x, it: int):
    """Inclusive prefix sum along lanes (axis=1) as a Hillis-Steele
    log-step doubling of masked lane rotates — Mosaic has no cumsum
    primitive, and log2(IT) shifted vector adds keep the whole scan at
    VREG distance (the re-association note in the module docstring)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    k = 1
    while k < it:
        x = x + jnp.where(lane >= k, pltpu.roll(x, k, 1), 0.0)
        k *= 2
    return x


def _reverse_sublanes(x):
    """Row r of the result is row R-1-r of `x` (R rows, a power of two):
    R-1-r = r XOR (R-1), one butterfly stage per bit — two sublane rotates
    and a select each. Mosaic lowers no `rev`; this is pure data movement,
    so it is exact."""
    n = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    k = 1
    while k < n:
        # x[r - k] where bit k of r is set, x[r + k] where it is clear
        x = jnp.where((row & k) != 0, pltpu.roll(x, k, 0),
                      pltpu.roll(x, n - k, 0))
        k *= 2
    return x


def live_diag_tiles(i, *, it: int, dt: int, k_start: int, k_end: int,
                    l_i: int, l_j: int, n_diags: int, xp=np):
    """(d_lo, d_end): row tile `i`'s live diag tiles are d_lo <= d < d_end
    (none where d_end <= d_lo); every other tile of the row is dead.

    Tile (i, d) keeps rows i0 + [0, ni) and diagonals k0 + [0, nd) under the
    kernel's mask, with i0 = i*it, k0 = k_start + d*dt, ni = min(it, l_i -
    i0), nd = min(dt, k_end - k0). Its cells' columns are the consecutive
    run i0 + k0 + [0, ni + nd - 1), so it holds a valid cell iff ni, nd >= 1
    and the run meets [0, l_j):
      i0 + k0 < l_j                            (not past the end)
      i0 + ni - 2 + min(k0 + dt, k_end) >= 0   (not before column 0)
    The first bounds d from above, the second from below. One inequality
    for the kernel and its index maps (`i` a traced scalar, xp=jnp) and for
    the plan's counters (`i` an integer array, xp=np); every division below
    has a non-negative numerator."""
    i0 = i * it
    ni = xp.minimum(it, l_i - i0)
    d_lo = (xp.maximum(2 - i0 - ni - dt - k_start, 0) + dt - 1) // dt
    d_end = xp.minimum((xp.maximum(l_j - i0 - k_start, 0) + dt - 1) // dt,
                       min(n_diags, -(-max(k_end - k_start, 0) // dt)))
    rows_ok = (ni >= 1) & (i0 + ni + k_end >= 2)
    return d_lo, xp.where(rows_ok, d_end, 0)


def live_tiles(n_rows: int, n_diags: int, **geometry) -> int:
    """Live grid steps of one `rowmax_profile_ab` launch: the (n_rows,
    n_diags) grid less the steps `live_diag_tiles` finds dead. `geometry`
    is the launch's it, dt, k_start, k_end, l_i, l_j."""
    d_lo, d_end = live_diag_tiles(np.arange(n_rows), n_diags=n_diags,
                                  **geometry)
    return int(np.maximum(d_end - d_lo, 0).sum())


def _kernel(df_row, dg_row, invn_row, df_j0, dg_j0, invn_j0,
            df_j1, dg_j1, invn_j1, seeds_hbm, _colc_init, _coli_init,
            out_corr, out_idx, colc_hbm, coli_hbm,
            carry, bank_c, bank_i, cur_bank, *, it: int, dt: int, jb: int,
            k_start: int, k_end: int, l_i: int, l_j: int, jpad: int,
            col_stride: int, n_rows: int, n_diags: int):
    i_idx = pl.program_id(0)
    d_idx = pl.program_id(1)

    # seed every diagonal register once (one DMA of the (DT, n_diags) seed
    # table); no column bank is resident yet
    @pl.when((i_idx == 0) & (d_idx == 0))
    def _start():
        pltpu.sync_copy(seeds_hbm, carry)
        cur_bank[0] = -1

    # the row block starts empty: its live steps read-modify-max into it,
    # and a row tile may have no live step at all
    @pl.when(d_idx == 0)
    def _init():
        out_corr[...] = jnp.full(out_corr.shape, NEG, jnp.float32)
        out_idx[...] = jnp.full(out_idx.shape, -1, jnp.int32)

    d_lo, d_end = live_diag_tiles(i_idx, it=it, dt=dt, k_start=k_start,
                                  k_end=k_end, l_i=l_i, l_j=l_j,
                                  n_diags=n_diags, xp=jnp)

    @pl.when((d_idx >= d_lo) & (d_idx < d_end))
    def _live():
        i0 = i_idx * it
        k0 = k_start + d_idx * dt      # signed diagonal offset of this tile
        s = i0 + k0 + jpad             # flat start of this tile's window

        # swap the resident column bank when this tile's window moved to
        # another one: write the old bank back, read the new one (see
        # module docstring)
        bank = s // col_stride

        @pl.when(bank != cur_bank[0])
        def _swap():
            @pl.when(cur_bank[0] >= 0)
            def _write_back():
                old = cur_bank[0]
                pltpu.sync_copy(bank_c, colc_hbm.at[old])
                pltpu.sync_copy(bank_i, coli_hbm.at[old])

            pltpu.sync_copy(colc_hbm.at[bank], bank_c)
            pltpu.sync_copy(coli_hbm.at[bank], bank_i)
            cur_bank[0] = bank

        # this diag tile's registers: lane d of the lane-aligned (DT, 128)
        # slab
        c0 = pl.multiple_of((d_idx // LANE) * LANE, LANE)
        slab = carry[:, pl.ds(c0, LANE)]
        mine = (jax.lax.broadcasted_iota(jnp.int32, (dt, LANE), 1)
                == d_idx % LANE)
        cov_in = jnp.max(jnp.where(mine, slab, -jnp.inf), axis=1,
                         keepdims=True)

        # reduced-dtype streams upcast at VREG distance — HBM moved the
        # narrow bytes, the VPU computes wide
        dfi = df_row[...].astype(jnp.float32)  # (1, IT)
        dgi = dg_row[...].astype(jnp.float32)
        invni = invn_row[...].astype(jnp.float32)

        # j-side strips for each diagonal in the tile: diagonal dd covers
        # [i0+k0+dd, i0+k0+dd+IT) — all of them inside the concatenated
        # double-buffer window [p*JB, (p+2)*JB), p = s // JB, at lanes
        # local + dd onward. Lane X + t of diagonal dd's row must hold
        # window lane local + dd + t: a right rotate (pltpu.roll's
        # direction) by X - local - dd, which lies in [0, 2*JB) for
        # X = 2*JB - IT (no wrap). Diagonals sit on sublanes in REVERSED
        # order (sublane r holds dd = DT-1-r), so the shift grows by one per
        # sublane: one stride-1 rotate. (A stride of 2*JB - 1, i.e. -1
        # modulo the width, would keep the natural order, but the chip's
        # strided rotate does not wrap its stride that way.)
        local = s - (s // jb) * jb
        x0 = 2 * jb - it
        rshift = x0 - local - (dt - 1)

        def strips(r0, r1):
            w = jnp.concatenate([r0[...], r1[...]],
                                axis=1).astype(jnp.float32)
            w = pltpu.roll(jnp.broadcast_to(w, (dt, 2 * jb)), rshift, 1,
                           stride=1, stride_axis=0)
            return w[:, x0:]                                     # (DT, IT)

        dfj = strips(df_j0, df_j1)
        dgj = strips(dg_j0, dg_j1)
        invnj = strips(invn_j0, invn_j1)

        ii = jax.lax.broadcasted_iota(jnp.int32, (dt, it), 1)   # row offset
        dd = dt - 1 - jax.lax.broadcasted_iota(jnp.int32, (dt, it),
                                               0)              # diag offset

        delta = dfi * dgj + dfj * dgi                              # (DT, IT)
        cov = cov_in + _cumsum_lanes(delta, it)
        last = jnp.max(jnp.where(ii == it - 1, cov, -jnp.inf), axis=1,
                       keepdims=True)                              # (DT, 1)
        carry[:, pl.ds(c0, LANE)] = jnp.where(mine, last, slab)

        corr = cov * invni * invnj

        jpos = i0 + ii + k0 + dd                                   # signed j
        ipos = i0 + ii
        # invn < 0 is the missing-data sentinel
        # (zstats.compute_stats_host): pairs touching a masked subsequence
        # are excluded like out-of-range cells. The cumsum above is
        # untouched — masked cells still carry the recurrence to later valid
        # cells on the diagonal.
        valid = ((jpos >= 0) & (jpos < l_j) & (ipos < l_i)
                 & (k0 + dd < k_end) & (invni >= 0) & (invnj >= 0))
        corr = jnp.where(valid, corr, NEG)

        # plain max + equality-recovered arg: cheaper than a variadic argmax
        # reduce on both the interpret (XLA CPU) and Mosaic paths; the arg
        # reduce runs in f32 (Mosaic has no integer reductions) — diagonal
        # offsets are < DT, exactly representable
        tile_best = jnp.max(corr, axis=0, keepdims=True)           # (1, IT)
        best_d = jnp.max(
            jnp.where(corr == tile_best, dd.astype(jnp.float32), -1.0),
            axis=0, keepdims=True).astype(jnp.int32)
        tile_idx = i0 + ii[:1] + k0 + best_d
        tile_idx = jnp.where(tile_best > NEG, tile_idx, -1)

        prev = out_corr[...]
        take = tile_best > prev
        out_corr[...] = jnp.where(take, tile_best, prev)
        out_idx[...] = jnp.where(take, tile_idx, out_idx[...])

        # -- column harvest of the SAME tile ---------------------------------
        # the tile covers columns j in [i0+k0, i0+k0+IT+DT); the best value
        # ending at local column t is max_dd corr[dd, t - dd] — row dd
        # right-rotated by dd (back in natural sublane order, one strided
        # rotate of the NEG-padded tile), then one read-modify-max into the
        # resident bank
        wr = rmw_width(it, dt)
        shifted = pltpu.roll(
            jnp.concatenate([_reverse_sublanes(corr),
                             jnp.full((dt, wr - it), NEG, jnp.float32)],
                            axis=1), 0, 1, stride=1, stride_axis=0)  # (DT, WR)
        col_best = jnp.max(shifted, axis=0, keepdims=True)           # (1, WR)
        ddw = jax.lax.broadcasted_iota(jnp.int32, (dt, wr),
                                       0).astype(jnp.float32)
        col_d = jnp.max(jnp.where(shifted == col_best, ddw, -1.0), axis=0,
                        keepdims=True).astype(jnp.int32)
        col_i = i0 + jax.lax.broadcasted_iota(jnp.int32, (1, wr), 1) - col_d
        col_i = jnp.where(col_best > NEG, col_i, -1)

        # the window starts at `local` inside the bank: read-modify-max the
        # lane-aligned WR-wide slab holding it, with the candidates
        # right-rotated into place (their NEG/-1 tail wraps around as
        # max-merge no-ops)
        local = s - bank * col_stride
        a0 = pl.multiple_of((local // LANE) * LANE, LANE)
        cand_c = pltpu.roll(col_best, local - a0, 1)
        cand_i = pltpu.roll(col_i, local - a0, 1)
        prev_c = bank_c[:, pl.ds(a0, wr)]
        prev_i = bank_i[:, pl.ds(a0, wr)]
        take_c = cand_c > prev_c
        bank_c[:, pl.ds(a0, wr)] = jnp.where(take_c, cand_c, prev_c)
        bank_i[:, pl.ds(a0, wr)] = jnp.where(take_c, cand_i, prev_i)

    # write back the resident bank, wherever the last live step left it
    @pl.when((i_idx == n_rows - 1) & (d_idx == n_diags - 1)
             & (cur_bank[0] >= 0))
    def _finish():
        pltpu.sync_copy(bank_c, colc_hbm.at[cur_bank[0]])
        pltpu.sync_copy(bank_i, coli_hbm.at[cur_bank[0]])


def col_bank_layout(col_len: int, it: int, dt: int,
                    col_tile: int | None) -> tuple[int, int, int]:
    """(n_banks, bank_width, stride) of the banked column accumulator.

    Every tile window is (it+dt) wide and starts at some flat s in
    [0, col_len - it - dt]; banks of width `col_tile` at stride
    `col_tile - (it+dt)` guarantee window s lives wholly inside bank
    s // stride. col_tile=None collapses to one full-length bank."""
    w = it + dt
    if col_tile is None:
        return 1, col_len, col_len
    if col_tile <= w:
        raise ValueError(f"col_tile={col_tile} must exceed the tile window "
                         f"it+dt={w}")
    stride = col_tile - w
    n_banks = max(1, (max(col_len - w, 0)) // stride + 1)
    return n_banks, col_tile, stride


def reduce_col_banks(colc: jax.Array, coli: jax.Array, stride: int,
                     out_len: int) -> tuple[jax.Array, jax.Array]:
    """Max-merge overlapping (n_banks, bank_width) accumulators back into the
    flat (out_len,) column profile — the host-side half of the banking
    scheme. ONE implementation serves kernel and engine: this delegates to
    `BankedColState.to_flat`, so stride/truncation/tie semantics cannot
    drift between the two (the mirror invariant the tiling tests pin).
    Imported lazily — core.matrix_profile never imports kernels, so there
    is no cycle."""
    from repro.core.matrix_profile import BankedColState

    return BankedColState(corr=colc, index=coli,
                          stride=stride).to_flat(out_len, NEG)


@functools.partial(jax.jit, static_argnames=(
    "it", "dt", "k_start", "k_end", "l_i", "l_j", "jpad", "col_tile",
    "return_banked", "interpret"))
def rowmax_profile_ab(df_i, dg_i, invn_i, df_j, dg_j, invn_j, cov0, *,
                      it: int, dt: int, k_start: int, k_end: int,
                      l_i: int, l_j: int, jpad: int = 0,
                      col_tile: int | None = None,
                      return_banked: bool = False,
                      interpret: bool | None = None):
    """Two-sided harvest over signed diagonals
    [k_start, k_start + len(cov0)) ∩ [k_start, k_end) of the AB rectangle,
    in ONE launch.

    Inputs are the padded streams:
      df_i/dg_i/invn_i : (n_row_tiles*IT,) — A-side row streams
      df_j/dg_j/invn_j : (JP,) — B-side, zero-prepadded by `jpad` with
          JP >= n_row_tiles*IT + k_start + n_diag_tiles*DT + jpad
      cov0             : (n_diag_tiles*DT,) f32 — CrossStats.cov0s slice
    Streams may be any float dtype (the plan's stream precision — bf16
    halves the HBM bytes per cell); the kernel upcasts to f32 in VMEM.
    Returns (corr (n_row_tiles*IT,), idx, col_corr (col_len,), col_idx):
    `idx` is the best j in B per row of A (-1 where no diagonal covers the
    row); `col_corr[j + jpad]` is the best correlation ending at column j of
    B with `col_idx` the winning row i in A (-1 where untouched), and
    col_len = max(n_row_tiles*IT + k_start + n_diag_tiles*DT, l_j) + jpad.

    `col_tile` bounds the column accumulator's VMEM block: the kernel
    accumulates into (n_banks, col_tile) overlapped banks (see
    `col_bank_layout`), one resident at a time, and the flat profile is
    recovered by
    `reduce_col_banks`. With `return_banked=True` the raw banks and their
    stride are returned instead — (corr, idx, banks_c, banks_i, stride) —
    for callers that reduce themselves (tests assert the block bound).
    """
    rows = df_i.shape[0]
    n_rows = rows // it
    assert rows % it == 0, (rows, it)
    n_diags = cov0.shape[0] // dt
    assert cov0.shape[0] % dt == 0
    assert dt & (dt - 1) == 0, f"dt={dt} must be a power of two"
    jp = df_j.shape[0]
    # the accumulators must cover every tile's store window AND the full
    # column space [0, l_j) + jpad — a short negative-only span (e.g. the
    # self-join-with-exclusion case) can have tile windows ending before
    # column l_j - 1
    col_len = max(n_rows * it + k_start + n_diags * dt + jpad, l_j + jpad)
    assert jp >= col_len, (jp, n_rows, it, k_start, n_diags, dt, jpad, l_j)
    assert k_start + jpad >= 0, (k_start, jpad)
    n_banks, bank_w, stride = col_bank_layout(col_len, it, dt, col_tile)

    # double-buffered j side: zero-extend the streams so the LAST tile's
    # second block (index s_max // JB + 1) is still in range, then hand the
    # same arrays in twice under consecutive block index maps
    jb = j_block(it, dt)
    s_max = (n_rows - 1) * it + k_start + (n_diags - 1) * dt + jpad
    j_len = (s_max // jb + 2) * jb
    if j_len > jp:
        df_j = jnp.pad(df_j, (0, j_len - jp))
        dg_j = jnp.pad(dg_j, (0, j_len - jp))
        invn_j = jnp.pad(invn_j, (0, j_len - jp))

    # every blocked ref is a (1, N) row with a (1, lane-multiple) block —
    # legal for Mosaic because the block's sublane dim is the array's own
    grid = (n_rows, n_diags)
    row_spec = pl.BlockSpec((1, it), lambda i, d: (0, i))
    geometry = dict(it=it, dt=dt, k_start=k_start, k_end=k_end, l_i=l_i,
                    l_j=l_j, n_diags=n_diags)

    def j_block_of(i, d):
        # a dead step asks for the block of the nearest live step of its row
        # (of one fixed tile if the row has none): an unchanged block index,
        # so the pipeline fetches nothing for it
        d_lo, d_end = live_diag_tiles(i, **geometry, xp=jnp)
        d = jnp.maximum(jnp.minimum(d, d_end - 1),
                        jnp.minimum(d_lo, n_diags - 1))
        return (i * it + k_start + d * dt + jpad) // jb

    j_spec0 = pl.BlockSpec((1, jb), lambda i, d: (0, j_block_of(i, d)))
    j_spec1 = pl.BlockSpec((1, jb), lambda i, d: (0, j_block_of(i, d) + 1))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)

    # seed table: diagonal tile d's DT seeds are lane d, in the kernel's
    # reversed sublane order (sublane r seeds diagonal DT-1-r)
    nd_pad = _round_up(n_diags, LANE)
    seeds = jnp.pad(cov0.astype(jnp.float32).reshape(n_diags, dt)[:, ::-1].T,
                    ((0, 0), (0, nd_pad - n_diags)))
    # banks live in HBM, padded so the kernel's lane-aligned WR-wide slab
    # at any window start stays inside the row; the NEG/-1 fill arrives
    # through aliasing
    bank_wp = _round_up(stride, LANE) + rmw_width(it, dt)
    colc_init = jnp.full((n_banks, 1, bank_wp), NEG, jnp.float32)
    coli_init = jnp.full((n_banks, 1, bank_wp), -1, jnp.int32)

    row2 = functools.partial(jnp.reshape, shape=(1, -1))
    kernel = functools.partial(_kernel, it=it, dt=dt, jb=jb, k_start=k_start,
                               k_end=k_end, l_i=l_i, l_j=l_j, jpad=jpad,
                               col_stride=stride, n_rows=n_rows,
                               n_diags=n_diags)
    corr, idx, colc, coli = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row_spec, row_spec, row_spec,
                  j_spec0, j_spec0, j_spec0,
                  j_spec1, j_spec1, j_spec1, hbm, hbm, hbm],
        out_specs=[row_spec, row_spec, hbm, hbm],
        out_shape=[jax.ShapeDtypeStruct((1, n_rows * it), jnp.float32),
                   jax.ShapeDtypeStruct((1, n_rows * it), jnp.int32),
                   jax.ShapeDtypeStruct((n_banks, 1, bank_wp), jnp.float32),
                   jax.ShapeDtypeStruct((n_banks, 1, bank_wp), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((dt, nd_pad), jnp.float32),
                        pltpu.VMEM((1, bank_wp), jnp.float32),
                        pltpu.VMEM((1, bank_wp), jnp.int32),
                        pltpu.SMEM((1,), jnp.int32)],
        input_output_aliases={10: 2, 11: 3},
        # the diagonal registers and the resident bank carry state from
        # step to step: both grid axes run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=resolve_interpret(interpret),
        name="natsa_rowmax",
    )(row2(df_i), row2(dg_i), row2(invn_i), row2(df_j), row2(dg_j),
      row2(invn_j), row2(df_j), row2(dg_j), row2(invn_j), seeds,
      colc_init, coli_init)
    corr, idx = corr[0], idx[0]
    colc, coli = colc[:, 0, :bank_w], coli[:, 0, :bank_w]
    if return_banked:
        return corr, idx, colc, coli, stride
    flat_c, flat_i = reduce_col_banks(colc, coli, stride, col_len)
    return corr, idx, flat_c, flat_i


def rowmax_profile(df, dg, invn, cov0, *, it: int, dt: int, excl: int, l: int,
                   col_tile: int | None = None,
                   interpret: bool | None = None):
    """Self-join entry: two-sided harvest over diagonals k in [excl, l) — the
    special case of `rowmax_profile_ab` where both stream sets alias one
    series. The column side (col_corr[:l], col_idx[:l]) is the lower
    triangle; merged with the row side it is the COMPLETE profile from one
    launch. `col_tile` bounds the column accumulator's VMEM block (banked).

    df/dg/invn : (LP,) f32, LP >= n_row_tiles*IT + excl + n_diag_tiles*DT
    cov0       : (n_diag_tiles*DT,) f32 — cov(0, excl+d), padded
    """
    rows = (-(-l // it)) * it
    return rowmax_profile_ab(
        df[:rows], dg[:rows], invn[:rows], df, dg, invn, cov0,
        it=it, dt=dt, k_start=excl, k_end=l, l_i=l, l_j=l, jpad=0,
        col_tile=col_tile, interpret=interpret)
