"""Jitted public wrapper for the NATSA matrix-profile kernel.

Pipeline (mirrors the paper's Fig. 1 dataflow):
  1. host-side f64 stream precompute (zstats.compute_stats_host) — data
     ingestion; TPUs have no f64 and NATSA likewise precomputes streams once;
  2. pad streams so every in-kernel dynamic load is in-bounds;
  3. ONE pallas_call -> BOTH profile sides: the row-max half plus the
     column-max half harvested from the same tiles (see natsa_mp._kernel's
     in-tile diagonal re-gather);
  4. merge the two sides in correlation space, convert to z-normalized
     distance.

The old pipeline ran a second reversed-series launch for the column half —
twice the streamed bytes, twice the stats precompute, same answer.

`interpret=None` (default) runs the kernel body in the Pallas interpreter on
the CPU backend and the compiled Mosaic kernel on a TPU
(`kernels.resolve_interpret`).
"""

from __future__ import annotations


import numpy as np
import jax
import jax.numpy as jnp

from repro.core.zstats import (
    CrossStats, ZStats, compute_stats_host,
)
from repro.kernels import DEFAULT_DT, DEFAULT_IT, natsa_mp

NEG = natsa_mp.NEG


def _pad_streams(stats: ZStats, it: int, dt: int, excl: int):
    """Pad streams; returns (df, dg, invn, cov0p, n_rows, n_diags, l)."""
    l = stats.n_subsequences
    n_rows = -(-l // it)
    n_diag_total = max(l - excl, 1)
    n_diags = -(-n_diag_total // dt)
    lp = n_rows * it + excl + n_diags * dt
    pad = lp - l

    def p(x):
        return jnp.pad(x, (0, pad))

    # seeds feed the f32 covariance scratch directly — always widened here,
    # whatever (possibly reduced) dtype the streams arrive in
    cov0p = jnp.pad(stats.cov0.astype(jnp.float32)[excl:],
                    (0, n_diags * dt - n_diag_total))
    return (p(stats.df), p(stats.dg), p(stats.invn), cov0p,
            n_rows, n_diags, l)


# Column accumulators below this flat length fit one VMEM block comfortably;
# longer spaces are banked into `auto_col_tile`-sized blocks so the working
# set stays bounded however long the series grows.
AUTO_COL_BANK_MIN = 8192


def auto_col_tile(col_len: int, it: int, dt: int,
                  col_tile: int | None) -> int | None:
    """Resolve the col_tile policy: None = auto (bank long spaces into
    max(4096, 2*(it+dt)) blocks rounded up to the lane width — Mosaic's
    compiled path needs lane-aligned bank blocks — keep short ones
    unbanked), 0 = force one full-length bank, any other int = explicit
    block bound."""
    if col_tile == 0:
        return None
    if col_tile is not None:
        return int(col_tile)
    if col_len <= AUTO_COL_BANK_MIN:
        return None
    return -(-max(4096, 2 * (it + dt)) // 128) * 128


def rowmax_from_stats(stats: ZStats, *, excl: int, it: int = DEFAULT_IT,
                      dt: int = DEFAULT_DT,
                      col_tile: int | None = None,
                      interpret: bool | None = None):
    """Two-sided self-join harvest via ONE kernel launch.

    Returns (corr (l,), idx, col_corr (l,), col_idx): the row-max half
    (upper triangle, j > i) and the column-max half (lower triangle, i < j)
    of the same swept cells. Their merge is the complete profile.
    `col_tile` bounds the kernel's column-accumulator block (see
    `auto_col_tile` for the default policy).
    """
    df, dg, invn, cov0p, n_rows, n_diags, l = _pad_streams(stats, it, dt, excl)
    ct = auto_col_tile(n_rows * it + excl + n_diags * dt, it, dt, col_tile)
    corr, idx, colc, coli = natsa_mp.rowmax_profile(
        df, dg, invn, cov0p, it=it, dt=dt, excl=excl, l=l, col_tile=ct,
        interpret=interpret)
    return corr[:l], idx[:l], colc[:l], coli[:l]


def _merge_corr(corr_a, idx_a, corr_b, idx_b):
    take = corr_b > corr_a
    return (jnp.where(take, corr_b, corr_a),
            jnp.where(take, idx_b, idx_a).astype(jnp.int32))


def natsa_matrix_profile(ts, window: int, *, exclusion: int | None = None,
                         it: int = DEFAULT_IT, dt: int = DEFAULT_DT,
                         col_tile: int | None = None,
                         interpret: bool | None = None, k: int = 1,
                         harvest: str = "merged",
                         precision=None):
    """Full matrix profile via the Pallas kernel -> `ProfileResult` (the
    left/right split — the kernel's column/row halves — finishes lazily
    from the launch's retained halves on first access; `harvest="both"`
    materializes it eagerly).

    Thin entry: builds a kernel-backend `SweepPlan` (the planner pins the
    `auto_col_tile` banking choice into the plan) and executes it — one
    launch, one pass over the streams: no reversed-series stats, no second
    launch. Matches core.matrix_profile / the brute-force oracle (tests
    enforce it). `k > 1` PLANS A FALLBACK to the band engine (the kernel's
    banked VMEM accumulators are k = 1-only — gated in `plan_sweep`), so
    top-k requests still answer exactly, just not through Pallas.
    """
    from repro.core import plan as plan_mod
    from repro.core.result import build_result

    m = int(window)
    arr = np.asarray(ts)
    plan = plan_mod.plan_sweep(m, arr.shape[0] - m + 1, exclusion=exclusion,
                               backend="kernel", it=it, dt=dt,
                               col_tile=col_tile, interpret=interpret, k=k,
                               harvest=harvest, precision=precision)
    stats = compute_stats_host(arr, m, **plan_mod.stats_dtypes_for(plan))
    res = plan_mod.execute(plan, stats)
    return build_result(plan, res, stats)


# -- AB join through the kernel ----------------------------------------------


def _pad_streams_ab(cross: CrossStats, it: int, dt: int, s0: int, s1: int):
    """Pad A-side row streams and zero-prepad B-side full streams for the
    signed diagonal span [s0, s1). Returns the seven kernel inputs plus
    (n_rows, n_diags, jpad)."""
    la, lb = cross.l_a, cross.l_b
    n_rows = -(-la // it)
    n_total = max(s1 - s0, 1)
    n_diags = -(-n_total // dt)
    jpad = max(0, -s0)
    rows_len = n_rows * it

    def prow(x):
        return jnp.pad(x, (0, rows_len - la))

    # padded_j[p] = stream_b[p - jpad]; the zero prepad makes df/dg gathers
    # before a negative diagonal's start contribute nothing to the cumsum.
    # The kernel's column accumulators span max(jlen, jpad + lb) (see
    # rowmax_profile_ab), so the j streams must reach at least that far.
    jlen = max(rows_len + s0 + n_diags * dt + jpad, jpad + lb)
    back = max(jlen - jpad - lb, 0)

    def pj(x):
        return jnp.pad(x, (jpad, back))

    u = np.clip(np.arange(s0, s0 + n_diags * dt) + la - 1, 0, la + lb - 2)
    cov0p = jnp.take(cross.cov0s.astype(jnp.float32), jnp.asarray(u))
    return (prow(cross.a.df), prow(cross.a.dg), prow(cross.a.invn),
            pj(cross.b.df), pj(cross.b.dg), pj(cross.b.invn), cov0p,
            n_rows, n_diags, jpad)


def ab_rowmax_from_stats(cross: CrossStats, *, exclusion: int = 0,
                         it: int = DEFAULT_IT, dt: int = DEFAULT_DT,
                         col_tile: int | None = None,
                      interpret: bool | None = None):
    """Two-sided AB harvest via the kernel.

    With exclusion == 0 the whole signed space [-(l_a-1), l_b) is ONE kernel
    launch; an exclusion band splits it into a negative and a positive span.
    Returns (corr_a (l_a,), idx_a, corr_b (l_b,), idx_b) — A's profile over
    B and B's profile over A, harvested from the same sweep. `col_tile`
    bounds the column-accumulator block (`auto_col_tile` policy).
    """
    la, lb = cross.l_a, cross.l_b
    excl = int(exclusion)
    if excl == 0:
        spans = [(-(la - 1), lb)]
    else:
        spans = []
        if la - excl > 0:
            spans.append((-(la - 1), -excl + 1))
        if lb - excl > 0:
            spans.append((excl, lb))
    corr = jnp.full((la,), natsa_mp.NEG, jnp.float32)
    idx = jnp.full((la,), -1, jnp.int32)
    corr_b = jnp.full((lb,), natsa_mp.NEG, jnp.float32)
    idx_b = jnp.full((lb,), -1, jnp.int32)
    for s0, s1 in spans:
        (df_i, dg_i, invn_i, df_j, dg_j, invn_j, cov0p,
         n_rows, n_diags, jpad) = _pad_streams_ab(cross, it, dt, s0, s1)
        ct = auto_col_tile(
            max(n_rows * it + s0 + n_diags * dt + jpad, lb + jpad),
            it, dt, col_tile)
        c, ix, cc, ci = natsa_mp.rowmax_profile_ab(
            df_i, dg_i, invn_i, df_j, dg_j, invn_j, cov0p,
            it=it, dt=dt, k_start=s0, k_end=s1, l_i=la, l_j=lb, jpad=jpad,
            col_tile=ct, interpret=interpret)
        corr, idx = _merge_corr(corr, idx, c[:la], ix[:la])
        corr_b, idx_b = _merge_corr(corr_b, idx_b,
                                    cc[jpad:jpad + lb], ci[jpad:jpad + lb])
    return corr, idx, corr_b, idx_b


def natsa_ab_join(ts_a, ts_b, window: int, *, exclusion: int | None = None,
                  it: int = DEFAULT_IT, dt: int = DEFAULT_DT,
                  col_tile: int | None = None,
                  interpret: bool | None = None, return_b: bool = False,
                  k: int = 1, precision=None):
    """AB join via the Pallas kernel -> `ProfileResult`.

    With `return_b=True` the result eagerly carries B's profile against A
    (`.b_p`/`.b_i`) — the column harvest of the same launch, not a second
    join; without it `.b_p` finishes lazily from the launch's retained
    column half. Matches core.matrix_profile.ab_join / the brute-force oracle
    (tests enforce it). No exclusion zone by default — pass one only to
    recover the self-join as the A == B special case. The rectangle is
    swept with its SHORT side on the row axis (fewest computed tiles);
    outputs are mapped back, so callers never see the orientation. `k > 1`
    plans the band-engine fallback (see `natsa_matrix_profile`).
    """
    from repro.core import plan as plan_mod
    from repro.core.result import build_result

    m = int(window)
    a, b = np.asarray(ts_a), np.asarray(ts_b)
    plan = plan_mod.plan_sweep(m, a.shape[0] - m + 1, b.shape[0] - m + 1,
                               exclusion=exclusion, backend="kernel",
                               harvest="both" if return_b else "merged",
                               it=it, dt=dt, col_tile=col_tile,
                               interpret=interpret, k=k, precision=precision)
    # swap_ab: row tiles cover the SHORT side — an (l_a/it x (l_a+l_b)/dt)
    # grid shrinks to (l_b/it x (l_a+l_b)/dt), the kernel-side row clamp
    stats = plan_mod.cross_stats_for(plan, a, b)
    res = plan_mod.execute(plan, stats)
    return build_result(plan, res, stats)


VMEM_BYTES = 128 * 2**20 // 8   # ~16 MiB/core, keep ~50% headroom


def kernel_vmem_bytes(l: int, it: int, dt: int,
                      col_tile: int | None = None) -> int:
    """VMEM working set of one rowmax_profile call (full streams resident).

    The column accumulator contributes ONE (col_tile)-sized bank block when
    banked (the auto policy for long series) instead of the full flat
    length — the term that used to grow with l and cap series length."""
    lp = l + it + dt + 64
    ct = auto_col_tile(lp, it, dt, col_tile)
    full = 3 * lp * 4                      # df/dg/invn
    rows = 3 * it * 4                      # row blocks
    outs = 2 * it * (4 + 4)                # corr+idx blocks (rw)
    cols = (ct if ct is not None else lp) * (4 + 4)  # col bank block (rw)
    tile = 4 * dt * it * 4                 # dfj/dgj/invnj/delta working tile
    carry = (-(-(l) // dt)) * dt * 4       # cov scratch
    return full + rows + outs + cols + tile + carry


def hbm_bytes_per_cell(l: int, excl: int, it: int = DEFAULT_IT,
                       dt: int = DEFAULT_DT, *,
                       stream_bytes: int = 4) -> float:
    """Roofline model of HBM traffic per distance-matrix cell.

    ONE pass now computes both profile sides (the reversed second pass is
    gone), so the per-cell traffic of the streams is half the old scheme's
    while each cell yields two profile updates. `stream_bytes` is the
    per-element width of the df/dg/invn streams — the plan's stream
    precision (2 for bf16/f16 halves every stream term below; seeds,
    outputs and accumulators stay 4-byte). Two regimes (§Roofline-NATSA):
      * VMEM-resident (l small enough): every stream element crosses
        HBM->VMEM ONCE — bytes/cell ~ O(1/l) -> effectively free.
        This is the TPU realization of NATSA's near-data principle.
      * streamed (l beyond VMEM): the engine row-blocks the space; the
        j-side strips and the column-accumulator window are re-fetched once
        per (row-tile, diag-tile), so bytes/cell ~ c*(it+dt)/(it*dt) —
        driven down by larger tiles and narrower streams.
    Used by `kernel_roofline` and `launch.roofline.matrix_profile_roofline`.
    """
    n_rows = -(-l // it)
    n_diags = -(-(l - excl) // dt)
    cells = float(sum(l - k for k in range(excl, l)))
    f32 = 4
    sb = int(stream_bytes)
    if kernel_vmem_bytes(l, it, dt) <= VMEM_BYTES:
        total = (3 * (l + it + dt) * sb                 # streams, once
                 + n_diags * dt * f32                   # seeds
                 + n_rows * it * (f32 + 4) * 2          # row outputs rw
                 + (l + it + dt) * (f32 + 4) * 2)       # col accumulators rw
        return total / max(cells, 1.0)
    i_side = n_rows * it * 3 * sb                       # once per row tile
    j_side = n_rows * n_diags * (it + dt) * 3 * sb      # per (row, diag) tile
    outs = n_rows * n_diags * it * (f32 + 4) * 2        # rw of row corr+idx
    cols = n_rows * n_diags * (it + dt) * (f32 + 4) * 2  # rw of col window
    seeds = n_diags * dt * f32
    total = i_side + j_side + outs + cols + seeds       # single fused pass
    return total / max(cells, 1.0)


# per evaluated cell: 2 mul + 1 add (delta) + cumsum add + corr mul2 + the
# row max AND the column max/select it now feeds (two-sided harvest)
FLOPS_PER_CELL = 9.0


def kernel_roofline(l: int, excl: int, it: int, dt: int, *,
                    stream_bytes: int = 4) -> dict:
    """Compute- and memory-term seconds for the full profile at (l, it, dt),
    single chip (197 TFLOP/s, 819 GB/s) — the paper-technique §Perf cell.
    Each cell is visited ONCE and contributes both profile sides;
    `stream_bytes` models the plan's stream precision (see
    `hbm_bytes_per_cell`)."""
    cells = float(sum(l - k for k in range(excl, l)))
    bpc = hbm_bytes_per_cell(l, excl, it, dt, stream_bytes=stream_bytes)
    return {
        "cells": cells,
        "bytes_per_cell": bpc,
        "stream_bytes": int(stream_bytes),
        "t_compute_s": cells * FLOPS_PER_CELL / 197e12,
        "t_memory_s": cells * bpc / 819e9,
        "vmem_bytes": kernel_vmem_bytes(l, it, dt),
        "resident": kernel_vmem_bytes(l, it, dt) <= VMEM_BYTES,
    }
