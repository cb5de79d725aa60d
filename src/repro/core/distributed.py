"""Multi-device matrix profile via shard_map — NATSA PUs ≙ mesh devices.

Each worker (device along the `workers` mesh axis) executes one equal-work
diagonal chunk per round; the global profile is merged with an argmax-carrying
all-reduce (`pmax` on correlation + index recovery), which is exactly NATSA's
cheap "merge local profiles" step — O(l) traffic per worker per merge,
independent of the O(l^2/P) compute per chunk.

Chunks are TWO-SIDED: every cell a worker streams updates both the row
profile P[i] and the column profile P[j] (for AB joins, A's and B's profiles
respectively), so the round plan needs to cover each diagonal exactly once —
there is no reversed-series second phase.

Chunks are equal-WORK, not equal-diagonal-count (long diagonals live at small
k), so chunk widths in BANDS vary wildly (a narrow-in-bands chunk of long
diagonals carries the same work as a wide chunk of short ones). Workers loop
a DYNAMIC per-worker band count (`fori_loop` to their own chunk end) instead
of a common static one: the old static-`n_bands` scan made every worker pay
for the widest chunk's band count, and because per-band cost is O(l)
regardless of diagonal length, that masked-band overhead grew with worker
count and sank multi-worker scaling. Masked bands are exact bitwise no-ops
(`merge`/`merge_window` take strictly-greater, all-NEG windows lose every
comparison), so skipping them leaves results bit-identical; the trailing
partial band keeps its per-diagonal mask. `n_bands` remains a static CAP on
the trip count, and `tests/test_partition.py` property-tests the balance.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.matrix_profile import (
    ColState, DEFAULT_RESEED, NEG, ProfileState, TopKState,
    _ab_padded_streams, ab_reseed, ab_row_tile, band_rowmax, band_rowmax_ab,
    band_topk, band_topk_ab, centered_windows, reseed_table,
)
from repro.core.zstats import CrossStats, ZStats


def pmax_profile(state: ProfileState, axis: str) -> ProfileState:
    """All-reduce a ProfileState across `axis` keeping argmax indices."""
    gmax = jax.lax.pmax(state.corr, axis)
    # recover the index of the winner; ties -> highest index (deterministic)
    cand = jnp.where(state.corr >= gmax, state.index, -1)
    gidx = jax.lax.pmax(cand, axis)
    return ProfileState(corr=gmax, index=gidx)


def allreduce_topk(state: TopKState, axis: str) -> TopKState:
    """All-reduce a TopKState across `axis`: gather every worker's (l, k)
    best-first set and take the exact union top-k. O(P·l·k) traffic — still
    independent of the O(l^2/P) compute per chunk, the same cheap
    merge-local-profiles step as `pmax_profile`, widened. Workers' candidate
    sets are disjoint (each diagonal belongs to exactly one chunk), so the
    union stays an exact top-k."""
    k = state.corr.shape[-1]
    c = jax.lax.all_gather(state.corr, axis)     # (P, l, k)
    i = jax.lax.all_gather(state.index, axis)
    l = state.corr.shape[0]
    c = jnp.moveaxis(c, 0, -1).reshape(l, -1)    # (l, k*P)
    i = jnp.moveaxis(i, 0, -1).reshape(l, -1)
    vals, pos = jax.lax.top_k(c, k)
    return TopKState(corr=vals, index=jnp.take_along_axis(i, pos, axis=-1))


def live_bands(k0: jax.Array, k1: jax.Array, n_bands: int,
               band: int) -> jax.Array:
    """Number of band tiles a chunk [k0, k1) actually touches, capped at the
    static `n_bands` bound. Dynamic per worker — this is the `fori_loop`
    trip count that replaces the old masked static scan."""
    n = (k1 - k0 + band - 1) // band
    return jnp.clip(n, 0, n_bands).astype(jnp.int32)


def worker_chunk(stats: ZStats, k0: jax.Array, k1: jax.Array,
                 n_bands: int, band: int,
                 reseed_every: int | None = DEFAULT_RESEED) -> ProfileState:
    """Two-sided harvest over band-aligned diagonals [k0, k1), <= n_bands
    bands. Both the row and the column updates of every swept cell land in
    the returned state.

    Precision: worker chunks run the band engine's pinned-f32 accumulation
    path (no `accum_dtype` override) — `plan_sweep` rejects any non-f32
    accum for the distributed backend, so the pmap'd bodies stay a single
    compiled specialization per geometry."""
    l = stats.n_subsequences
    table = (reseed_table(centered_windows(stats), reseed_every, band)
             if reseed_every is not None else None)

    def body(b, carry):
        state, col = carry
        start = k0 + b * band
        rc, ri, win, wi = band_rowmax(stats, start, band,
                                      reseed_every=reseed_every,
                                      seed_table=table)
        live = start < k1            # trailing band may overhang the chunk
        rc = jnp.where(live, rc, NEG)
        win = jnp.where(live, win, NEG)
        state = state.merge(ProfileState(rc, ri))
        col = col.merge_window(win, wi, start)
        return (state, col)

    init = (ProfileState.empty(l), ColState.empty(0, l, l + band))
    state, col = jax.lax.fori_loop(0, live_bands(k0, k1, n_bands, band),
                                   body, init)
    return state.merge(col.to_profile(0, l))


def worker_chunk_ab(cross: CrossStats, k0: jax.Array, k1: jax.Array,
                    n_bands: int, band: int,
                    reseed_every: int | None = DEFAULT_RESEED
                    ) -> tuple[ProfileState, ProfileState]:
    """Two-sided harvest over one SIGNED diagonal chunk [k0, k1) of the AB
    rectangle.

    Returns (state_a (l_a,), state_b (l_b,)) — A's row harvest and B's
    column harvest of the same swept cells. Diagonals may be negative and
    the chunk end is masked per-diagonal (AB chunk widths are not always
    band-aligned — the exclusion gap forces odd cuts). Band tiles are
    row-clamped (see `ab_row_tile`): both harvests come back as bounded
    windows merged at each band's dynamic row offset i0, so a skewed
    rectangle costs ~l_b cells per diagonal, not l_a."""
    la, lb = cross.l_a, cross.l_b
    reseed_every = ab_reseed(la, lb, reseed_every)
    wa = centered_windows(cross.a) if reseed_every is not None else None
    wb = centered_windows(cross.b) if reseed_every is not None else None
    li = ab_row_tile(la, lb, band)
    padded = _ab_padded_streams(cross, band, li)
    pad_l = la - 1                 # most negative valid diagonal start

    def body(b, carry):
        rows, col = carry
        start = k0 + b * band
        ra, ia, win, wi, i0 = band_rowmax_ab(cross, start, band, k_hi=k1,
                                             reseed_every=reseed_every,
                                             wa=wa, wb=wb, padded=padded)
        live = start < k1
        ra = jnp.where(live, ra, NEG)
        win = jnp.where(live, win, NEG)
        rows = rows.merge_window(ra, ia, i0)
        col = col.merge_window(win, wi, start + i0 + pad_l)
        return (rows, col)

    init = (ColState.empty(0, la, li),
            ColState.empty(pad_l, lb, li + 2 * band))
    rows, col = jax.lax.fori_loop(0, live_bands(k0, k1, n_bands, band),
                                  body, init)
    return rows.to_profile(0, la), col.to_profile(pad_l, lb)


def worker_chunk_topk(stats: ZStats, k0: jax.Array, k1: jax.Array,
                      n_bands: int, band: int, k: int,
                      reseed_every: int | None = DEFAULT_RESEED) -> TopKState:
    """`worker_chunk` widened to exact top-k: the merged (l, k) best-first
    set of every row AND column update the chunk's cells imply."""
    l = stats.n_subsequences
    table = (reseed_table(centered_windows(stats), reseed_every, band)
             if reseed_every is not None else None)

    def body(b, carry):
        rows, col = carry
        start = k0 + b * band
        rc, ri, win, wi = band_topk(stats, start, band, k,
                                    reseed_every=reseed_every,
                                    seed_table=table)
        live = start < k1            # trailing band may overhang the chunk
        rc = jnp.where(live, rc, NEG)
        win = jnp.where(live, win, NEG)
        rows = rows.merge(TopKState(rc, ri))
        col = col.merge_window(win, wi, start)
        return (rows, col)

    init = (TopKState.empty(l, k), TopKState.empty(2 * l + band, k))
    rows, col = jax.lax.fori_loop(0, live_bands(k0, k1, n_bands, band),
                                  body, init)
    return rows.merge(col.to_state(0, l))


def worker_chunk_ab_topk(cross: CrossStats, k0: jax.Array, k1: jax.Array,
                         n_bands: int, band: int, k: int,
                         reseed_every: int | None = DEFAULT_RESEED
                         ) -> tuple[TopKState, TopKState]:
    """`worker_chunk_ab` widened to exact top-k on both sides."""
    la, lb = cross.l_a, cross.l_b
    reseed_every = ab_reseed(la, lb, reseed_every)
    wa = centered_windows(cross.a) if reseed_every is not None else None
    wb = centered_windows(cross.b) if reseed_every is not None else None
    li = ab_row_tile(la, lb, band)
    padded = _ab_padded_streams(cross, band, li)
    pad_l = la - 1                 # most negative valid diagonal start

    def body(b, carry):
        rows, col = carry
        start = k0 + b * band
        ra, ia, win, wi, i0 = band_topk_ab(cross, start, band, k, k_hi=k1,
                                           reseed_every=reseed_every,
                                           wa=wa, wb=wb, padded=padded)
        live = start < k1
        ra = jnp.where(live, ra, NEG)
        win = jnp.where(live, win, NEG)
        rows = rows.merge_window(ra, ia, i0)
        col = col.merge_window(win, wi, start + i0 + pad_l)
        return (rows, col)

    init = (TopKState.empty(la + li, k),
            TopKState.empty(pad_l + lb + li + 2 * band, k))
    rows, col = jax.lax.fori_loop(0, live_bands(k0, k1, n_bands, band),
                                  body, init)
    return rows.to_state(0, la), col.to_state(pad_l, lb)


def make_round_fn(plan, mesh, axis: str = "workers"):
    """SPMD function for one anytime round of a distributed `SweepPlan`
    (core.plan.round_executor is the only caller — tiling and reseed knobs
    come off the plan, not positional args).

    Signature: (stats, running_profile, k0s (P,), k1s (P,)) -> merged profile.
    Idle workers pass k0 == k1 (empty chunk). Stats are replicated — they are
    O(n); the implicit distance matrix is O(n^2). Shipping the small streams
    to every worker instead of partitioning the matrix is the NDP move. A
    full set of rounds yields the EXACT profile (two-sided chunks — no
    reversed finish phase).

    Plans with `harvest.k > 1` run the widened top-k chunks: the running
    state is a `TopKState` (the scheduler sizes it) and the merge step is
    the gather + union-top-k all-reduce (`allreduce_topk`).
    """
    n_bands, band, reseed = plan.n_bands, plan.band, plan.reseed_every
    k = plan.harvest.k

    def per_worker(stats: ZStats, running, k0_local, k1_local):
        if k > 1:
            local = worker_chunk_topk(stats, k0_local[0], k1_local[0],
                                      n_bands, band, k, reseed)
            # all-reduce the LOCALS first, then merge into the replicated
            # running state ONCE: gathering running.merge(local) instead
            # would hand lax.top_k P copies of every prior winner, and the
            # duplicates would evict true top-k entries (max-merge is
            # idempotent under that duplication; top-k union is not)
            return running.merge(allreduce_topk(local, axis))
        local = worker_chunk(stats, k0_local[0], k1_local[0], n_bands, band,
                             reseed)
        return pmax_profile(running.merge(local), axis)

    shmapped = jax.shard_map(
        per_worker, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis)),
        out_specs=P(), check_vma=False,
    )
    return jax.jit(shmapped)


def make_round_fn_ab(plan, mesh, axis: str = "workers"):
    """AB analogue of `make_round_fn`: one anytime round over signed chunks,
    carrying BOTH profiles.

    Signature: (cross, running_a, running_b, k0s (P,), k1s (P,))
    -> (merged_a, merged_b). Idle workers pass k0 == k1. CrossStats (both
    series' streams + seeds) are replicated — still O(n_a + n_b) traffic vs
    the O(n_a * n_b) rectangle. `harvest.k > 1` plans run the widened
    top-k chunks and union-top-k all-reduce, both sides.
    """
    n_bands, band, reseed = plan.n_bands, plan.band, plan.reseed_every
    k = plan.harvest.k

    def per_worker(cross: CrossStats, running_a, running_b,
                   k0_local, k1_local):
        if k > 1:
            loc_a, loc_b = worker_chunk_ab_topk(
                cross, k0_local[0], k1_local[0], n_bands, band, k, reseed)
            # locals first, running once — see make_round_fn
            return (running_a.merge(allreduce_topk(loc_a, axis)),
                    running_b.merge(allreduce_topk(loc_b, axis)))
        loc_a, loc_b = worker_chunk_ab(cross, k0_local[0], k1_local[0],
                                       n_bands, band, reseed)
        return (pmax_profile(running_a.merge(loc_a), axis),
                pmax_profile(running_b.merge(loc_b), axis))

    shmapped = jax.shard_map(
        per_worker, mesh=mesh,
        in_specs=(P(), P(), P(), P(axis), P(axis)),
        out_specs=(P(), P()), check_vma=False,
    )
    return jax.jit(shmapped)
