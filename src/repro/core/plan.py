"""Declarative sweep planning: ONE plan + executor behind every entry point.

NATSA's architectural claim is a single specialized sweep primitive with all
workload variation pushed into a thin planning layer. This module is that
layer for the repro: every public way of asking for a matrix profile —
`matrix_profile`, `ab_join`, the `batch_*` variants, the nonnorm variants,
the Pallas kernel ops, the anytime/distributed scheduler rounds, and
`StreamingProfile.query` — builds a frozen `SweepPlan` via `plan_sweep(...)`
and hands it to `execute(...)` (or, for SPMD rounds, `round_executor(...)`).

The executor functions here are the ONLY callers of the low-level sweeps
(`profile_from_stats`, `ab_join_from_stats`, `ab_join_rowstream`, the
nonnorm engines, `kernels.ops.*rowmax_from_stats`, and
`distributed.make_round_fn*`). Entry points stay thin; geometry / tiling /
harvest / reseed knobs live in exactly one dataclass instead of being
threaded positionally through four layers; and per-backend equivalence is
testable at one seam (tests/test_plan.py pins both the planner's choices and
bit-equality of plan-built results against direct low-level calls).

Planner heuristics centralized here (formerly scattered per entry point):
  * AB orientation: sweep the rectangle with its SHORT side on rows
    (`swap_ab`) — fewest streamed cells — for the rowstream and kernel
    backends; the band engine's row clamp makes orientation moot there.
  * rowstream choice: a normalized AB join whose short side fits
    `AB_ROWSTREAM_MAX_ROWS` takes the row-streamed scan (the fastest exact
    path on skewed shapes); huge near-square joins and every partitioned /
    batched / nonnorm sweep take the band-diagonal engine.
  * `auto_col_tile` banking: kernel self-joins resolve their column
    accumulator policy AT PLAN TIME (col_tile pinned in the plan: 0 = one
    flat bank, else the bank width); AB kernel spans resolve per span inside
    `ops` (an exclusion gap splits the signed space into two spans with
    different flat lengths) from the plan's `col_tile` policy value.
  * band / exclusion defaults in one place (`DEFAULT_BAND`,
    `default_exclusion`; AB joins default to NO exclusion zone).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

# NOTE: names are imported from the module directly — `repro.core`'s package
# namespace rebinds `matrix_profile` to the FUNCTION of the same name, so a
# `from repro.core import matrix_profile` would grab the entry point, not
# the module. The kernel (`repro.kernels.ops`, pulls in the Pallas stack)
# and SPMD (`repro.core.distributed`, shard_map) backends are imported
# lazily inside their executor branches so `import repro.core` stays light
# for engine-only users.
from repro.core.matrix_profile import (
    AB_ROWSTREAM_MAX_ROWS, DEFAULT_BAND, DEFAULT_RESEED, ab_join_from_stats,
    ab_join_nonnorm, ab_join_rowstream, ab_join_rowstream_topk,
    ab_join_topk_from_stats, default_exclusion, nonnorm_profile_from_ts,
    nonnorm_to_distance, profile_from_stats, profile_topk_from_stats,
    tile_profile_from_stats,
)
from repro.core.precision import DEFAULT_PRECISION, PrecisionSpec, as_precision
from repro.core.result import HarvestSpec
from repro.core.zstats import CrossStats, ZStats, corr_to_dist
# tile-geometry defaults only — repro.kernels itself imports nothing
from repro.kernels import DEFAULT_DT, DEFAULT_IT, resolve_interpret

BACKENDS = ("engine", "rowstream", "kernel", "distributed")


@dataclasses.dataclass(frozen=True)
class SweepPlan:
    """Frozen description of one exact matrix-profile sweep.

    Geometry is in the CALLER's orientation (`l_a` is the caller's A side);
    `swap_ab` records that the executor streams the transposed rectangle
    (short side on rows) and maps the outputs back, so callers never see the
    orientation. `k_min/k_max` (derived) are the signed diagonal span the
    sweep covers, also in caller orientation (self-joins: the upper triangle
    `[exclusion, l_a)`; the executor removes the `|k| < exclusion` band of
    AB spans itself).
    """

    # -- geometry ----------------------------------------------------------
    kind: str                       # "self" | "ab"
    l_a: int                        # subsequence count of A (rows)
    l_b: int | None                 # AB: subsequence count of B; self: None
    window: int
    exclusion: int
    # -- normalization -----------------------------------------------------
    normalize: bool = True          # z-normalized corr vs raw euclidean
    # -- harvest -----------------------------------------------------------
    # sides "merged" (minimal, lazy finish) | "row" (A side only) | "both"
    # (eager two-sided); k > 1 = exact top-k accumulators
    harvest: HarvestSpec = HarvestSpec()
    swap_ab: bool = False           # executor sweeps B-vs-A, un-swaps outputs
    # -- tiling ------------------------------------------------------------
    band: int = DEFAULT_BAND        # diagonals per band tile
    clamp_rows: bool = True         # row-clamp AB band tiles to the rectangle
    col_tile: int | None = None     # column-accumulator bank width policy
    n_bands: int | None = None      # distributed: static bands per chunk
    it: int = DEFAULT_IT            # kernel row-tile height
    dt: int = DEFAULT_DT            # kernel diagonal-tile width
    # -- reseed policy -----------------------------------------------------
    reseed_every: int | None = DEFAULT_RESEED
    # -- backend -----------------------------------------------------------
    backend: str = "engine"         # engine | rowstream | kernel | distributed
    interpret: bool = False         # kernel backend: Pallas interpret mode
    batch: int | None = None        # vmapped stack size (engine/rowstream)
    # -- precision ---------------------------------------------------------
    # stream/accum/seed dtypes, decided HERE at plan time (default: the
    # historical all-f32 pipeline, bitwise). A reduced (16-bit) stream
    # switches the self-join engine to the recurrence-free dot-product tile
    # sweep (`tile_profile_from_stats`); see core/precision.py.
    precision: PrecisionSpec = DEFAULT_PRECISION

    @property
    def k_min(self) -> int:
        """First signed diagonal of the sweep (caller orientation) — derived,
        so it can never go stale against kind/exclusion/l_a."""
        return self.exclusion if self.kind == "self" else -(self.l_a - 1)

    @property
    def k_max(self) -> int:
        """One past the last signed diagonal (caller orientation)."""
        return self.l_a if self.kind == "self" else self.l_b


@dataclasses.dataclass
class SweepResult:
    """Everything an executed plan harvested, in the caller's orientation.

    `dist/index` are the classic merged profile. `dist_b/index_b` are the B
    side of a two-sided AB harvest (None for self-joins and minimal plans).
    Self-join `sides="both"` plans also carry the LEFT/RIGHT split
    (column/row harvest; None for AB). Plans with `harvest.k > 1` fill the
    `(l, k)` top-k fields (best-first; slot 0 == the merged profile's
    values). `core.result.build_result` wraps this into the public
    `ProfileResult`.

    `raw` is the PAY-AS-YOU-GO seam: for sides the sweep computed anyway
    but a minimal plan did not eagerly finish (the engine/kernel split
    halves, rowstream's B accumulator), the executor installs
    `{group: callable}` closures over the retained device state returning
    `{public_field: array}` — `ProfileResult`'s lazy attributes call them
    on first access instead of re-sweeping."""

    dist: jax.Array
    index: jax.Array
    dist_b: jax.Array | None = None
    index_b: jax.Array | None = None
    left_dist: jax.Array | None = None
    left_index: jax.Array | None = None
    right_dist: jax.Array | None = None
    right_index: jax.Array | None = None
    topk_dist: jax.Array | None = None
    topk_index: jax.Array | None = None
    topk_dist_b: jax.Array | None = None
    topk_index_b: jax.Array | None = None
    raw: dict | None = None


def _kernel_self_col_tile(l: int, excl: int, it: int, dt: int,
                          col_tile: int | None) -> int:
    """Resolve the self-join kernel's column-bank policy at plan time.

    Mirrors `ops._pad_streams`' flat accumulator length exactly, then applies
    `ops.auto_col_tile`. Encoding matches what `ops` accepts back: 0 forces
    one flat full-length bank, any other int is the bank width — so a plan
    always pins a CONCRETE choice (testable), never a deferred None.
    """
    from repro.kernels import ops

    n_rows = -(-l // it)
    n_diags = -(-max(l - excl, 1) // dt)
    flat_len = n_rows * it + excl + n_diags * dt
    ct = ops.auto_col_tile(flat_len, it, dt, col_tile)
    return 0 if ct is None else ct


def plan_sweep(window: int, l_a: int, l_b: int | None = None, *,
               exclusion: int | None = None, normalize: bool = True,
               harvest: str | HarvestSpec = "merged", k: int = 1,
               backend: str | None = None,
               band: int = DEFAULT_BAND, clamp_rows: bool = True,
               col_tile: int | None = None,
               reseed_every: int | None = DEFAULT_RESEED,
               it: int = DEFAULT_IT, dt: int = DEFAULT_DT,
               interpret: bool | None = None,
               batch: int | None = None,
               precision: PrecisionSpec | str | None = None) -> SweepPlan:
    """Heuristic planner: fill in every sweep decision an entry point used to
    make inline. `l_a`/`l_b` are SUBSEQUENCE counts (n - window + 1);
    `backend=None` lets the planner choose (entry points only force a backend
    when the user asked for a specific engine, e.g. the Pallas kernel ops or
    the scheduler's SPMD rounds).

    `harvest` is the sides string ("merged" | "row" | "both") or a full
    `HarvestSpec`. The DEFAULT is the minimal "merged" harvest — plan only
    what the caller asked for; sides a minimal sweep computed anyway are
    finished lazily by the result layer, and only `sides="both"` pays to
    materialize them eagerly. `k` (> 1 = exact top-k accumulators)
    overrides the spec's k. Top-k planning rules, all pinned here:
      * the kernel backend's VMEM accumulator layout is k = 1-only — a
        kernel request with k > 1 PLANS A FALLBACK to the band engine
        (same answer, same single sweep, no kernel launch);
      * likewise the banked column accumulator (`col_tile`) stays k = 1 —
        top-k plans pin flat accumulation;
      * rowstream's per-row `lax.top_k` needs k neighbours to exist on the
        full-width side, and the band engines reduce top-k over the band
        axis — so k must fit min(l_a, l_b) resp. `band`;
      * the nonnorm recurrence has no top-k harvest (nobody asked for
        amplitude-anomaly k-NN yet) — explicit ValueError.

    `interpret` is resolved here, once: `None` runs the Pallas interpreter
    only on the CPU backend (`kernels.resolve_interpret`), so the plan
    always carries a concrete bool.

    `precision` is a `PrecisionSpec`, a preset name ("bf16"/"f16"/"f64"),
    or None (the bitwise-default f32 spec). Precision rules pinned here:
      * 16-bit streams are z-normalized only (raw squared distances have
        no [-1, 1] bound, so reduced streams lose unbounded relative
        precision there) and k = 1 only (the top-k accumulators ride the
        drift-prone recurrence with no bounded-error story yet);
      * the kernel backend accumulates in f32 VMEM scratch — it accepts
        any stream dtype but rejects `accum="float64"`;
      * distributed worker chunks likewise keep f32 running states.
    """
    m = int(window)
    prec = as_precision(precision)
    kind = "self" if l_b is None else "ab"
    if exclusion is None:
        excl = default_exclusion(m) if kind == "self" else 0
    else:
        excl = int(exclusion)
    if isinstance(harvest, HarvestSpec):
        spec = harvest if int(k) == 1 else dataclasses.replace(harvest,
                                                               k=int(k))
    else:
        spec = HarvestSpec(sides=harvest, k=int(k))
    topk = spec.k > 1

    if topk and not normalize:
        raise ValueError("top-k (k > 1) harvests are z-normalized only: the "
                         "nonnorm engines carry no top-k accumulator")
    if topk and backend == "kernel":
        # planful fallback: the kernel's banked VMEM accumulators are k=1;
        # the band engine answers the same plan from the same single sweep.
        # col_tile rides along only as the kernel's banking knob, so it is
        # dropped with the backend (otherwise the generic topk+col_tile
        # guard below would reject a fallback the caller was promised)
        backend = "engine"
        col_tile = None
    if topk and kind == "self" and excl == 0:
        raise ValueError(
            "self-join top-k needs exclusion >= 1: with exclusion=0 every "
            "cell (i, i) is harvested by BOTH the row and column sides, so "
            "the union would hold the self-match twice (and slot 0 would "
            "be the trivial zero-distance self-match anyway)")
    if topk and spec.k > int(band):
        raise ValueError(f"k={spec.k} exceeds band={band}: the band engines "
                         "reduce top-k over the band axis — raise band or "
                         "lower k")

    if backend is None:
        if kind == "ab" and normalize and batch is None and clamp_rows \
                and min(l_a, l_b) <= AB_ROWSTREAM_MAX_ROWS \
                and spec.k <= min(l_a, l_b):
            backend = "rowstream"
        else:
            backend = "engine"
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    if backend in ("rowstream", "kernel") and not normalize:
        raise ValueError(f"backend {backend!r} is z-normalized only")
    if backend == "rowstream" and kind != "ab":
        raise ValueError("rowstream sweeps the AB rectangle; self-joins use "
                         "the band engine (or the kernel)")
    if backend == "rowstream" and spec.k > min(l_a, l_b):
        raise ValueError(f"rowstream top-k needs k <= min(l_a, l_b) = "
                         f"{min(l_a, l_b)}, got k={spec.k}")
    if batch is not None and backend not in ("engine", "rowstream"):
        raise ValueError("batched plans vmap the band engine or the AB "
                         f"rowstream; backend {backend!r} cannot batch")
    if batch is not None and backend == "rowstream" and kind != "ab":
        raise ValueError("rowstream sweeps the AB rectangle; batched "
                         "self-joins vmap the band engine")
    if batch is not None and not normalize:
        raise ValueError("batched plans are z-normalized only: the nonnorm "
                         "sweeps take raw series, which the executor does "
                         "not vmap")
    if topk and col_tile is not None:
        raise ValueError("the banked column accumulator (col_tile) is "
                         "k=1-only; top-k plans accumulate flat")
    if topk and not clamp_rows:
        raise ValueError("clamp_rows=False is the k=1 A/B-comparison sweep; "
                         "top-k plans always row-clamp")
    if prec.reduced_stream and not normalize:
        raise ValueError("16-bit streams are z-normalized only: raw squared "
                         "distances have no [-1, 1] bound, so reduced "
                         "streams lose unbounded relative precision")
    if not normalize and kind == "ab" and not prec.is_default:
        raise ValueError("nonnorm AB joins run the fixed f32 pipeline; "
                         "precision specs apply to z-normalized sweeps and "
                         "the nonnorm self-join accumulator only")
    if prec.reduced_stream and topk:
        raise ValueError("top-k harvests with 16-bit streams are not "
                         "supported: the top-k accumulators ride the "
                         "recurrence, which has no bounded-error analysis "
                         "under reduced streams — use f32 streams")
    if backend in ("kernel", "distributed") and prec.accum != "float32":
        raise ValueError(f"backend {backend!r} accumulates in f32 (VMEM "
                         f"scratch / worker chunk states); "
                         f"accum={prec.accum!r} is engine/rowstream-only")

    # short side onto rows for the backends whose row axis is streamed
    swap_ab = (kind == "ab" and backend in ("rowstream", "kernel")
               and l_b < l_a)

    if backend == "kernel" and kind == "self":
        col_tile = _kernel_self_col_tile(l_a, excl, it, dt, col_tile)

    return SweepPlan(kind=kind, l_a=int(l_a),
                     l_b=None if l_b is None else int(l_b),
                     window=m, exclusion=excl,
                     normalize=normalize, harvest=spec, swap_ab=swap_ab,
                     band=int(band), clamp_rows=clamp_rows, col_tile=col_tile,
                     it=int(it), dt=int(dt), reseed_every=reseed_every,
                     backend=backend, interpret=resolve_interpret(interpret),
                     batch=batch,
                     precision=prec)


def stats_dtypes_for(plan: SweepPlan) -> dict:
    """The `(out_dtype, seed_dtype)` kwargs host stream prep needs under a
    plan — the one place the stream-emission dtype is decided.

    One subtlety: the reduced-stream SELF-JOIN path (the dot-product tile
    sweep) must receive f32 stats and round only the CENTERED windows to the
    16-bit stream dtype inside the sweep — rounding `ts` itself first would
    scale the centering error by the series LEVEL rather than the window
    deviation. Every other backend streams the stats arrays themselves, so
    those are emitted directly in the plan's stream dtype."""
    prec = plan.precision
    if (plan.kind == "self" and plan.normalize and prec.reduced_stream
            and plan.backend == "engine"):
        return dict(out_dtype=jnp.float32, seed_dtype=prec.seed_dtype)
    return dict(out_dtype=prec.stream_dtype, seed_dtype=prec.seed_dtype)


def cross_stats_for(plan: SweepPlan, ts_a, ts_b) -> CrossStats:
    """Host-side stream prep for an AB plan, in the plan's SWEPT orientation
    — the one place that honors `swap_ab`, so entry points never hand
    `execute` a transposed rectangle by accident. (Callers with a cached
    resident corpus side build their payload through `resident_stats`
    instead — same orientation contract, corpus side precomputed.)"""
    from repro.core.zstats import compute_cross_stats_host

    if plan.kind != "ab" or not plan.normalize:
        raise ValueError("cross_stats_for prepares z-normalized AB plans; "
                         f"got kind={plan.kind!r} "
                         f"normalize={plan.normalize}")
    m = plan.window
    prec = plan.precision
    dt_kw = dict(out_dtype=prec.stream_dtype, seed_dtype=prec.seed_dtype)
    if plan.swap_ab:               # stream the short side as rows
        return compute_cross_stats_host(ts_b, ts_a, m, **dt_kw)
    return compute_cross_stats_host(ts_a, ts_b, m, **dt_kw)


def resident_stats(plan: SweepPlan, query, resident):
    """`cross_stats_for`'s RESIDENT twin: the `execute` payload for an AB
    plan whose corpus side (`core.resident.ResidentSide`) was precomputed
    once and stays cached across queries — the serving seam: only the
    QUERY's stats are computed here, the corpus side is consumed as-is, and
    `plan.swap_ab` is honored in this one place so resident callers
    (`StreamingProfile.query`, `serve.ShardedCorpus`) never orient the
    rectangle by hand.

    Assembly runs through `zstats.cross_stats_from_parts` — the exact same
    seed-dot path `compute_cross_stats_host` uses internally, so a
    resident-side payload is bitwise-identical to building both sides fresh.
    Raw (nonnorm) plans return the `(query, corpus_ts)` series tuple the
    nonnorm executor expects. Resident caching stores only the default-
    precision streams, so non-default precision plans are rejected rather
    than silently re-deriving dtypes."""
    if plan.kind != "ab":
        raise ValueError(f"resident_stats prepares AB plans, got "
                         f"kind={plan.kind!r}")
    if not plan.precision.is_default:
        raise ValueError("resident corpus sides cache default-precision "
                         "streams only; plan a default-precision sweep or "
                         "build CrossStats directly via cross_stats_for")
    if resident.normalize != plan.normalize:
        raise ValueError(f"resident side is "
                         f"normalize={resident.normalize}, plan wants "
                         f"normalize={plan.normalize}")
    from repro.core.zstats import compute_stats_host

    m = plan.window
    if not plan.normalize:
        return (jnp.asarray(query, jnp.float32), resident.ts)
    from repro.core.zstats import cross_stats_from_parts

    s_q, w_q = compute_stats_host(query, m, min_subsequences=1,
                                  return_centered_windows=True)
    if plan.swap_ab:               # corpus shorter than the query: B on rows
        return cross_stats_from_parts(resident.stats, resident.windows,
                                      s_q, w_q)
    return cross_stats_from_parts(s_q, w_q, resident.stats, resident.windows)


# -- executor -----------------------------------------------------------------


def _kernel_dist(corr: jax.Array, m: int) -> jax.Array:
    from repro.kernels import ops

    return jnp.where(corr <= ops.NEG + 1e-6, jnp.inf,
                     corr_to_dist(jnp.clip(corr, -1.0, 1.0), m))


def _check_stats(plan: SweepPlan, stats) -> None:
    if not plan.normalize:
        ok = (isinstance(stats, tuple) if plan.kind == "ab"
              else not isinstance(stats, (ZStats, CrossStats, tuple)))
        what = "(ts_a, ts_b) raw series" if plan.kind == "ab" else "raw series"
    elif plan.kind == "ab":
        ok, what = isinstance(stats, CrossStats), "CrossStats"
    else:
        ok, what = isinstance(stats, ZStats), "ZStats"
    if not ok:
        raise TypeError(f"{plan.kind}/{'z-norm' if plan.normalize else 'raw'} "
                        f"plan expects {what}, got {type(stats).__name__}")


def execute(plan: SweepPlan, stats) -> SweepResult:
    """Run a plan. `stats` is the device payload matching the plan:
    `ZStats` (self, z-norm), `CrossStats` in the plan's SWEPT orientation
    (AB, z-norm; build with the B/A sides exchanged when `plan.swap_ab`),
    a raw series array (self, nonnorm), or an `(ts_a, ts_b)` tuple (AB,
    nonnorm). Batched plans take the same payloads with a leading stack axis
    (`jax.tree.map(jnp.stack, ...)`). Distributed plans run round-by-round —
    build their SPMD step with `round_executor` instead."""
    _check_stats(plan, stats)
    if plan.backend == "distributed":
        raise ValueError("distributed plans execute round-by-round: build "
                         "the SPMD round fn with round_executor(plan, mesh) "
                         "— AnytimeScheduler drives it")
    if plan.kind == "self":
        return _execute_self(plan, stats)
    return _execute_ab(plan, stats)


# public lazy-field name -> SweepResult field, for eagerly materializing a
# finish-closure's payload under sides="both" (the closure itself is keyed
# by ProfileResult names — the names the lazy result layer fills)
_SWEEP_FIELD_OF = {
    "left_p": "left_dist", "left_i": "left_index",
    "right_p": "right_dist", "right_i": "right_index",
    "b_p": "dist_b", "b_i": "index_b",
    "b_topk_p": "topk_dist_b", "b_topk_i": "topk_index_b",
}


def _attach(res: SweepResult, groups: tuple[str, ...], fin, eager: bool):
    """Wire a finish closure for `groups` into `res`: eagerly materialized
    under sides="both", else installed as a zero-sweep `raw` provider the
    lazy `ProfileResult` calls on first access."""
    if eager:
        for pub, val in fin().items():
            setattr(res, _SWEEP_FIELD_OF[pub], val)
    else:
        if res.raw is None:
            res.raw = {}
        for g in groups:
            res.raw[g] = fin
    return res


def _execute_self(plan: SweepPlan, stats) -> SweepResult:
    m = plan.window
    eager_split = plan.harvest.sides == "both"
    if not plan.normalize:
        split = nonnorm_profile_from_ts(
            jnp.asarray(stats, plan.precision.stream_dtype), m,
            plan.exclusion, plan.band, accum_dtype=plan.precision.accum)
        res = SweepResult(nonnorm_to_distance(split.merged),
                          split.merged.index)

        def fin_split():
            return dict(left_p=nonnorm_to_distance(split.left),
                        left_i=split.left.index,
                        right_p=nonnorm_to_distance(split.right),
                        right_i=split.right.index)

        return _attach(res, ("split",), fin_split, eager_split)
    if plan.backend == "kernel":
        from repro.kernels import ops

        # the kernel's two halves ARE the split: row half = right profile
        # (j > i), column half = left profile (i < j)
        corr_r, idx_r, corr_c, idx_c = ops.rowmax_from_stats(
            stats, excl=plan.exclusion, it=plan.it, dt=plan.dt,
            col_tile=plan.col_tile, interpret=plan.interpret)
        corr, idx = ops._merge_corr(corr_r, idx_r, corr_c, idx_c)
        res = SweepResult(_kernel_dist(corr, m), idx)

        def fin_split():
            return dict(left_p=_kernel_dist(corr_c, m), left_i=idx_c,
                        right_p=_kernel_dist(corr_r, m), right_i=idx_r)

        return _attach(res, ("split",), fin_split, eager_split)
    if plan.harvest.k > 1:
        fn = lambda s: profile_topk_from_stats(             # noqa: E731
            s, plan.exclusion, plan.band, plan.reseed_every, plan.harvest.k,
            accum_dtype=plan.precision.accum)
        if plan.batch is not None:
            fn = jax.vmap(fn)
        merged, rows, col = fn(stats)
        # dist IS slot 0 of the top-k conversion, so the top-k fields ride
        # along at zero extra cost — only the split stays deferred
        dk = merged.to_distance(m)
        res = SweepResult(dk[..., 0], merged.index[..., 0],
                          topk_dist=dk, topk_index=merged.index)

        def fin_split():
            return dict(left_p=col.to_distance(m)[..., 0],
                        left_i=col.index[..., 0],
                        right_p=rows.to_distance(m)[..., 0],
                        right_i=rows.index[..., 0])

        return _attach(res, ("split",), fin_split, eager_split)
    if plan.precision.reduced_stream:
        # recurrence-free dot-product tile sweep: the ONLY self-join engine
        # path for 16-bit streams (bounded absolute corr error, no drift,
        # no reseed machinery — see tile_profile_from_stats)
        fn = lambda s: tile_profile_from_stats(             # noqa: E731
            s, plan.exclusion, stream_dtype=plan.precision.stream,
            accum_dtype=plan.precision.accum)
    else:
        fn = lambda s: profile_from_stats(                  # noqa: E731
            s, plan.exclusion, plan.band, plan.reseed_every,
            accum_dtype=plan.precision.accum)
    if plan.batch is not None:
        fn = jax.vmap(fn)
    split = fn(stats)
    res = SweepResult(split.merged.to_distance(m), split.merged.index)

    def fin_split():
        return dict(left_p=split.left.to_distance(m),
                    left_i=split.left.index,
                    right_p=split.right.to_distance(m),
                    right_i=split.right.index)

    return _attach(res, ("split",), fin_split, eager_split)


def _execute_ab(plan: SweepPlan, stats) -> SweepResult:
    m = plan.window
    two_sided = plan.harvest.sides == "both"
    if not plan.normalize:
        ts_a, ts_b = stats
        # the nonnorm sweep genuinely skips the column harvest when one-
        # sided: a lazily-accessed B side recomputes through the same plan
        da, ia, db, ib = ab_join_nonnorm(
            ts_a, ts_b, m, plan.exclusion, plan.band,
            two_sided=two_sided, clamp_rows=plan.clamp_rows)
        return SweepResult(da, ia, db, ib)
    if plan.harvest.k > 1:
        return _execute_ab_topk(plan, stats, two_sided)
    if plan.backend == "rowstream":
        fn = lambda c: ab_join_rowstream(                   # noqa: E731
            c, plan.exclusion, plan.reseed_every,
            accum_dtype=plan.precision.accum)
        if plan.batch is not None:
            # vmap keeps every per-row FMA and reduce order, so each lane
            # stays bitwise-identical to its unbatched rowstream sweep
            fn = jax.vmap(fn)
        sa, sb = fn(stats)
        if plan.swap_ab:
            sa, sb = sb, sa
        res = SweepResult(sa.to_distance(m), sa.index)

        def fin_b():
            # B's state IS rowstream's running accumulator — computed anyway
            return dict(b_p=sb.to_distance(m), b_i=sb.index)

        return _attach(res, ("b",), fin_b, two_sided)
    if plan.backend == "kernel":
        from repro.kernels import ops

        corr, idx, corr_b, idx_b = ops.ab_rowmax_from_stats(
            stats, exclusion=plan.exclusion, it=plan.it, dt=plan.dt,
            col_tile=plan.col_tile, interpret=plan.interpret)
        if plan.swap_ab:
            corr, idx, corr_b, idx_b = corr_b, idx_b, corr, idx
        res = SweepResult(_kernel_dist(corr, m), idx)

        def fin_b():
            # the kernel launch always harvests both halves
            return dict(b_p=_kernel_dist(corr_b, m), b_i=idx_b)

        return _attach(res, ("b",), fin_b, two_sided)
    # band-diagonal engine: row clamp makes orientation moot, never swapped;
    # a minimal plan really skips the column accumulators (the entry-layer
    # saving), so its B side has no raw finish — lazy access re-executes
    # the same plan with sides="both"
    fn = lambda c: ab_join_from_stats(                      # noqa: E731
        c, plan.exclusion, plan.band, plan.reseed_every, two_sided,
        plan.clamp_rows, plan.col_tile, accum_dtype=plan.precision.accum)
    if plan.batch is not None:
        fn = jax.vmap(fn)
    sa, sb = fn(stats)
    return SweepResult(sa.to_distance(m), sa.index,
                       sb.to_distance(m) if two_sided else None,
                       sb.index if two_sided else None)


def _execute_ab_topk(plan: SweepPlan, stats, two_sided: bool) -> SweepResult:
    """k > 1 AB plans: rowstream's per-row/insertion top-k or the band
    engine's widened `(l, k)` accumulators — one sweep either way. The
    rowstream sweep always carries both sides (B's set IS its running
    accumulator), so a minimal plan defers — not drops — the B side."""
    m = plan.window
    k = plan.harvest.k
    if plan.backend == "rowstream":
        fn = lambda c: ab_join_rowstream_topk(              # noqa: E731
            c, plan.exclusion, plan.reseed_every, k,
            accum_dtype=plan.precision.accum)
        if plan.batch is not None:
            fn = jax.vmap(fn)
        ta, tb = fn(stats)
        if plan.swap_ab:
            ta, tb = tb, ta
    else:
        fn = lambda c: ab_join_topk_from_stats(             # noqa: E731
            c, plan.exclusion, plan.band, plan.reseed_every, two_sided, k,
            accum_dtype=plan.precision.accum)
        if plan.batch is not None:
            fn = jax.vmap(fn)
        ta, tb = fn(stats)
    da = ta.to_distance(m)
    res = SweepResult(da[..., 0], ta.index[..., 0],
                      topk_dist=da, topk_index=ta.index)
    if tb is not None:
        def fin_b():
            db = tb.to_distance(m)        # one conversion serves both groups
            return dict(b_p=db[..., 0], b_i=tb.index[..., 0],
                        b_topk_p=db, b_topk_i=tb.index)

        return _attach(res, ("b", "b_topk"), fin_b, two_sided)
    return res


def round_executor(plan: SweepPlan, mesh, axis: str = "workers"):
    """Executor entry for distributed plans: the jitted SPMD round function
    the AnytimeScheduler steps (the only caller of
    `distributed.make_round_fn` / `make_round_fn_ab`). The plan must carry
    `n_bands` — the static band count of the widest chunk — which the
    scheduler knows only after partitioning (use `dataclasses.replace`)."""
    if plan.backend != "distributed":
        raise ValueError(f"round_executor needs a distributed plan, got "
                         f"backend {plan.backend!r}")
    if plan.n_bands is None:
        raise ValueError("distributed plan lacks n_bands: "
                         "dataclasses.replace(plan, n_bands=...) after "
                         "partitioning")
    from repro.core import distributed

    if plan.kind == "ab":
        return distributed.make_round_fn_ab(plan, mesh, axis)
    return distributed.make_round_fn(plan, mesh, axis)
