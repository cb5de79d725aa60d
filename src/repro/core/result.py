"""Profile API v2: the rich result object every entry point returns.

The paper positions the matrix profile as the substrate for a family of
time-series data-mining tasks — motif discovery, discord (anomaly)
detection, segmentation — and those tasks need MORE than the bare
nearest-neighbor vector the old `(P, I)` tuples carried:

  * top-k neighbor sets (motif groups, k-NN discords),
  * LEFT/RIGHT split profiles (nearest neighbor strictly before / strictly
    after each position — streaming discords, arc-curve segmentation),
  * the B side of an AB join,
  * the geometry/normalize metadata needed to interpret any of it.

PAY-AS-YOU-GO: the entry points default to a minimal harvest (the merged
profile, k = 1) and `ProfileResult` is cheap to build — no side is
converted to distance, copied, or synced to host unless the caller touches
it. `.left_p/.right_p/.b_p/.topk_*` are LAZY attributes:

  * when the executed sweep already harvested the side (the engine's single
    pass computes both sides anyway; the kernel's two halves ARE the
    split), first access finishes it from the RETAINED device state — a
    couple of O(l) elementwise conversions, no new sweep;
  * when the sweep genuinely skipped the side (the band engine's AB column
    harvest under a minimal plan), first access runs a narrow follow-up of
    the SAME plan with `sides="both"` — the identical sweep, so the late
    arrays are bitwise-equal to an eager `harvest="both"` request
    (tests/test_lazy_result.py pins this across backends);
  * a side the plan can never produce (B side of a self-join, top-k of a
    k = 1 plan) stays None, exactly as before.

Results materialize what they resolve: accessing `.left_p` fills the whole
split group, so repeated access costs nothing further.

The one-release tuple-unpacking shim is RETIRED as scheduled: iterating,
indexing, or `len()` on a `ProfileResult` now raises `TypeError`
consistently — use `result.p` / `result.i` (and `.b_p/.b_i`,
`.left_p/.right_p`, `.topk_p/.topk_i`).
"""

from __future__ import annotations

import dataclasses
from typing import Any

from repro import obs


@dataclasses.dataclass(frozen=True)
class HarvestSpec:
    """What a sweep should harvest, beyond touching every cell.

    `sides`: "merged" (the default) harvests the minimal product — the
    merged profile of a self-join, A's profile of an AB join — leaving the
    other sides to the result layer's lazy finish; "row" is the explicit
    A-side-only AB harvest (same executed sweep as "merged"); "both"
    eagerly materializes row AND column sides (self-join: merged profile +
    left/right split; AB: A's and B's profiles) from the same streamed
    cells.

    `k`: neighbors kept per position. k == 1 is the classic profile and
    runs the unchanged (bitwise-pinned) engine paths; k > 1 widens the
    accumulators to exact (l, k) insertion-merged top-k sets through the
    engine, rowstream, and distributed/scheduler backends (the kernel
    backend plans a fallback to the engine — its VMEM accumulator layout
    stays k = 1).
    """

    sides: str = "merged"         # "merged" | "row" | "both"
    k: int = 1

    def __post_init__(self):
        if self.sides not in ("merged", "row", "both"):
            raise ValueError(f"harvest sides must be 'merged', 'row' or "
                             f"'both', got {self.sides!r}")
        if int(self.k) < 1:
            raise ValueError(f"harvest k must be >= 1, got {self.k}")


# lazy field -> the group one resolution fills (split sides come as a set:
# finishing left without right would re-derive the shared state twice)
_LAZY_GROUPS = {
    "left_p": "split", "left_i": "split",
    "right_p": "split", "right_i": "split",
    "b_p": "b", "b_i": "b",
    "topk_p": "topk", "topk_i": "topk",
    "b_topk_p": "b_topk", "b_topk_i": "b_topk",
}

# SweepResult field for each public lazy name (recompute fallback path)
_SWEEP_FIELDS = {
    "left_p": "left_dist", "left_i": "left_index",
    "right_p": "right_dist", "right_i": "right_index",
    "b_p": "dist_b", "b_i": "index_b",
    "topk_p": "topk_dist", "topk_i": "topk_index",
    "b_topk_p": "topk_dist_b", "b_topk_i": "topk_index_b",
}


class _LazyHarvest:
    """Deferred-harvest provider attached to a `ProfileResult`.

    `raw` maps group name ("split" | "b" | "topk" | "b_topk") to a
    zero-sweep callable the EXECUTOR installed — a closure over device
    state the sweep computed anyway, returning `{public_name: array}`.
    Groups without a raw provider recompute via the retained (plan, stats)
    pair: the same plan re-executed with `sides="both"`, so the answer is
    bitwise-identical to an eager two-sided request. `recomputes` counts
    those follow-up sweeps (tests assert 0 where the sweep already
    harvested the side).
    """

    __slots__ = ("plan", "stats", "raw", "recomputes")

    def __init__(self, plan, stats=None, raw=None):
        self.plan = plan
        self.stats = stats
        self.raw = dict(raw) if raw else {}
        self.recomputes = 0

    def _producible(self, result: "ProfileResult", group: str) -> bool:
        if group == "split":
            return result.kind == "self"
        if group == "b":
            return result.kind == "ab"
        if group == "topk":
            return result.k > 1
        return result.kind == "ab" and result.k > 1       # b_topk

    def resolve(self, result: "ProfileResult", name: str) -> None:
        group = _LAZY_GROUPS[name]
        if not self._producible(result, group):
            return
        with obs.span("mp.finish"):
            fn = self.raw.get(group)
            if fn is not None:
                fields = fn()
            else:
                fields = self._recompute()
            for key, val in fields.items():
                if object.__getattribute__(result, "_" + key) is None:
                    object.__setattr__(result, "_" + key, val)

    def _recompute(self) -> dict:
        if self.stats is None:
            return {}
        from repro.core import plan as plan_mod

        full = dataclasses.replace(
            self.plan, harvest=dataclasses.replace(self.plan.harvest,
                                                   sides="both"))
        res = plan_mod.execute(full, self.stats)
        self.recomputes += 1
        return {pub: getattr(res, fld) for pub, fld in _SWEEP_FIELDS.items()
                if getattr(res, fld) is not None}


def _lazy_property(name: str):
    slot = "_" + name

    def get(self: "ProfileResult"):
        val = object.__getattribute__(self, slot)
        if val is None:
            lazy = object.__getattribute__(self, "_lazy")
            if lazy is not None:
                lazy.resolve(self, name)
                val = object.__getattribute__(self, slot)
        return val

    get.__name__ = name
    get.__doc__ = f"Lazy `{name}` (see module docstring for what resolves " \
                  f"at zero cost vs a narrow follow-up sweep)."
    return property(get)


class ProfileResult:
    """Everything one executed sweep learned, in the caller's orientation.

    `p`/`i` are the classic merged profile: `p[t]` the distance from
    subsequence t to its nearest admissible neighbor, `i[t]` that
    neighbor's start position (-1 where none exists). Batched entry points
    return stacked `(B, l)` arrays in every field.

    Self-joins additionally carry the SPLIT profiles — `left_p/left_i`
    restrict the neighbor to j < t, `right_p/right_i` to j > t; these are
    the row/column harvests of the same sweep, so
    `min(left_p, right_p) == p` elementwise (inf where a side is empty).
    AB joins instead carry B's profile against A (`b_p/b_i`). With
    `k > 1`, `topk_p/topk_i` are exact `(l, k)` best-first neighbor sets
    (slot 0 == the k = 1 profile; unfilled slots are inf/-1), and
    `b_topk_p/b_topk_i` the B-side sets of an AB join.

    All sides beyond `p`/`i` are LAZY unless the plan harvested them
    eagerly (`harvest="both"` / `return_b=True`): first access finishes
    them from retained sweep state, or — only where the sweep truly
    skipped the side — re-runs the same plan two-sided (bitwise-equal
    either way; see the module docstring). Sides the plan can never
    produce stay None. Instances are frozen like the old dataclass.

    `fraction_done` is the anytime coverage of the answer: 1.0 everywhere
    except a gracefully-degraded supervised distributed run, where it is
    the fraction of true cells swept before retries were exhausted
    (`AnytimeScheduler.run_supervised`).
    """

    _META = ("kind", "window", "exclusion", "normalize", "k", "backend",
             "fraction_done")
    LAZY_FIELDS = tuple(_LAZY_GROUPS)

    def __init__(self, p: Any, i: Any, *, left_p: Any = None,
                 left_i: Any = None, right_p: Any = None, right_i: Any = None,
                 b_p: Any = None, b_i: Any = None, topk_p: Any = None,
                 topk_i: Any = None, b_topk_p: Any = None,
                 b_topk_i: Any = None, kind: str = "self", window: int = 0,
                 exclusion: int = 0, normalize: bool = True, k: int = 1,
                 backend: str = "engine", fraction_done: float = 1.0,
                 lazy: _LazyHarvest | None = None):
        sa = object.__setattr__
        sa(self, "p", p)
        sa(self, "i", i)
        sa(self, "_left_p", left_p)
        sa(self, "_left_i", left_i)
        sa(self, "_right_p", right_p)
        sa(self, "_right_i", right_i)
        sa(self, "_b_p", b_p)
        sa(self, "_b_i", b_i)
        sa(self, "_topk_p", topk_p)
        sa(self, "_topk_i", topk_i)
        sa(self, "_b_topk_p", b_topk_p)
        sa(self, "_b_topk_i", b_topk_i)
        sa(self, "kind", kind)
        sa(self, "window", int(window))
        sa(self, "exclusion", int(exclusion))
        sa(self, "normalize", bool(normalize))
        sa(self, "k", int(k))
        sa(self, "backend", backend)
        # anytime coverage: 1.0 for a completed sweep; the distributed
        # scheduler's supervised loop tags gracefully-degraded answers with
        # the fraction of true cells actually swept (see
        # SchedulerState.fraction_done)
        sa(self, "fraction_done", float(fraction_done))
        sa(self, "_lazy", lazy)

    # frozen like the dataclass it replaces
    def __setattr__(self, name, value):
        raise dataclasses.FrozenInstanceError(
            f"cannot assign to field {name!r}")

    def __delattr__(self, name):
        raise dataclasses.FrozenInstanceError(
            f"cannot delete field {name!r}")

    left_p = _lazy_property("left_p")
    left_i = _lazy_property("left_i")
    right_p = _lazy_property("right_p")
    right_i = _lazy_property("right_i")
    b_p = _lazy_property("b_p")
    b_i = _lazy_property("b_i")
    topk_p = _lazy_property("topk_p")
    topk_i = _lazy_property("topk_i")
    b_topk_p = _lazy_property("b_topk_p")
    b_topk_i = _lazy_property("b_topk_i")

    # -- convenience -------------------------------------------------------

    @property
    def n_subsequences(self) -> int:
        return self.p.shape[-1]

    def has_split(self) -> bool:
        """Whether the left/right split is available — materialized or lazily
        producible. Does NOT trigger resolution."""
        if object.__getattribute__(self, "_left_p") is not None:
            return True
        lazy = object.__getattribute__(self, "_lazy")
        return lazy is not None and lazy._producible(self, "split")

    def has_topk(self) -> bool:
        """Whether (l, k) top-k sets are available (see `has_split`)."""
        if object.__getattribute__(self, "_topk_p") is not None:
            return True
        lazy = object.__getattribute__(self, "_lazy")
        return lazy is not None and lazy._producible(self, "topk")

    def __repr__(self) -> str:
        sides = [f for f in self.LAZY_FIELDS
                 if object.__getattribute__(self, "_" + f) is not None]
        meta = ", ".join(f"{f}={getattr(self, f)!r}" for f in self._META)
        return (f"ProfileResult(l={self.p.shape[-1]}, {meta}, "
                f"materialized={sides!r})")


def build_result(plan, res, stats=None) -> ProfileResult:
    """Wrap an executed plan's `SweepResult` into the public `ProfileResult`.

    `plan` is the `SweepPlan` that produced `res` — geometry metadata and
    the harvest spec are read off it (duck-typed here; `core.plan` imports
    this module, not the other way round). `stats` is the device payload
    the plan executed on; retaining it lets lazily-accessed sides the
    sweep skipped recompute through the SAME plan (pass None to disable
    the recompute fallback — zero-cost raw finishes still work).
    """
    spec = plan.harvest
    with obs.span("mp.finish"):
        lazy = _LazyHarvest(plan, stats, raw=getattr(res, "raw", None))
        return ProfileResult(
            p=res.dist, i=res.index,
            left_p=res.left_dist, left_i=res.left_index,
            right_p=res.right_dist, right_i=res.right_index,
            b_p=res.dist_b, b_i=res.index_b,
            topk_p=res.topk_dist, topk_i=res.topk_index,
            b_topk_p=res.topk_dist_b, b_topk_i=res.topk_index_b,
            kind=plan.kind, window=plan.window, exclusion=plan.exclusion,
            normalize=plan.normalize, k=spec.k, backend=plan.backend,
            lazy=lazy)
