"""Resident sharded corpus — the data the profile service serves against.

`ShardedCorpus` loads N reference series ONCE: each series' z-stats and
centered-window matrix are computed host-side in f64 (`core.resident.
build_side`, the same audited path `StreamingProfile.query` caches through)
and stay resident for the corpus's lifetime; the f32 stats streams are
device-placed round-robin across the mesh's devices, one shard per device,
so per-shard sweeps dispatch against data that already lives where the
compute runs — queries ship O(l_q) query streams, never corpus state
(NATSA's near-data move, applied to serving).

Series are grouped by (shard, length): a group is the unit of batched
execution — Q queries against its S series stack into one `(Q*S)`-wide
vmapped engine sweep (`assemble_batch` builds the stacked `CrossStats`
with `zstats.seed_dots_batch`, the row-block seed dots
`compute_cross_stats_host` takes, so every pair is bitwise-identical to a
fresh two-sided build). Content changes go through `reload(sid, values)`,
which bumps the series' generation — the shared `ReferenceCache` keys sides
by it, so stale stats can never be served (same contract as the streaming
monitor's append counter).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro import obs
from repro.core.resident import ReferenceCache, ResidentSide, build_side


@dataclasses.dataclass(frozen=True)
class ShardGroup:
    """One (shard, geometry) execution group: the series of one shard that
    share a subsequence count, batched together in every sweep."""

    shard: int
    l_ref: int                    # per-series subsequence count
    sids: tuple[int, ...]         # series ids, ascending
    device: object = None         # mesh device the shard's stats live on


class ShardedCorpus:
    """N reference series resident behind the profile service.

    `mesh` (see `launch.mesh.make_worker_mesh`) supplies the devices shards
    are placed on; without one (or with a single device) the corpus is
    still sharded logically — `n_shards` controls fault granularity (a
    failed shard degrades answers by its series only) independent of the
    physical device count."""

    def __init__(self, series, window: int, *, mesh=None,
                 n_shards: int | None = None, normalize: bool = True,
                 plan_max: int = 16):
        self.window = int(window)
        self.normalize = bool(normalize)
        if not self.normalize:
            raise ValueError("ShardedCorpus serves z-normalized joins only: "
                             "batched plans vmap the normalized engine "
                             "(core.plan rejects nonnorm batch plans); use "
                             "StreamingProfile.query for raw distances")
        self._series = [np.asarray(s, np.float64) for s in series]
        if not self._series:
            raise ValueError("corpus needs at least one series")
        for i, s in enumerate(self._series):
            if s.ndim != 1 or s.shape[0] < self.window:
                raise ValueError(f"series {i} must be 1-D with >= "
                                 f"{self.window} points, got shape {s.shape}")
        self._devices = list(mesh.devices.flat) if mesh is not None else []
        if n_shards is None:
            n_shards = max(1, len(self._devices)) if mesh is not None else 1
        self.n_shards = min(int(n_shards), len(self._series))
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        # per-series generation counters: reload() bumps, and the shared
        # ReferenceCache keys sides by (sid, gen, normalize) — the
        # staleness contract is the helper's, not a private dict's
        self._gens = [0] * len(self._series)
        self._refs = ReferenceCache(self.window,
                                    side_max=2 * len(self._series) + 2,
                                    plan_max=plan_max)
        self._stacks: dict = {}              # per-group series-side stacks
        for sid in range(len(self._series)):
            self.side(sid)               # load once, resident from here on

    # -- residency ---------------------------------------------------------

    @property
    def n_series(self) -> int:
        return len(self._series)

    def shard_of(self, sid: int) -> int:
        return sid % self.n_shards

    def device_of(self, shard: int):
        if not self._devices:
            return None
        return self._devices[shard % len(self._devices)]

    def side(self, sid: int) -> ResidentSide:
        """Series `sid`'s resident side (stats + centered windows), built on
        first access and cached by (sid, generation, normalize)."""
        norm = self.normalize
        key = (sid, self._gens[sid], norm)

        def build():
            s = build_side(self._series[sid], self.window, normalize=norm)
            dev = self.device_of(self.shard_of(sid))
            if dev is not None and norm:
                import jax

                s = dataclasses.replace(
                    s, stats=jax.device_put(s.stats, dev))
            return s

        return self._refs.side(key, build)

    def reload(self, sid: int, values) -> None:
        """Replace series `sid`'s content. Bumps its generation, so every
        cached side/plan consumer sees fresh stats on next access — a
        same-length reload can never serve stale streams."""
        v = np.asarray(values, np.float64)
        if v.ndim != 1 or v.shape[0] < self.window:
            raise ValueError(f"reload needs a 1-D series with >= "
                             f"{self.window} points, got shape {v.shape}")
        self._series[sid] = v
        self._gens[sid] += 1
        self.side(sid)                   # re-resident immediately

    def groups(self) -> list[ShardGroup]:
        """Execution groups, shard-major then length-major — the batcher's
        fan-out order."""
        by_key: dict[tuple[int, int], list[int]] = {}
        for sid, s in enumerate(self._series):
            key = (self.shard_of(sid), s.shape[0] - self.window + 1)
            by_key.setdefault(key, []).append(sid)
        return [ShardGroup(shard=sh, l_ref=l, sids=tuple(sids),
                           device=self.device_of(sh))
                for (sh, l), sids in sorted(by_key.items())]

    # -- batched sweep assembly -------------------------------------------

    def plan_for(self, group: ShardGroup, l_q: int, *, k: int = 1,
                 batch: int | None = None):
        """The group's query-geometry plan (shared geometry-keyed LRU)."""
        return self._refs.plan_for(self.side(group.sids[0]), l_q,
                                   k=k, batch=batch)

    def _series_stack(self, group: ShardGroup):
        """The group's series-side `ZStats` tree stacked to `(S, ...)` —
        query-independent, so cached per (group content) and reused by
        every batch that touches the group."""
        import jax
        import jax.numpy as jnp

        key = (group.shard, group.l_ref)
        gens = tuple(self._gens[sid] for sid in group.sids)
        hit = self._stacks.get(key)
        if hit is not None and hit[0] == gens:
            return hit[1]
        sides = [self.side(sid) for sid in group.sids]
        stack = jax.tree.map(lambda *xs: jnp.stack(xs),
                             *[s.stats for s in sides])
        self._stacks[key] = (gens, stack)    # stale gens overwritten here
        return stack

    def assemble_batch(self, group: ShardGroup, queries: list, plan):
        """Stack the (query, series) pair payloads of one group sweep.

        `queries` holds `(s_q, w_q)` parts (query z-stats + centered
        windows, computed ONCE per query by the front-end and reused across
        every group). Pairs are ordered query-major — result row `q * S + s`
        is query q against `group.sids[s]` — and padded by repeating the
        last pair up to `plan.batch` (batch sizes are bucketed so jit
        compiles O(log) variants, the pad rows are sliced off by the
        caller).

        Assembly is vectorized, not a per-pair loop: the query-side stats
        tree is stacked once and `repeat`ed S-wise, the cached series-side
        stack is `tile`d Q-wise, and the seed dots of every pair run in
        one `zstats.seed_dots_batch` — the SAME `zstats.seed_dots` products
        `cross_stats_from_parts` computes, stacked host-side and rounded to
        f32 exactly once — so every pair's streams stay bitwise-identical
        to a fresh `compute_cross_stats_host` build of the same two
        series."""
        import jax
        import jax.numpy as jnp

        if plan.swap_ab:
            raise ValueError("batched serve plans never swap: the vmapped "
                             "engine row-clamps instead")
        from repro.core.zstats import CrossStats, seed_dots_batch

        nq, ns = len(queries), len(group.sids)
        sides = [self.side(sid) for sid in group.sids]

        a = jax.tree.map(lambda *xs: jnp.stack(xs),
                         *[s for s, _ in queries])
        a = jax.tree.map(lambda x: jnp.repeat(x, ns, axis=0), a)
        b = jax.tree.map(
            lambda x: jnp.tile(x, (nq,) + (1,) * (x.ndim - 1)),
            self._series_stack(group))

        with obs.span("mp.seeds"):
            rows = np.stack(seed_dots_batch([w_q for _, w_q in queries],
                                            [s.windows for s in sides]))
        with obs.span("mp.put"):
            cov0s = jnp.asarray(rows, jnp.float32)
            obs.count("mp.put_bytes", cov0s.nbytes)

        stack = CrossStats(a=a, b=b, cov0s=cov0s)
        if plan.batch is not None:
            pad = plan.batch - nq * ns
            if pad < 0:
                raise ValueError(f"{nq * ns} pairs exceed plan batch "
                                 f"{plan.batch}")
            if pad:
                stack = jax.tree.map(
                    lambda x: jnp.concatenate(
                        [x, jnp.repeat(x[-1:], pad, axis=0)]), stack)
        dev = group.device
        if dev is not None:
            with obs.span("mp.put"):
                stack = jax.device_put(stack, dev)
                obs.count("mp.put_bytes", sum(
                    x.nbytes for x in jax.tree.leaves(stack)))
        return stack
