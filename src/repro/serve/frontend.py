"""The profile service: batched query execution + per-shard union merge.

`ProfileService` turns admitted queries into answers in three moves:

  1. the admission queue's batcher hands it a geometry-compatible batch
     (same subsequence count and k) — the service computes each query's
     z-stats + centered windows ONCE and reuses them against every shard;
  2. per corpus group (shard x reference length) it stacks the Q x S
     (query, series) pairs into one vmapped engine sweep (padded to a
     power-of-two batch so jit compiles O(log) variants, not one per batch
     size; split by query when the pairs' window matrices would pass
     `DISPATCH_BYTES`) and dispatches it through the async `RoundLoop` —
     host assembly of the next group overlaps device execution of the
     previous one, and `block_until_ready` happens only at delivery;
  3. at delivery it union-merges the per-shard neighbor sets with
     `TopKState.merge` (`lax.top_k` over negated distances with indices
     packed as `sid * stride + position`) — exact for the union because
     shards hold DISJOINT series, the same argument `allreduce_topk` makes
     across workers — into one `ProfileResult` per query.

Faults degrade, they don't fail: a shard that crashes (or exhausts its
`FaultPolicy.max_retries` transient retries) is dropped from the batch and
every affected answer is tagged with the coverage it actually got
(`ProfileResult.fraction_done` = fraction of corpus series consulted), the
same anytime contract the distributed scheduler's supervised runs use. A
query whose deadline lapses in the queue is answered immediately with
coverage 0 instead of holding a batch slot.
"""

from __future__ import annotations

import dataclasses
import time

import numpy as np

from repro import obs
from repro.serve.queue import AdmissionQueue, PendingQuery
from repro.serve.rounds import RoundLoop

# Device bytes one dispatch may spend on the batched sweep's window
# matrices; a batch whose (query, series) pairs need more is split by query.
DISPATCH_BYTES = 1 << 31


@dataclasses.dataclass
class ServeAnswer:
    """One query's answer. `result` is a standard `ProfileResult` (AB kind,
    `fraction_done` = corpus coverage); `series` maps each profile position
    to the WINNING corpus series id (`(l_q,)`, or `(l_q, k)` aligned with
    `result.topk_i` when k > 1), since a multi-series join needs (series,
    position) to name a neighbor, not position alone."""

    qid: int
    result: object                  # ProfileResult
    series: np.ndarray
    coverage: float                 # fraction of corpus series consulted
    status: str                     # "ok" | "degraded" | "expired"
    elapsed: float                  # submit -> answer, seconds
    failed_shards: tuple[int, ...] = ()

    @property
    def ok(self) -> bool:
        return self.status == "ok"


class ProfileService:
    """Batched always-on front-end over a `ShardedCorpus`."""

    def __init__(self, corpus, *, max_pending: int = 64, max_batch: int = 32,
                 depth: int = 2, policy=None, injector=None):
        """`policy` is a `core.faults.FaultPolicy` (retry budget + backoff
        clock for transient shard failures); `injector` a `FaultInjector`
        driving chaos tests — each group dispatch consumes one injector
        tick, `crashed_workers(tick)` naming shards that fail it outright
        and `round_should_fail(tick, attempt)` transient attempts."""
        from repro.core.faults import FaultPolicy

        self.corpus = corpus
        self.queue = AdmissionQueue(corpus.window, max_pending=max_pending,
                                    max_batch=max_batch)
        self.policy = policy if policy is not None else FaultPolicy()
        self.injector = injector
        self._loop = RoundLoop(depth=depth, deliver=self._on_delivered)
        self._ready: list[ServeAnswer] = []
        self._tick = 0
        # packed-neighbor stride: one id space over (series, position)
        self._stride = max(g.l_ref for g in corpus.groups())

    # -- submission --------------------------------------------------------

    def submit(self, values, *, k: int = 1,
               deadline: float | None = None) -> int:
        """Admit one query (raises `QueryRejected` under backpressure);
        returns its qid. `deadline` is a relative budget in seconds."""
        return self.queue.submit(values, k=k, deadline=deadline).qid

    @property
    def stats(self):
        return self.queue.stats

    # -- execution ---------------------------------------------------------

    def step(self, now: float | None = None) -> list[ServeAnswer]:
        """One service step: expire lapsed queries, dispatch the next
        geometry batch across every corpus group, and return whatever
        answers became ready (expirations immediately; batch answers as
        the in-flight window rolls them out — call `drain()` to flush)."""
        now = time.monotonic() if now is None else now
        with obs.span("mp.serve.step", batch=self.stats.batches + 1):
            answers = [self._expired_answer(q, now)
                       for q in self.queue.take_expired(now)]
            batch = self.queue.take_batch(now)
            if batch:
                self._dispatch_batch(batch)
            answers.extend(self._ready)
            self._ready = []
            return answers

    def drain(self) -> list[ServeAnswer]:
        """Deliver every in-flight round and return the finished answers."""
        self._loop.drain()
        out = self._ready
        self._ready = []
        return out

    def serve(self, queries, *, k: int = 1) -> list[ServeAnswer]:
        """Convenience synchronous path: submit `queries`, run the loop to
        completion, return answers in submission order."""
        qids = [self.submit(q, k=k) for q in queries]
        answers = []
        while len(self.queue):
            answers.extend(self.step())
        answers.extend(self.drain())
        order = {qid: n for n, qid in enumerate(qids)}
        return sorted((a for a in answers if a.qid in order),
                      key=lambda a: order[a.qid])

    # -- internals ---------------------------------------------------------

    def _dispatch_batch(self, batch: list[PendingQuery]) -> None:
        from repro.core import plan as plan_mod
        from repro.core.zstats import compute_stats_host

        m = self.corpus.window
        lq, k = batch[0].l_q, batch[0].k
        parts = [compute_stats_host(q.values, m, min_subsequences=1,
                                    return_centered_windows=True)
                 for q in batch]
        groups = self.corpus.groups()
        ids = {"batch": self.stats.batches}
        rec = {"batch": batch, "lq": lq, "k": k, "expected": 0,
               "collected": [], "failed_shards": [], "ids": ids}
        for group in groups:
            tick = self._tick
            self._tick += 1
            if not self._group_survives(tick, group.shard):
                if group.shard not in rec["failed_shards"]:
                    rec["failed_shards"].append(group.shard)
                continue
            # queries per dispatch: as many as keep the sweep's window
            # matrices inside DISPATCH_BYTES (at least one). Each pair's
            # vmapped rowstream builds both sides' (l, m) f32 centered
            # windows for its exact reseed dots.
            ns = len(group.sids)
            per_query = ns * 4 * (lq + group.l_ref) * m
            step = max(1, min(len(batch), DISPATCH_BYTES // per_query))
            for q0 in range(0, len(batch), step):
                q1 = min(q0 + step, len(batch))
                npairs = (q1 - q0) * ns
                pad = 1 << (npairs - 1).bit_length()  # power-of-two bucket
                plan = self.corpus.plan_for(group, lq, k=k, batch=pad)
                with obs.span("mp.serve.assemble", **ids):
                    stats = self.corpus.assemble_batch(group, parts[q0:q1],
                                                       plan)
                with obs.span("mp.serve.dispatch", **ids):
                    res = plan_mod.execute(plan, stats)   # async dispatch
                    if k > 1:
                        payload = {"d": res.topk_dist, "i": res.topk_index}
                    else:
                        payload = {"d": res.dist, "i": res.index}
                    rec["expected"] += 1
                    self._loop.dispatch(payload, meta=(rec, group, q0, q1),
                                        ids=ids)
        if rec["expected"] == 0:
            self._finalize(rec)                       # every shard failed

    def _group_survives(self, tick: int, shard: int) -> bool:
        inj = self.injector
        if inj is None:
            return True
        if shard in inj.crashed_workers(tick):
            return False
        attempt = 0
        while inj.round_should_fail(tick, attempt):
            attempt += 1
            if attempt > self.policy.max_retries:
                return False
            self.policy.sleep(min(
                self.policy.backoff_base * 2 ** (attempt - 1),
                self.policy.backoff_max))
        return True

    def _on_delivered(self, meta, payload) -> None:
        rec, group, q0, q1 = meta
        rec["collected"].append((group, q0, q1, payload))
        if len(rec["collected"]) == rec["expected"]:
            self._finalize(rec)

    def _finalize(self, rec: dict) -> None:
        """Union-merge every delivered group into one answer per query."""
        import jax.numpy as jnp

        from repro.core.matrix_profile import TopKState

        with obs.span("mp.serve.merge", **rec["ids"]):
            batch, lq, k = rec["batch"], rec["lq"], rec["k"]
            nq, stride = len(batch), self._stride
            state = TopKState(corr=jnp.full((nq, lq, k), -jnp.inf, jnp.float32),
                              index=jnp.full((nq, lq, k), -1, jnp.int32))
            covered = 0
            for group, q0, q1, payload in rec["collected"]:
                ns, nb = len(group.sids), q1 - q0
                if q0 == 0:                   # count each group's series once
                    covered += ns
                d = jnp.asarray(payload["d"])[:nb * ns]
                i = jnp.asarray(payload["i"])[:nb * ns]
                if k == 1:
                    d, i = d[..., None], i[..., None]
                # rows are query-major: (q * S + s) -> (Q, S, lq, k); pack the
                # neighbor as a single id so the union is one top_k
                d = jnp.moveaxis(d.reshape(nb, ns, lq, k), 1, 2)
                i = jnp.moveaxis(i.reshape(nb, ns, lq, k), 1, 2)
                sid = jnp.asarray(group.sids, jnp.int32)[None, None, :, None]
                packed = jnp.where(i >= 0, sid * stride + i, -1)
                cand = TopKState(corr=(-d).reshape(nb, lq, ns * k),
                                 index=packed.reshape(nb, lq, ns * k))
                # exact union: shards hold disjoint series, so no neighbor is
                # offered twice (allreduce_topk's argument, applied to shards)
                part = TopKState(corr=state.corr[q0:q1],
                                 index=state.index[q0:q1]).merge(cand)
                state = TopKState(corr=state.corr.at[q0:q1].set(part.corr),
                                  index=state.index.at[q0:q1].set(part.index))
            dist = np.asarray(-state.corr)
            packed = np.asarray(state.index)
            pos = np.where(packed >= 0, packed % stride, -1).astype(np.int32)
            sid = np.where(packed >= 0, packed // stride, -1).astype(np.int32)
            coverage = covered / self.corpus.n_series
            degraded = coverage < 1.0
            now = time.monotonic()
            for n, q in enumerate(batch):
                self._ready.append(self._make_answer(
                    q, dist[n], pos[n], sid[n], k, coverage,
                    "degraded" if degraded else "ok",
                    now, tuple(rec["failed_shards"])))
            self.queue.mark_completed(len(batch),
                                      degraded=len(batch) if degraded else 0)

    def _make_answer(self, q: PendingQuery, dist, pos, sid, k: int,
                     coverage: float, status: str, now: float,
                     failed: tuple) -> ServeAnswer:
        from repro.core.result import ProfileResult

        kwargs = {}
        if k > 1:
            kwargs = {"topk_p": dist, "topk_i": pos}
        result = ProfileResult(
            dist[..., 0], pos[..., 0], kind="ab", window=self.corpus.window,
            exclusion=0, normalize=True, k=k, backend="serve",
            fraction_done=coverage, **kwargs)
        series = sid[..., 0] if k == 1 else sid
        return ServeAnswer(qid=q.qid, result=result, series=series,
                           coverage=coverage, status=status,
                           elapsed=now - q.submitted_at,
                           failed_shards=failed)

    def _expired_answer(self, q: PendingQuery, now: float) -> ServeAnswer:
        """A lapsed-deadline query still gets a VALID `ProfileResult` — the
        coverage-0 anytime answer (all-inf, no neighbors), tagged expired."""
        dist = np.full((q.l_q, q.k), np.inf, np.float32)
        idx = np.full((q.l_q, q.k), -1, np.int32)
        return self._make_answer(q, dist, idx, idx.copy(), q.k, 0.0,
                                 "expired", now, ())
