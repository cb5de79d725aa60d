"""Profile service: batched == sequential, sharded union == unsharded,
degraded answers under faults/deadlines, and admission backpressure.

The service's correctness contract is BITWISE against direct entry-point
calls: every (query, series) pair flows through `cross_stats_from_parts` +
a vmapped rowstream sweep — vmap keeps each lane's arithmetic identical to
the unbatched rowstream `ab_join` defaults to on these geometries — and
the union merge is an exact top-k over disjoint candidate sets, so the
served profile must equal the elementwise reduction of per-pair joins to
the bit."""

import numpy as np
import pytest

from repro.core import plan as plan_mod
from repro.core.faults import FaultInjector, FaultPolicy
from repro.core.zstats import compute_cross_stats_host
from repro.serve import (AdmissionQueue, ProfileService, QueryRejected,
                         RoundLoop, ShardedCorpus)

WINDOW = 16


def _corpus_series(rng, n_series=5, n=220):
    return [rng.normal(size=n) for _ in range(n_series)]


def _pair_sweep(q, s, m, k=1):
    """Reference: one unbatched rowstream AB sweep of q against s — the
    backend `ab_join` itself picks on these geometries."""
    lq, ls = q.shape[0] - m + 1, s.shape[0] - m + 1
    plan = plan_mod.plan_sweep(m, lq, ls, exclusion=0, harvest="row",
                               k=k, backend="rowstream")
    return plan_mod.execute(plan, compute_cross_stats_host(q, s, m))


def _reference_union(q, series, m):
    """Elementwise min over per-pair sweeps + winning series/pos."""
    lq = q.shape[0] - m + 1
    best_d = np.full(lq, np.inf, np.float32)
    best_s = np.full(lq, -1, np.int64)
    best_i = np.full(lq, -1, np.int64)
    for sid, s in enumerate(series):
        r = _pair_sweep(q, s, m)
        d, i = np.asarray(r.dist), np.asarray(r.index)
        take = d < best_d
        best_d = np.where(take, d, best_d)
        best_s = np.where(take, sid, best_s)
        best_i = np.where(take, i, best_i)
    return best_d, best_s, best_i


@pytest.mark.parametrize("dispatch_bytes", [None, 1])
def test_batched_service_matches_sequential_engine_bitwise(monkeypatch,
                                                           dispatch_bytes):
    """The headline equality: a batch of concurrent queries answered by the
    service is BITWISE-equal (distances, winning series, positions) to
    looping per-(query, series) sweeps and reducing on the host — also when
    the memory bound splits the batch into one dispatch per query."""
    from repro.serve import frontend

    if dispatch_bytes is not None:
        monkeypatch.setattr(frontend, "DISPATCH_BYTES", dispatch_bytes)
    rng = np.random.default_rng(0)
    series = _corpus_series(rng)
    corpus = ShardedCorpus(series, WINDOW, n_shards=2)
    svc = ProfileService(corpus)
    queries = [rng.normal(size=150) for _ in range(4)]

    answers = svc.serve(queries)
    assert [a.status for a in answers] == ["ok"] * 4
    for q, a in zip(queries, answers):
        d_ref, s_ref, i_ref = _reference_union(q, series, WINDOW)
        np.testing.assert_array_equal(np.asarray(a.result.p), d_ref)
        np.testing.assert_array_equal(np.asarray(a.series), s_ref)
        np.testing.assert_array_equal(np.asarray(a.result.i), i_ref)
        assert a.result.kind == "ab" and a.result.fraction_done == 1.0


def test_service_matches_default_ab_join_values():
    """Against the DEFAULT `ab_join` entry point (which may pick rowstream,
    a different-but-exact accumulation order): indices match exactly and
    distances to fp tolerance."""
    from repro.core.matrix_profile import ab_join

    rng = np.random.default_rng(1)
    series = _corpus_series(rng, n_series=3)
    corpus = ShardedCorpus(series, WINDOW)
    q = rng.normal(size=140)
    [a] = ProfileService(corpus).serve([q])
    lq = q.shape[0] - WINDOW + 1
    best_d = np.full(lq, np.inf)
    best_i = np.full(lq, -1)
    for sid, s in enumerate(series):
        r = ab_join(q, s, WINDOW)
        take = np.asarray(r.p) < best_d
        best_d = np.where(take, r.p, best_d)
        best_i = np.where(take, r.i, best_i)
    np.testing.assert_array_equal(np.asarray(a.result.i), best_i)
    np.testing.assert_allclose(np.asarray(a.result.p), best_d,
                               rtol=1e-5, atol=1e-5)


def test_sharded_topk_union_equals_unsharded():
    """k > 1: the per-shard union must equal the top-k over ALL series'
    candidate sets at once — shard boundaries cannot change the answer."""
    rng = np.random.default_rng(2)
    series = _corpus_series(rng, n_series=6)
    k = 3
    q = rng.normal(size=130)
    lq = q.shape[0] - WINDOW + 1

    # unsharded reference: stable sort over every series' top-k candidates
    cand_d, cand_i, cand_s = [], [], []
    for sid, s in enumerate(series):
        r = _pair_sweep(q, s, WINDOW, k=k)
        cand_d.append(np.asarray(r.topk_dist))
        cand_i.append(np.asarray(r.topk_index))
        cand_s.append(np.full((lq, k), sid))
    D = np.concatenate(cand_d, axis=1)
    order = np.argsort(D, axis=1, kind="stable")[:, :k]
    d_ref = np.take_along_axis(D, order, 1)
    i_ref = np.take_along_axis(np.concatenate(cand_i, axis=1), order, 1)
    s_ref = np.take_along_axis(np.concatenate(cand_s, axis=1), order, 1)

    for n_shards in (1, 2, 3):
        corpus = ShardedCorpus(series, WINDOW, n_shards=n_shards)
        [a] = ProfileService(corpus).serve([q], k=k)
        np.testing.assert_array_equal(np.asarray(a.result.topk_p), d_ref)
        np.testing.assert_array_equal(np.asarray(a.result.topk_i), i_ref)
        np.testing.assert_array_equal(np.asarray(a.series), s_ref)


def test_mixed_geometry_batches_split_and_all_answer():
    """Queries of different lengths can't share a vmapped sweep — the
    batcher buckets them, and every query still gets a full answer."""
    rng = np.random.default_rng(3)
    series = _corpus_series(rng, n_series=3)
    corpus = ShardedCorpus(series, WINDOW)
    svc = ProfileService(corpus)
    queries = [rng.normal(size=n) for n in (100, 150, 100, 150, 100)]
    answers = svc.serve(queries)
    assert svc.stats.batches >= 2            # at least one per geometry
    for q, a in zip(queries, answers):
        d_ref, s_ref, _ = _reference_union(q, series, WINDOW)
        np.testing.assert_array_equal(np.asarray(a.result.p), d_ref)
        np.testing.assert_array_equal(np.asarray(a.series), s_ref)


def test_shard_failure_degrades_answer_with_partial_coverage():
    """A crashed shard drops ITS series from the union; the answer is still
    a valid ProfileResult over the survivors, tagged with the coverage it
    got (fraction_done < 1) and the failed shard id."""
    rng = np.random.default_rng(4)
    series = _corpus_series(rng, n_series=4)
    corpus = ShardedCorpus(series, WINDOW, n_shards=2)
    # shard 0 crashes on the first group dispatch (tick 0)
    inj = FaultInjector(worker_crashes={0: (0,)})
    svc = ProfileService(corpus, injector=inj,
                         policy=FaultPolicy(sleep=lambda _t: None))
    q = rng.normal(size=150)
    [a] = svc.serve([q])

    assert a.status == "degraded" and a.failed_shards == (0,)
    survivors = [s for sid, s in enumerate(series)
                 if corpus.shard_of(sid) != 0]
    assert a.coverage == pytest.approx(len(survivors) / len(series))
    assert a.result.fraction_done == a.coverage
    d_ref = np.full(q.shape[0] - WINDOW + 1, np.inf, np.float32)
    for s in survivors:
        d_ref = np.minimum(d_ref, np.asarray(_pair_sweep(q, s, WINDOW).dist))
    np.testing.assert_array_equal(np.asarray(a.result.p), d_ref)
    # winning series ids must all live on the surviving shard
    assert all(corpus.shard_of(int(sid)) == 1 for sid in a.series)
    assert svc.stats.degraded == 1


def test_transient_failures_retry_then_succeed_or_degrade():
    """Transient round failures within the FaultPolicy retry budget are
    invisible; beyond it the shard degrades the batch."""
    rng = np.random.default_rng(5)
    series = _corpus_series(rng, n_series=2)
    corpus = ShardedCorpus(series, WINDOW, n_shards=2)
    policy = FaultPolicy(max_retries=3, sleep=lambda _t: None)

    # 2 failures on tick 0 < budget: full answer
    svc = ProfileService(corpus, injector=FaultInjector(round_failures={0: 2}),
                         policy=policy)
    [a] = svc.serve([rng.normal(size=120)])
    assert a.status == "ok" and a.coverage == 1.0

    # 5 failures on tick 0 > budget: shard 0 dropped
    svc = ProfileService(corpus, injector=FaultInjector(round_failures={0: 5}),
                         policy=policy)
    [a] = svc.serve([rng.normal(size=120)])
    assert a.status == "degraded" and a.coverage == 0.5
    assert a.failed_shards == (0,)


def test_all_shards_failed_still_answers_with_zero_coverage():
    rng = np.random.default_rng(6)
    corpus = ShardedCorpus(_corpus_series(rng, n_series=2), WINDOW,
                           n_shards=2)
    inj = FaultInjector(worker_crashes={0: (0,), 1: (1,)})
    svc = ProfileService(corpus, injector=inj,
                         policy=FaultPolicy(sleep=lambda _t: None))
    [a] = svc.serve([rng.normal(size=100)])
    assert a.status == "degraded" and a.coverage == 0.0
    assert np.all(np.isinf(np.asarray(a.result.p)))
    assert np.all(np.asarray(a.result.i) == -1)


def test_deadline_expired_query_answers_degraded_not_lost():
    """A query whose deadline lapses in the queue is answered immediately:
    a VALID coverage-0 ProfileResult tagged expired, never silently
    dropped, and it frees its queue slot."""
    rng = np.random.default_rng(7)
    corpus = ShardedCorpus(_corpus_series(rng, n_series=2), WINDOW)
    svc = ProfileService(corpus)
    qid = svc.submit(rng.normal(size=100), deadline=0.0)
    live = svc.submit(rng.normal(size=100))

    import time
    time.sleep(0.005)
    answers = svc.step() + svc.drain()
    by_qid = {a.qid: a for a in answers}
    a = by_qid[qid]
    assert a.status == "expired" and a.coverage == 0.0
    assert a.result.fraction_done == 0.0
    assert np.all(np.isinf(np.asarray(a.result.p)))
    assert by_qid[live].status == "ok"       # the live query is unaffected
    assert svc.stats.expired == 1 and svc.stats.pending == 0


def test_backpressure_rejects_instead_of_growing():
    rng = np.random.default_rng(8)
    corpus = ShardedCorpus(_corpus_series(rng, n_series=2), WINDOW)
    svc = ProfileService(corpus, max_pending=3)
    for _ in range(3):
        svc.submit(rng.normal(size=100))
    with pytest.raises(QueryRejected):
        svc.submit(rng.normal(size=100))
    assert svc.stats.rejected == 1 and svc.stats.pending == 3
    while len(svc.queue):
        svc.step()
    assert len(svc.drain()) == 3
    svc.submit(rng.normal(size=100))         # slot freed after completion


def test_admission_queue_buckets_by_geometry_oldest_first():
    q = AdmissionQueue(WINDOW, max_pending=8, max_batch=8)
    a = q.submit(np.zeros(100))
    b = q.submit(np.zeros(150))
    c = q.submit(np.zeros(100))
    d = q.submit(np.zeros(100), k=3)         # same l_q, different k
    batch = q.take_batch()
    assert [p.qid for p in batch] == [a.qid, c.qid]
    assert [p.qid for p in q.take_batch()] == [b.qid]
    assert [p.qid for p in q.take_batch()] == [d.qid]
    with pytest.raises(ValueError):
        q.submit(np.zeros(4))                # shorter than the window


def test_corpus_reload_bumps_generation_and_serves_fresh_stats():
    """Satellite regression: the shared ReferenceCache generation contract
    holds through the corpus — a same-length reload must change answers."""
    rng = np.random.default_rng(9)
    series = [rng.normal(size=160), rng.normal(size=160)]
    corpus = ShardedCorpus(series, WINDOW)
    svc = ProfileService(corpus)
    q = rng.normal(size=100)
    [before] = svc.serve([q])

    fresh = rng.normal(size=160)
    corpus.reload(1, fresh)
    [after] = svc.serve([q])
    d_ref, s_ref, _ = _reference_union(q, [series[0], fresh], WINDOW)
    np.testing.assert_array_equal(np.asarray(after.result.p), d_ref)
    assert not np.array_equal(np.asarray(before.result.p),
                              np.asarray(after.result.p))


def test_corpus_rejects_nonnorm_and_bad_series():
    rng = np.random.default_rng(10)
    with pytest.raises(ValueError, match="z-normalized"):
        ShardedCorpus([rng.normal(size=100)], WINDOW, normalize=False)
    with pytest.raises(ValueError, match="at least one"):
        ShardedCorpus([], WINDOW)
    with pytest.raises(ValueError, match="1-D"):
        ShardedCorpus([np.zeros((4, 4))], WINDOW)


def test_round_loop_bounds_inflight_and_preserves_order():
    delivered = []
    loop = RoundLoop(depth=2, deliver=lambda m, _p: delivered.append(m))
    import jax.numpy as jnp

    for n in range(5):
        loop.dispatch(jnp.zeros(4) + n, meta=n)
        assert len(loop) <= 2
    loop.drain()
    assert delivered == [0, 1, 2, 3, 4]
    assert loop.dispatched == loop.delivered == 5
    with pytest.raises(RuntimeError):
        loop.deliver_next()
