"""The program's spans and counters (`repro.obs`): every span of the profile
entries and the service lands in a profiler trace, the prep spans nest on
one thread, and the counters count what the work did."""

import glob
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.core.matrix_profile import ab_join
from repro.core.zstats import compute_cross_stats_host, compute_stats_host
from repro.kernels import ops
from repro.serve import AdmissionQueue, ProfileService, ShardedCorpus

SPANS = ("mp.stats", "mp.seeds", "mp.put", "mp.sweep",
         "mp.finish", "mp.serve.step", "mp.serve.take", "mp.serve.assemble",
         "mp.serve.dispatch", "mp.serve.deliver", "mp.serve.merge")


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    """{line id: [(start, end, name, args)]} of the `mp.` host spans of a
    kernel self-join, a two-sided AB join and one service batch."""
    from jax.profiler import ProfileData

    rng = np.random.default_rng(7)
    m = 32
    corpus = ShardedCorpus([np.cumsum(rng.normal(size=512))
                            for _ in range(4)], m)
    svc = ProfileService(corpus, max_batch=2)
    ts = np.cumsum(rng.normal(size=1024))
    q = np.cumsum(rng.normal(size=256))
    out = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(out):
        jax.block_until_ready(ops.natsa_matrix_profile(ts, m).p)
        jax.block_until_ready(ab_join(q, ts, m, return_b=True).b_p)
        svc.submit(q[:128])
        svc.step()
        assert len(svc.drain()) == 1
    (path,) = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                        recursive=True)
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for n, line in enumerate(plane.lines):
                lines[(plane.name, n)] = [
                    (e.start_ns, e.end_ns, e.name, dict(e.stats))
                    for e in line.events if e.name.startswith("mp.")]
    return lines


def test_every_span_lands_in_the_trace(spans):
    seen = {name for events in spans.values() for _, _, name, _ in events}
    assert set(SPANS) <= seen, sorted(set(SPANS) - seen)


def test_service_spans_carry_the_batch(spans):
    serve = [(name, args) for events in spans.values()
             for _, _, name, args in events if name.startswith("mp.serve.")]
    assert {name for name, _ in serve} == {s for s in SPANS
                                           if s.startswith("mp.serve.")}
    assert all(args.get("batch") == 1 for _, args in serve), serve


def test_sweep_span_names_its_backend(spans):
    """The kernel self-join, the AB join and the service batch: the planner's
    route is an argument of each `mp.sweep`."""
    backends = [args.get("backend") for events in spans.values()
                for _, _, name, args in events if name == "mp.sweep"]
    assert len(backends) == 3
    assert set(backends) == {"kernel", "rowstream"}, backends


def test_seeds_and_put_nest_inside_stats_on_one_thread(spans):
    n_stats = 0
    for events in spans.values():
        for s, e, name, _ in events:
            if name != "mp.stats":
                continue
            n_stats += 1
            inside = {n for s2, e2, n, _ in events if s <= s2 and e2 <= e}
            assert {"mp.seeds", "mp.put"} <= inside
    # one span per prep: the self-join series, the AB join (both sides
    # under one span) and the service's one query
    assert n_stats == 3


def test_window_bytes_count_each_centered_window_matrix():
    from repro.core.resident import build_side

    rng = np.random.default_rng(3)
    m = 16
    a, b = rng.normal(size=200), rng.normal(size=90)
    l_a, l_b = a.size - m + 1, b.size - m + 1
    before = obs.counters()
    compute_stats_host(a, m)
    mid = obs.counters()
    compute_cross_stats_host(a, b, m)
    after = obs.counters()
    build_side(a, m)
    kept = obs.counters()
    # the fresh entries stream their windows; a resident side keeps its
    # matrix
    wb = [c.get("mp.window_bytes", 0) for c in (before, mid, after, kept)]
    assert wb[0] == wb[1] == wb[2]
    assert wb[3] - wb[2] == l_a * m * 8
    assert kept["mp.window_blocks"] == after["mp.window_blocks"]
    # six f32 streams of one series, then two sides and the f32 seeds
    assert mid["mp.put_bytes"] - before.get("mp.put_bytes", 0) \
        == 4 * (a.size + 5 * l_a)
    assert after["mp.put_bytes"] - mid["mp.put_bytes"] \
        == 4 * (a.size + 5 * l_a + b.size + 5 * l_b + l_a + l_b - 1)


def test_fresh_stats_stream_row_blocks_in_bounded_memory():
    """At n = 2^18, m = 1024 the (l, m) f64 window matrix would be 2.1 GB
    per side: the fresh entries build none, stay under 64 MB of host
    allocations, and stream each side in ceil(l / rows) row blocks."""
    import tracemalloc

    from repro.core import zstats

    m = 1024
    rng = np.random.default_rng(11)
    a = np.cumsum(rng.normal(size=2 ** 18))
    b = np.cumsum(rng.normal(size=2 ** 18 - 4099))
    l_a, l_b = a.size - m + 1, b.size - m + 1
    blocks = lambda l: -(-l // zstats._block_rows(m))
    for run, sides in ((lambda: compute_stats_host(a, m), [l_a]),
                       (lambda: compute_cross_stats_host(a, b, m),
                        [l_a, l_b])):
        before = obs.counters()
        tracemalloc.start()
        try:
            run()
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        after = obs.counters()
        assert peak < 64 * 2 ** 20, peak
        assert after.get("mp.window_bytes", 0) \
            == before.get("mp.window_bytes", 0)
        assert after["mp.window_blocks"] \
            - before.get("mp.window_blocks", 0) == sum(map(blocks, sides))


def test_counters_are_a_copy():
    obs.count("test.obs", 2)
    c = obs.counters()
    c["test.obs"] += 5
    obs.count("test.obs", 1)
    assert obs.counters()["test.obs"] - c["test.obs"] == -4


def test_queue_sums_the_wait_of_the_queries_it_takes():
    queue = AdmissionQueue(window=4, max_batch=8)
    queue.submit(np.arange(10.0), now=1.0)
    queue.submit(np.arange(10.0), now=2.5)
    queue.submit(np.arange(12.0), now=3.0)          # another geometry
    queue.submit(np.arange(10.0), deadline=0.5, now=3.0)
    assert len(queue.take_expired(now=3.75)) == 1   # expired, not taken
    assert len(queue.take_batch(now=4.0)) == 2
    assert queue.stats.taken == 2
    assert queue.stats.waited_s == pytest.approx((4.0 - 1.0) + (4.0 - 2.5))
    assert len(queue.take_batch(now=10.0)) == 1
    assert queue.stats.taken == 3
    assert queue.stats.waited_s == pytest.approx(4.5 + 7.0)


def test_service_launcher_reports_the_counters_of_its_timed_load():
    from repro.launch.serve import run_service

    n_queries, query_n, m = 4, 100, 16
    rep = run_service(3, 600, m, n_queries, query_n, 1, use_mesh=False)
    # one centered-window matrix per query; the corpus was built before
    assert rep["counters"]["mp.window_bytes"] \
        == n_queries * (query_n - m + 1) * m * 8
    assert rep["counters"]["mp.put_bytes"] > 0
    assert rep["stats"].taken == 1 + n_queries
