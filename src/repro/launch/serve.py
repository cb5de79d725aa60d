"""Profile-service driver: a resident sharded corpus answering AB queries.

  PYTHONPATH=src python -m repro.launch.serve --series 16 --n 4000 \
      --window 64 --queries 32 --k 1

Loads `--series` synthetic reference series ONCE into a `ShardedCorpus`
(z-stats + centered windows resident, shards device-placed across the
worker mesh when more than one device is visible), then pushes `--queries`
concurrent AB-join queries through the batched `ProfileService` front-end
and reports throughput. Run with
`XLA_FLAGS=--xla_force_host_platform_device_count=N` to shard across N
host devices.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def run_service(n_series: int, n: int, window: int, n_queries: int,
                query_n: int, k: int, *, seed: int = 0,
                use_mesh: bool = True):
    """Build corpus + service, answer the query load, return a report."""
    import jax

    from repro.launch.mesh import make_worker_mesh
    from repro.serve import ProfileService, ShardedCorpus

    rng = np.random.default_rng(seed)
    series = [rng.normal(size=n) for _ in range(n_series)]
    mesh = None
    if use_mesh and len(jax.devices()) > 1:
        mesh = make_worker_mesh()

    t0 = time.monotonic()
    corpus = ShardedCorpus(series, window, mesh=mesh)
    t_load = time.monotonic() - t0

    svc = ProfileService(corpus, max_pending=max(64, n_queries),
                         max_batch=n_queries)
    queries = [rng.normal(size=query_n) for _ in range(n_queries)]
    svc.serve(queries[:1], k=k)               # warm the compiled variants

    t0 = time.monotonic()
    answers = svc.serve(queries, k=k)
    t_serve = time.monotonic() - t0
    return {
        "mesh_devices": 1 if mesh is None else mesh.devices.size,
        "shards": corpus.n_shards,
        "load_s": t_load,
        "serve_s": t_serve,
        "qps": n_queries / t_serve,
        "answers": answers,
        "stats": svc.stats,
        "series": series,
        "queries": queries,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--series", type=int, default=16,
                    help="reference series resident in the corpus")
    ap.add_argument("--n", type=int, default=4000,
                    help="points per reference series")
    ap.add_argument("--window", type=int, default=64)
    ap.add_argument("--queries", type=int, default=32,
                    help="concurrent queries pushed through the front-end")
    ap.add_argument("--query-n", type=int, default=512,
                    help="points per query")
    ap.add_argument("--k", type=int, default=1,
                    help="neighbors per profile position")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-mesh", action="store_true",
                    help="skip device sharding even when devices > 1")
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    rep = run_service(args.series, args.n, args.window, args.queries,
                      args.query_n, args.k, seed=args.seed,
                      use_mesh=not args.no_mesh)
    print(f"[serve] corpus: {args.series} series x {args.n} pts, "
          f"{rep['shards']} shards on {rep['mesh_devices']} device(s), "
          f"resident in {rep['load_s']:.2f}s")
    print(f"[serve] {args.queries} queries (m={args.window}, k={args.k}) in "
          f"{rep['serve_s']:.2f}s -> {rep['qps']:.1f} queries/s")
    a = rep["answers"][0]
    print(f"[serve] sample answer: status={a.status} coverage={a.coverage:.2f}"
          f" best d={float(np.min(a.result.p)):.4f} "
          f"(series {int(a.series[int(np.argmin(a.result.p))])})")
    print(f"[serve] queue: {rep['stats']}")
    return rep


if __name__ == "__main__":
    main()
