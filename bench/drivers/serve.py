"""Open-loop queries against a resident corpus through `ProfileService`.

Traffic parameters (the cell file's `traffic`):
  rate_per_s     queries offered per second, fixed below the knee
  query_n        samples per query (fresh random walk each)
  k              neighbours per profile position
  deadline_s     each query's deadline, relative to its submission
  check_queries  answered queries compared with the reference
  check_rows     rows of each compared query

One caller thread drives the service as its API asks: it submits every
query that is due, calls `step`, and when nothing is queued calls `drain`
to take the answers in flight, then sleeps until the next query is due.
Due times are Poisson gaps in one fixed order (`traffic.arrivals`), and each
query's latency runs from its due time to the return of the call that
handed its answer back. The window is the due times within `--seconds`,
plus the time the last answer takes.
"""

from __future__ import annotations

import time

import numpy as np

import reference
import traffic
import work

GRACE_S = 60.0       # wait past the window for answers before giving up


class Serve:
    def __init__(self, cfg: dict, cell: dict, seed: int, span,
                 control: bool = False):
        from repro.serve import ProfileService, ShardedCorpus

        self.cfg, self.seed, self.span = cfg, seed, span
        self.t = cell["traffic"]
        self.control = control
        m = cfg["window"]
        self.corpus = [traffic.walk(seed, traffic.CORPUS, s, cfg["n"])
                       for s in range(cfg["n_series"])]
        self.svc = ProfileService(ShardedCorpus(self.corpus, m),
                                  max_pending=cfg["max_pending"],
                                  max_batch=cfg["max_batch"])
        self.l_q = self.t["query_n"] - m + 1
        self.cells = work.service_cells(cfg["n_series"], self.l_q,
                                        cfg["n"] - m + 1)
        self.queries: list = []
        self.answers: dict = {}

    def _query(self, purpose: int, index: int) -> np.ndarray:
        return traffic.walk(self.seed, purpose, index, self.t["query_n"])

    def warm(self) -> None:
        """Batches of every size the window can form, through the same
        submit/step/drain calls (each size is its own merge shape)."""
        n = 0
        for size in range(1, self.cfg["max_batch"] + 1):
            for _ in range(size):
                self.svc.submit(self._query(traffic.WARM, n), k=self.t["k"])
                n += 1
            self.svc.step()
            self.svc.drain()

    def window(self, seconds: float, rate: float | None = None) -> dict:
        """Offer the cell's rate (or `rate`, for a sweep) for `seconds`."""
        from repro.serve.queue import QueryRejected

        svc, span = self.svc, self.span
        due = traffic.arrivals(rate or self.t["rate_per_s"], seconds)
        n = due.size
        self.queries = [None] * n
        late = np.zeros(n)
        done = np.full(n, np.nan)
        status = ["unanswered"] * n
        k_of: dict = {}
        self.answers = {}

        def take(answers):
            now = time.perf_counter() - t0
            for a in answers:
                k = k_of.pop(a.qid)
                done[k], status[k] = now, a.status
                self.answers[k] = a

        t0 = time.perf_counter()
        k = 0
        while k < n or k_of:
            now = time.perf_counter() - t0
            if now > seconds + GRACE_S:
                break
            with span("bench.submit"):
                while k < n and due[k] <= now:
                    q = self._query(traffic.QUERY, k)
                    self.queries[k] = q
                    try:
                        qid = svc.submit(q, k=self.t["k"],
                                         deadline=self.t["deadline_s"])
                    except QueryRejected:
                        status[k] = "rejected"
                    else:
                        k_of[qid] = k
                    late[k] = time.perf_counter() - t0 - due[k]
                    k += 1
            with span("bench.step"):
                take(svc.step())
            if len(svc.queue):
                continue
            with span("bench.drain"):
                take(svc.drain())
            if k < n:
                wait = due[k] - (time.perf_counter() - t0)
                if wait > 0:
                    with span("bench.idle"):
                        time.sleep(wait)
        t1 = time.perf_counter() - t0
        ok = np.array([s == "ok" for s in status])
        deadline_ms = 1e3 * self.t["deadline_s"]
        lat = 1e3 * (done - due)
        # a query that failed counts as missing the deadline
        lat = np.where(ok, lat, np.fmax(np.nan_to_num(lat, nan=0.0),
                                        deadline_ms))
        return {"kind": "serve", "window_s": t1, "queries": n,
                "answered": int(ok.sum()), "cells_per_query": self.cells,
                "late_ms_p90": float(np.percentile(1e3 * late, 90)),
                "unanswered": status.count("unanswered"),
                "attempted": n, "failed": int(n - ok.sum()),
                "metrics": {"query_p90_ms": {
                    "value": float(np.percentile(lat, 90)), "unit": "ms"}}}

    def probe(self, record: dict) -> None:
        """The lateness of the generator was read in the window."""

    def release(self) -> None:
        self.svc = None

    def check(self) -> dict:
        """Compared numbers, over a sample of the answered queries drawn
        from the seed: `prof_gap`, the widest gap in correlation between an
        answer's profile value and the reference's best match over the
        whole corpus; `idx_gap`, the widest gap between that best and the
        exact correlation of the answer's (series, position); and
        `unanswered`, queries that never got an answer. The control puts
        the bfloat16 reference's answers in the service's place."""
        m = self.cfg["window"]
        done = sorted(k for k, a in self.answers.items() if a.status == "ok")
        unanswered = sum(q is not None and k not in self.answers
                         for k, q in enumerate(self.queries))
        if not done:
            return {"prof_gap": float("inf"), "idx_gap": float("inf"),
                    "unanswered": unanswered}
        picks = traffic.sample(self.seed, 0, len(done),
                               self.t["check_queries"])
        prof = idx = 0.0
        for k in (done[p] for p in picks):
            q, a = self.queries[k], self.answers[k]
            rows = traffic.sample(self.seed, 1 + k, self.l_q,
                                  self.t["check_rows"])
            best, _, _ = reference.best_match_corpus(q, rows, self.corpus, m)
            if self.control:
                got, sid, pos = reference.best_match_corpus(
                    q, rows, self.corpus, m, lowp=True)
            else:
                got = reference.corr_from_dist(
                    np.asarray(a.result.p)[rows], m)
                sid = np.asarray(a.series)[rows]
                pos = np.asarray(a.result.i)[rows]
            g = float(np.max(np.abs(got - best)))
            prof = max(prof, g if not np.isnan(g) else float("inf"))
            for r, s, j, c in zip(rows, sid, pos, best):
                s, j = int(s), int(j)
                if not (0 <= s < len(self.corpus)
                        and 0 <= j <= self.cfg["n"] - m):
                    idx = float("inf")
                    continue
                idx = max(idx, abs(c - reference.pair_corr(
                    q, r, self.corpus[s], j, m)))
        return {"prof_gap": prof, "idx_gap": idx, "unanswered": unanswered}


def make(cfg, cell, seed, span, control=False) -> Serve:
    return Serve(cfg, cell, seed, span, control)
