"""Closed-loop joins: one caller runs whole joins back to back.

Traffic parameters (the cell file's `traffic`):
  join        "self": a self-join of a fresh series of the config's `n`
              samples per call; "ab": a fresh `query_n`-sample query per
              call against one recording of `n` samples made in set-up.
  check_rows  rows of each side compared with the reference, in all:
              spread evenly over the joins of the window.

The window starts with the first join and ends where the join running at
`--seconds` ends; `join_s` is its length over the joins it holds. Each
join's answer is kept and compared afterwards, on rows drawn from the seed,
with the plain float64 reference.
"""

from __future__ import annotations

import time

import numpy as np

import reference
import traffic
import work


def _entry(cfg: dict, kind: str, precision):
    """The call the window drives. Looked up at call time, so a test can
    put a broken entry in its place."""
    m, excl = cfg["window"], cfg["exclusion"]
    if kind == "self":
        from repro.kernels import ops

        def call(x):
            res = ops.natsa_matrix_profile(x, m, exclusion=excl,
                                           precision=precision)
            return res.p, res.i
    else:
        import importlib

        mp = importlib.import_module("repro.core.matrix_profile")

        def call(x, ref):
            res = mp.ab_join(x, ref, m, return_b=True, precision=precision)
            return res.p, res.i, res.b_p, res.b_i
    return call


class Join:
    def __init__(self, cfg: dict, cell: dict, seed: int, span,
                 control: bool = False):
        self.cfg, self.seed, self.span = cfg, seed, span
        self.kind = cell["traffic"]["join"]
        self.check_rows = cell["traffic"]["check_rows"]
        # the control runs the program's own bfloat16-stream path
        self.call = _entry(cfg, self.kind, "bf16" if control else None)
        n, m = cfg["n"], cfg["window"]
        l = n - m + 1
        if self.kind == "self":
            self.n_in = n
            self.cells = work.selfjoin_cells(l, cfg["exclusion"])
            self.recording = None
        else:
            self.n_in = cell["traffic"]["query_n"]
            self.cells = work.ab_cells(self.n_in - m + 1, l)
            self.recording = traffic.walk(seed, traffic.SERIES, 0, n)
        self.answers = []

    def _input(self, j: int, purpose: int = traffic.QUERY) -> np.ndarray:
        """Join j's input: the series to self-join, or the AB query."""
        return traffic.walk(self.seed, purpose, j, self.n_in)

    def _join(self, x):
        import jax

        with self.span("bench.entry"):
            out = (self.call(x) if self.recording is None
                   else self.call(x, self.recording))
        with self.span("bench.block"):
            return jax.block_until_ready(out)

    def warm(self) -> None:
        """One join of the window's shape, through the window's entry."""
        self._join(self._input(0, traffic.WARM))

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        while True:
            self.answers.append(self._join(self._input(len(self.answers))))
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
        joins = len(self.answers)
        return {"kind": "join", "window_s": t1 - t0, "joins": joins,
                "cells_per_join": self.cells, "attempted": joins,
                "failed": 0,
                "metrics": {"join_s": {"value": (t1 - t0) / joins,
                                       "unit": "s"}}}

    def probe(self, record: dict) -> None:
        """Layer readings taken after the window: the host z-stats prep of
        the last join's own arguments, on the host clock."""
        from repro.core.zstats import compute_stats_host

        m = self.cfg["window"]
        x = self._input(record["joins"] - 1)
        t0 = time.perf_counter()
        if self.kind == "self":
            compute_stats_host(x, m)
        else:
            # both sides, as an AB join prepares them (the recording with
            # its centered windows, for the seed dots)
            compute_stats_host(x, m, min_subsequences=1,
                               return_centered_windows=True)
            compute_stats_host(self.recording, m, min_subsequences=1,
                               return_centered_windows=True)
        record["stats_prep_s"] = time.perf_counter() - t0

    def release(self) -> None:
        """Bring the answers to the host and drop the device arrays."""
        self.answers = [tuple(np.asarray(a) for a in ans)
                        for ans in self.answers]

    def check(self) -> dict:
        """Compared numbers: `prof_gap`, the widest gap in correlation
        between a returned profile value and the reference's best match;
        `idx_gap`, the widest gap between the reference's best and the
        exact correlation of the returned neighbour (a neighbour that
        cannot be, out of range or inside the exclusion zone, reads inf);
        `unset_rows`, profile entries left without a neighbour."""
        m, excl = self.cfg["window"], self.cfg["exclusion"]
        joins = len(self.answers)
        rows_each = max(1, -(-self.check_rows // joins))
        prof = idx = 0.0
        unset = 0
        rec = (None if self.recording is None
               else reference.Series(self.recording, m))
        for j, ans in enumerate(self.answers):
            x = self._input(j)
            unset += sum(int(np.sum(~np.isfinite(np.asarray(a, np.float64))))
                         for a in ans[0::2])
            if self.kind == "self":
                sides = [(x, x, ans[0], ans[1], excl, None)]
            else:
                sides = [(x, self.recording, ans[0], ans[1], 0, rec),
                         (self.recording, x, ans[2], ans[3], 0, None)]
            for s, (a, b, p, i, ex, ser) in enumerate(sides):
                rows = traffic.sample(self.seed, 2 * j + s,
                                      a.shape[0] - m + 1, rows_each)
                g, e = _gaps(a, b, rows, p, i, m, ex, ser)
                prof, idx = max(prof, g), max(idx, e)
        return {"prof_gap": prof, "idx_gap": idx, "unset_rows": unset}


def _gaps(a, b, rows, p, i, m: int, excl: int, series):
    best, _ = reference.best_match(a, rows, b, m, excl, series=series)
    got = reference.corr_from_dist(np.asarray(p)[rows], m)
    prof = float(np.max(np.abs(got - best)))
    if np.isnan(prof):
        prof = float("inf")
    l_b = b.shape[0] - m + 1
    idx = 0.0
    for r, j, c in zip(rows, np.asarray(i)[rows], best):
        j = int(j)
        if not 0 <= j < l_b or abs(j - r) < excl:
            return prof, float("inf")
        idx = max(idx, abs(c - reference.pair_corr(a, r, b, j, m)))
    return prof, idx


def make(cfg, cell, seed, span, control=False) -> Join:
    return Join(cfg, cell, seed, span, control)
