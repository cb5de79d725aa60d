"""Plain reference for z-normalized matrix-profile answers, in float64.

It imports nothing of the system under test and takes nothing it made:
only the raw series that the benchmark generated and the answers that
the timed calls returned. Correlation space is used throughout: for
z-normalized windows of length m, distance d and Pearson correlation c
are tied by d**2 = 2 m (1 - c).

`best_match_corpus(..., lowp=True)` is a control: the same search computed
in bfloat16 (unit-norm windows rounded to bfloat16, products summed in
float32), the precision step below the float32 sweep the configurations
state.
"""

from __future__ import annotations

import numpy as np


def corr_from_dist(p, m: int) -> np.ndarray:
    """Distance profile -> correlation; non-finite distances -> -inf."""
    p = np.asarray(p, np.float64)
    return np.where(np.isfinite(p), 1.0 - p * p / (2.0 * m), -np.inf)


def _centered_norms(x: np.ndarray, m: int) -> np.ndarray:
    """Centered L2 norm of every length-m window of `x` (f64)."""
    c1 = np.concatenate([[0.0], np.cumsum(x)])
    c2 = np.concatenate([[0.0], np.cumsum(x * x)])
    s1, s2 = c1[m:] - c1[:-m], c2[m:] - c2[:-m]
    return np.sqrt(np.maximum(s2 - s1 * s1 / m, 0.0))


def unit_window(x, i: int, m: int) -> np.ndarray:
    """Window i of `x`, centered and scaled to unit norm (zeros if flat)."""
    w = np.asarray(x[i:i + m], np.float64)
    w = w - w.mean()
    nrm = np.linalg.norm(w)
    return w / nrm if nrm > 0 else w


def pair_corr(a, i: int, b, j: int, m: int) -> float:
    """Correlation of window i of `a` with window j of `b`."""
    return float(unit_window(a, i, m) @ unit_window(b, j, m))


class Series:
    """One column-side series prepared once for many row queries: its
    centered FFT and window norms."""

    def __init__(self, b, m: int):
        b = np.asarray(b, np.float64)
        self.m = m
        self.n_cols = b.shape[0] - m + 1
        self.size = 1 << (b.shape[0] + m - 1).bit_length()
        c = b - b.mean()
        self.fft = np.fft.rfft(c, self.size)
        self.norms = _centered_norms(c, m)

    def corr_rows(self, q: np.ndarray) -> np.ndarray:
        """Correlation of each unit window in `q` (rows, m) with every
        window of the series: (rows, n_cols). The query windows sum to 0,
        so the series' window means drop out of the dots."""
        m = self.m
        fq = np.fft.rfft(q[:, ::-1], self.size, axis=1)
        dots = np.fft.irfft(fq * self.fft[None, :], self.size, axis=1)
        dots = dots[:, m - 1:m - 1 + self.n_cols]
        nrm = self.norms[None, :]
        return np.where(nrm > 0, dots / np.maximum(nrm, 1e-300), 0.0)


def best_match(a, rows, b, m: int, excl: int = 0, *, series=None,
               chunk: int = 8):
    """Exact best correlation, and its column, of each row window of `a`
    against every window of `b`; with `excl`, columns j with |j - row| <
    excl are banned (the self-join exclusion zone). `series` reuses a
    prepared `Series(b, m)`."""
    s = series if series is not None else Series(b, m)
    rows = np.asarray(rows, int)
    best = np.empty(rows.size)
    arg = np.empty(rows.size, int)
    cols = np.arange(s.n_cols)
    for c0 in range(0, rows.size, chunk):
        r = rows[c0:c0 + chunk]
        q = np.stack([unit_window(a, i, m) for i in r])
        corr = s.corr_rows(q)
        if excl:
            corr = np.where(np.abs(cols[None, :] - r[:, None]) < excl,
                            -np.inf, corr)
        arg[c0:c0 + chunk] = corr.argmax(axis=1)
        best[c0:c0 + chunk] = corr.max(axis=1)
    return best, arg


def _bf16(x) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even)."""
    u = np.asarray(x, np.float32).view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32)


def unit_windows(x, m: int) -> np.ndarray:
    """Every window of `x`, centered and scaled to unit norm: (l, m)."""
    w = np.lib.stride_tricks.sliding_window_view(
        np.asarray(x, np.float64), m)
    w = w - w.mean(axis=1, keepdims=True)
    nrm = np.linalg.norm(w, axis=1, keepdims=True)
    return np.where(nrm > 0, w / np.maximum(nrm, 1e-300), 0.0)


def best_match_corpus(q, rows, corpus, m: int, *, lowp: bool = False):
    """Best correlation of each row window of query `q` over every window
    of every corpus series, with the winning (series, column). Exact f64
    dots, or with `lowp` the bfloat16 control."""
    rows = np.asarray(rows, int)
    qw = np.stack([unit_window(q, i, m) for i in rows])
    if lowp:
        qw = _bf16(qw)
    best = np.full(rows.size, -np.inf)
    sid = np.full(rows.size, -1)
    col = np.full(rows.size, -1)
    for s, x in enumerate(corpus):
        w = unit_windows(x, m)
        corr = (qw @ _bf16(w).T) if lowp else qw @ w.T
        j = corr.argmax(axis=1)
        c = corr[np.arange(rows.size), j]
        take = c > best
        best = np.where(take, c, best)
        sid = np.where(take, s, sid)
        col = np.where(take, j, col)
    return best, sid, col

