"""Algorithmic cell counts: how many (row, column) cells of the distance
matrix one answer requires, whatever implements the sweep.

These counts are the benchmark's yardstick for work rates
(`sweep_gcells_s.*`); they follow from the problem's shape alone.
"""


def selfjoin_cells(l: int, excl: int) -> int:
    """Cells of a self-join over `l` subsequences with exclusion zone
    `excl`: every diagonal k in [excl, l) of the upper triangle, each
    holding l - k cells (the lower triangle is the same cells mirrored)."""
    n = max(l - excl, 0)
    return n * (n + 1) // 2          # sum(l - k for k in range(excl, l))


def ab_cells(l_a: int, l_b: int) -> int:
    """Cells of an AB join: every subsequence of A against every one of B."""
    return l_a * l_b


def service_cells(n_series: int, l_q: int, l_ref: int) -> int:
    """Cells of one service query: the query's `l_q` subsequences against
    the `l_ref` subsequences of each of `n_series` corpus series."""
    return n_series * l_q * l_ref
