"""Quickstart: exact matrix profile on a synthetic ECG-like series.

Profile API v2: `matrix_profile` returns a rich `ProfileResult` — merged
profile (`.p`/`.i`), LEFT/RIGHT split profiles, and (with `k > 1`) exact
top-k neighbor sets — and the `analytics` layer turns it into motifs and
discords without re-sweeping. The NATSA Pallas kernel (interpret mode on
CPU) returns the same object.

    PYTHONPATH=src python examples/quickstart.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.core import analytics
from repro.core.matrix_profile import matrix_profile
from repro.kernels import ops


def main():
    n, m = 6000, 120
    # smooth aperiodic background (low-pass random walk)
    rng = np.random.default_rng(5)
    walk = np.cumsum(rng.normal(size=n + 40))
    ts = np.convolve(walk, np.ones(41) / 41, mode="valid")[:n].astype(np.float32)
    # motif: an exactly repeated chirp burst at 800 and 4200
    t = np.linspace(0, 1, m)
    pattern = (np.sin(2 * np.pi * (2 * t + 6 * t * t)) * 3
               + 0.05 * np.random.default_rng(3).normal(size=m)).astype(np.float32)
    ts[800:800 + m] = pattern
    ts[4200:4200 + m] = pattern
    # discord: a shape anomaly (signal replaced by noise for one window)
    ts[2600:2600 + m] = ts[2600] + 0.5 * np.random.default_rng(9).normal(
        size=m).astype(np.float32)

    print(f"series n={n}, window m={m}")

    result = matrix_profile(ts, m, k=4)
    motifs = analytics.top_motifs(result, max_motifs=1)
    i, j = motifs[0].a, motifs[0].b
    print(f"[engine] top motif pair: ({i}, {j})  (planted at 800 / 4200)")
    discords = analytics.discords(result, n=3, exclusion=m)
    print(f"[engine] top-3 discords: {[d.position for d in discords]}  "
          f"(noise window planted at ~2600)")
    # the same sweep also harvested the split profiles and top-k sets
    lp, rp = np.asarray(result.left_p), np.asarray(result.right_p)
    assert (np.minimum(lp, rp) == np.asarray(result.p)).all()
    print(f"[engine] left/right split: e.g. position {i} has left neighbor "
          f"{int(result.left_i[i])} and right neighbor "
          f"{int(result.right_i[i])}; top-{result.k} neighbors of {i}: "
          f"{np.asarray(result.topk_i[i]).tolist()}")

    kres = ops.natsa_matrix_profile(ts, m, it=256, dt=16)
    err = np.abs(np.asarray(kres.p) - np.asarray(result.p))
    err = err[np.isfinite(err)]
    print(f"[pallas kernel] max |Δ| vs engine: {err.max():.2e}")

    kmot = analytics.top_motifs(kres, max_motifs=1)[0]
    print(f"[pallas kernel] top motif pair: ({kmot.a}, {kmot.b})")
    pair = sorted((i, j))
    assert abs(pair[0] - 800) < 40 and abs(pair[1] - 4200) < 40, pair
    assert any(abs(d.position - 2600) < m for d in discords), discords
    print("OK — motif and discord recovered.")


if __name__ == "__main__":
    main()
