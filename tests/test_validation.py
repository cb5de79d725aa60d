"""Entry-point input validation: malformed inputs fail at the API boundary
with ValueError (shared `core.validate.validate_series`), not as shape
errors deep inside the planner/stats pass — table-driven across entries."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "src")))

from repro.core.matrix_profile import (ab_join, batch_ab_join,  # noqa: E402
                                       batch_profile, matrix_profile)
from repro.core.streaming import StreamingProfile               # noqa: E402
from repro.core.validate import validate_series                 # noqa: E402

GOOD = np.cumsum(np.random.default_rng(0).normal(size=64))

# (label, ts, window, message-fragment)
BAD_SERIES = [
    ("scalar", np.float64(3.0), 8, "1-D"),
    ("zero_d", np.array(3.0), 8, "1-D"),
    ("two_d", np.zeros((8, 8)), 4, "1-D"),
    ("complex", np.zeros(32, np.complex128), 4, "real-valued"),
    ("strings", np.array(["a", "b", "c"]), 2, "numeric"),
    ("object", np.array([1.0, None, 2.0], object), 2, "numeric"),
    ("window_too_small", GOOD, 1, "window must be >= 2"),
    ("window_zero", GOOD, 0, "window must be >= 2"),
    ("window_negative", GOOD, -4, "window must be >= 2"),
    ("empty", np.array([]), 4, "empty"),
    ("window_gt_len", GOOD[:5], 10, "exceeds len"),
]


@pytest.mark.parametrize("label,ts,window,msg",
                         BAD_SERIES, ids=[c[0] for c in BAD_SERIES])
def test_validate_series_rejects(label, ts, window, msg):
    with pytest.raises(ValueError, match=msg):
        validate_series(ts, window)


@pytest.mark.parametrize("label,ts,window,msg",
                         BAD_SERIES, ids=[c[0] for c in BAD_SERIES])
def test_matrix_profile_entry_rejects(label, ts, window, msg):
    with pytest.raises(ValueError, match=msg):
        matrix_profile(ts, window)


@pytest.mark.parametrize("side", ["a", "b"])
@pytest.mark.parametrize("label,ts,window,msg",
                         [c for c in BAD_SERIES if "window" not in c[0]],
                         ids=[c[0] for c in BAD_SERIES
                              if "window" not in c[0]])
def test_ab_join_entry_rejects_either_side(side, label, ts, window, msg):
    a, b = (ts, GOOD) if side == "a" else (GOOD, ts)
    with pytest.raises(ValueError, match=msg):
        ab_join(a, b, window)


def test_ab_join_entry_rejects_bad_window():
    with pytest.raises(ValueError, match="window must be >= 2"):
        ab_join(GOOD, GOOD, 1)
    with pytest.raises(ValueError, match="exceeds len"):
        ab_join(GOOD, GOOD[:5], 10)


def test_empty_b_side_rejected():
    with pytest.raises(ValueError, match="empty"):
        ab_join(GOOD, np.array([]), 8)


def test_batch_entries_reject_malformed_stacks():
    with pytest.raises(ValueError, match="stack"):
        batch_profile(GOOD, 8)                       # 1-D, not (B, n)
    with pytest.raises(ValueError, match="non-empty"):
        batch_profile(np.zeros((0, 64)), 8)          # empty batch
    with pytest.raises(ValueError, match="window must be >= 2"):
        batch_profile(np.zeros((2, 64)), 1)
    with pytest.raises(ValueError, match="stack"):
        batch_ab_join(np.zeros((2, 64)), np.zeros((3, 64)), 8)
    with pytest.raises(ValueError, match="exceeds len"):
        batch_ab_join(np.zeros((2, 64)), np.zeros((2, 6)), 8)


def test_nonnorm_entry_requires_finite():
    bad = GOOD.copy()
    bad[10] = np.nan
    with pytest.raises(ValueError, match="non-finite"):
        matrix_profile(bad, 8, normalize=False)


def test_streaming_profile_validates_construction_and_append():
    with pytest.raises(ValueError, match="window must be >= 2"):
        StreamingProfile(1)
    sp = StreamingProfile(8)
    with pytest.raises(ValueError, match="1-D"):
        sp.append(np.zeros((4, 4)))


def test_scheduler_validates_inputs():
    from repro.core.scheduler import AnytimeScheduler
    import jax
    mesh = jax.make_mesh((1,), ("workers",), (jax.sharding.AxisType.Auto,))
    with pytest.raises(ValueError, match="1-D"):
        AnytimeScheduler(np.zeros((4, 4)), 8, mesh)
    with pytest.raises(ValueError, match="window must be >= 2"):
        AnytimeScheduler(GOOD, 1, mesh)
    with pytest.raises(ValueError, match="ts_b"):
        AnytimeScheduler(GOOD, 8, mesh, ts_b=np.zeros((2, 2)))


def test_valid_inputs_still_pass():
    assert validate_series(GOOD, 8).shape == (64,)
    assert validate_series(GOOD.astype(np.float32), 8).dtype == np.float32
    assert validate_series(np.arange(32), 4).dtype == np.int64
    r = matrix_profile(np.arange(64, dtype=np.float64) ** 1.5, 8)
    assert np.asarray(r.p).shape == (57,)


if __name__ == "__main__":
    sys.exit(pytest.main([os.path.abspath(__file__), "-q"]))
