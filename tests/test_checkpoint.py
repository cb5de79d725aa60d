"""Hardened checkpoints: crc32-verified npz formats, corruption detection
with clear errors, and fallback-to-previous-good-checkpoint — for both the
generic pytree store (checkpoint.ckpt) and the scheduler's own
checkpoint/resume (core.scheduler, in-process 1-worker mesh)."""

import json
import os
import sys
import warnings

import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "src")))

from repro.checkpoint import ckpt                       # noqa: E402
from repro.core.faults import (CheckpointCorruptionError,  # noqa: E402
                               CheckpointWriteError, FaultInjector,
                               flip_bits)

TREE = {"params": {"w": np.arange(24.0).reshape(4, 6),
                   "b": np.ones(6, np.float32)},
        "step_count": np.int64(7)}


def _corrupt_payload(path):
    """Overwrite a big interior run of the file — guaranteed to hit array
    payload bytes, unlike single bit-flips that can land in zip padding."""
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.seek(size // 3)
        f.write(b"\xa5" * (size // 3))


# -- generic pytree store ----------------------------------------------------

def test_ckpt_roundtrip_and_format_tag(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 3, TREE, metadata={"note": "x"})
    out, step, md = ckpt.restore(d, TREE)
    assert step == 3 and md == {"note": "x"}
    np.testing.assert_array_equal(out["params"]["w"], TREE["params"]["w"])
    with open(os.path.join(d, "step_%010d" % 3, "meta.json")) as f:
        meta = json.load(f)
    assert meta["format"] == ckpt.FORMAT
    assert set(meta["checksums"]) == set(meta["keys"])


def test_ckpt_corrupted_latest_falls_back_to_previous(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, TREE)
    ckpt.save(d, 2, TREE)
    _corrupt_payload(os.path.join(d, "step_%010d" % 2, "arrays.npz"))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        out, step, _ = ckpt.restore(d, TREE)
    assert step == 1
    assert any("falling back" in str(x.message) for x in w)
    np.testing.assert_array_equal(out["params"]["b"], TREE["params"]["b"])


def test_ckpt_bitflip_detected_by_checksums(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, TREE)
    # flip bits across the interior until verification fails — zip CRC or
    # our meta checksums must catch payload damage either way
    flip_bits(os.path.join(d, "step_%010d" % 1, "arrays.npz"),
              seed=3, n_flips=64)
    with pytest.raises((ckpt.CheckpointCorruptionError, FileNotFoundError)):
        try:
            ckpt.restore(d, TREE, step=1)
        except ckpt.CheckpointCorruptionError:
            raise
        else:  # pragma: no cover - flips all landed in padding
            raise FileNotFoundError("flips landed in padding")


def test_ckpt_truncated_archive_reports_missing_keys(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, TREE)
    p = os.path.join(d, "step_%010d" % 1)
    # rewrite the npz with one array dropped: meta keys no longer match
    with np.load(os.path.join(p, "arrays.npz")) as z:
        arrays = {k: z[k] for k in z.files}
    arrays.pop(sorted(arrays)[0])
    np.savez(os.path.join(p, "arrays.npz"), **arrays)
    with pytest.raises(ckpt.CheckpointCorruptionError, match="missing"):
        ckpt.restore(d, TREE, step=1)


def test_ckpt_pinned_step_does_not_fall_back(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, TREE)
    ckpt.save(d, 2, TREE)
    _corrupt_payload(os.path.join(d, "step_%010d" % 2, "arrays.npz"))
    with pytest.raises(ckpt.CheckpointCorruptionError):
        ckpt.restore(d, TREE, step=2)


def test_ckpt_format1_files_still_restore(tmp_path):
    d = str(tmp_path)
    ckpt.save(d, 1, TREE)
    p = os.path.join(d, "step_%010d" % 1, "meta.json")
    with open(p) as f:
        meta = json.load(f)
    del meta["format"], meta["checksums"]          # what old writers produced
    with open(p, "w") as f:
        json.dump(meta, f)
    out, step, _ = ckpt.restore(d, TREE)
    assert step == 1
    np.testing.assert_array_equal(out["params"]["w"], TREE["params"]["w"])


# -- scheduler checkpoint/resume ---------------------------------------------

@pytest.fixture(scope="module")
def sched_mod():
    from repro.core.scheduler import AnytimeScheduler
    import jax
    mesh = jax.make_mesh((1,), ("workers",), (jax.sharding.AxisType.Auto,))
    ts = np.cumsum(np.random.default_rng(5).normal(size=240))
    mk = lambda **kw: AnytimeScheduler(ts, 12, mesh, chunks_per_worker=4,
                                       band=16, **kw)
    return mk


def test_scheduler_checkpoint_meta_has_checksums(sched_mod, tmp_path):
    from repro.core.scheduler import CHECKPOINT_FORMAT
    s = sched_mod()
    s.run(2)
    path = str(tmp_path / "ck.npz")
    s.checkpoint(path)
    with np.load(path) as z:
        meta = json.loads(str(z["meta"]))
    assert meta["format"] == CHECKPOINT_FORMAT
    assert set(meta["checksums"]) >= {"corr", "index", "done"}


def test_scheduler_resume_rotation_and_corruption_fallback(sched_mod,
                                                          tmp_path):
    path = str(tmp_path / "ck.npz")
    s = sched_mod()
    s.run(1)
    s.checkpoint(path)
    s.run(1)
    s.checkpoint(path)                 # rotates first write to .prev
    assert os.path.exists(path + ".prev")
    flip_bits(path, seed=9, n_flips=64)
    s2 = sched_mod()
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        s2.resume(path)
    assert any("falling back" in str(x.message) for x in w)
    s2.run()
    clean = sched_mod()
    clean.run()
    np.testing.assert_array_equal(np.asarray(s2.result().p),
                                  np.asarray(clean.result().p))


def test_scheduler_resume_corruption_without_fallback_raises(sched_mod,
                                                             tmp_path):
    path = str(tmp_path / "ck.npz")
    s = sched_mod()
    s.run(1)
    s.checkpoint(path)
    assert not os.path.exists(path + ".prev")
    _corrupt_payload(path)
    s2 = sched_mod()
    with pytest.raises(CheckpointCorruptionError):
        s2.resume(path)


def test_scheduler_resume_geometry_mismatch_is_valueerror(sched_mod,
                                                          tmp_path):
    from repro.core.scheduler import AnytimeScheduler
    import jax
    path = str(tmp_path / "ck.npz")
    s = sched_mod()
    s.run(1)
    s.checkpoint(path)
    mesh = jax.make_mesh((1,), ("workers",), (jax.sharding.AxisType.Auto,))
    other = AnytimeScheduler(np.cumsum(np.ones(300)), 12, mesh)
    with pytest.raises(ValueError, match="geometry mismatch"):
        other.resume(path)
    wrong_window = AnytimeScheduler(
        np.cumsum(np.random.default_rng(5).normal(size=240)), 24, mesh)
    with pytest.raises(ValueError, match="geometry mismatch"):
        wrong_window.resume(path)


def test_scheduler_checkpoint_kill_leaves_previous_intact(sched_mod,
                                                          tmp_path):
    path = str(tmp_path / "ck.npz")
    s = sched_mod()
    s.run(1)
    s.checkpoint(path)
    good = open(path, "rb").read()
    s.run(1)
    inj = FaultInjector(checkpoint_kills={0})
    with pytest.raises(CheckpointWriteError):
        s.checkpoint(path, injector=inj, serial=0)
    assert open(path, "rb").read() == good     # atomic: old file untouched
    s2 = sched_mod()
    s2.resume(path)                            # and it still verifies


def test_scheduler_future_format_rejected(sched_mod, tmp_path):
    path = str(tmp_path / "ck.npz")
    s = sched_mod()
    s.run(1)
    s.checkpoint(path)
    with np.load(path) as z:
        arrays = {k: z[k] for k in z.files}
    meta = json.loads(str(arrays.pop("meta")))
    meta["format"] = 99
    np.savez(path, meta=json.dumps(meta), **arrays)
    s2 = sched_mod()
    with pytest.raises(ValueError, match="format 99"):
        s2.resume(path)


if __name__ == "__main__":
    sys.exit(pytest.main([os.path.abspath(__file__), "-q"]))
