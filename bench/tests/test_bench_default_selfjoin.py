"""The default-entry self-join driver (`drivers/default_join.py`) and its
metric `tile_gcells_s.join` at a tiny size on the CPU: a tiny copy of
`seismology-64k.selfjoin` (its traffic kind and its limits) in a copied
benchmark directory, run through `run.run_cell` with the look for a chip
skipped, as `test_bench_cells.py` runs the other drivers.
"""

import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import pytest  # noqa: E402

import run  # noqa: E402
import trace_reduce  # noqa: E402

SEED = 2**33 + 54321          # seeds run past 32 bits
REAL = "seismology-64k.selfjoin"
CELL = "tiny.default-selfjoin"


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench") / "bench"
    shutil.copytree(BENCH, root,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "configs" / "tiny-default.json").write_text(json.dumps(
        {"n": 1024, "window": 32, "exclusion": 8}))
    spec = json.loads((BENCH / "workloads" / f"{REAL}.json").read_text())
    spec.update(config="tiny-default",
                traffic={"join": "self", "check_rows": 16})
    (root / "workloads" / f"{CELL}.json").write_text(json.dumps(spec))
    return root


def _run(bench, *, trace=False, control=False):
    out = run.run_cell(CELL, SEED, 1.0, trace, bench=bench,
                       require_chip=False, control=control)
    json.dumps(out)                      # the result line serializes
    return out


def test_sound_run_is_correct(bench):
    out = _run(bench)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", "join_s"}


def test_control_is_not_correct(bench):
    """The bfloat16 tile route fails the cell's limits."""
    out = _run(bench, control=True)
    assert not out["correct"], out["checks"]


def test_broken_default_entry_is_not_correct(bench, monkeypatch):
    import importlib

    from repro.core.result import ProfileResult

    mp = importlib.import_module("repro.core.matrix_profile")
    real = mp.matrix_profile

    def broken(*a, **kw):
        res = real(*a, **kw)
        return ProfileResult(kind=res.kind, window=res.window,
                             exclusion=res.exclusion, backend=res.backend,
                             p=res.p.at[1::2].multiply(1.5), i=res.i)

    monkeypatch.setattr(mp, "matrix_profile", broken)
    assert not _run(bench)["correct"]


def test_traced_run_records_the_tile_cells(bench):
    """The window's record carries the counter's delta (read here through
    a metric file dropped beside the others); on the CPU no op runs on a
    device plane, so the work rates themselves stay silent."""
    probe = bench / "metrics" / "tile_cells_seen.join.py"
    probe.write_text('UNIT = "cells"\n\n\ndef read(record, trace):\n'
                     '    return record.get("tile_cells")\n')
    try:
        out = _run(bench, trace=True)
    finally:
        probe.unlink()
    assert out["correct"], out["checks"]
    # l = 993, exclusion 8: ceil(985 / 256) = 4 bands of (256, 993) a join
    per_join = 4 * 256 * 993
    assert out["metrics"]["tile_cells_seen.join"]["value"] \
        == out["attempted"] * per_join
    assert "tile_gcells_s.join" not in out["metrics"]


def test_tile_rate_reads_a_summary_and_is_silent_elsewhere():
    reader = run.load_module(BENCH / "metrics" / "tile_gcells_s.join.py")
    summary = trace_reduce.TraceSummary(window_s=10.0, busy_s=4.0, ops=[],
                                        gaps=[])
    record = {"kind": "join", "joins": 2, "cells_per_join": 5_000_000_000,
              "tile_cells": 20_000_000_000}
    assert reader.read(record, summary) == pytest.approx(5.0)
    # the join driver's record has no tile cells; the parent's counts none
    assert reader.read({**record, "tile_cells": 0}, summary) is None
    del record["tile_cells"]
    assert reader.read(record, summary) is None
    assert reader.read({"kind": "serve"}, summary) is None
    assert reader.read({**record, "tile_cells": 1}, None) is None
