"""Every driver's set-up, window loop and comparison at a tiny size on the
CPU (the kernel in interpret mode), through `run.run_cell` with the look
for a chip skipped; the controls and the planted faults must come out not
correct under the limits of the benchmark's own cells.

Each test drops its own configuration and cell files into a copy of the
benchmark's directory: they are found by name, with no edit to the code.
"""

import dataclasses
import json
import os
import shutil
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import run  # noqa: E402

SEED = 2**33 + 12345          # seeds run past 32 bits
CONFIGS = {
    "tiny-1k": {"n": 1024, "window": 32, "exclusion": 8},
    "tiny-corpus": {"n_series": 4, "n": 512, "window": 32,
                    "max_pending": 64, "max_batch": 2},
}
# each tiny cell keeps the traffic kind and the limits of a real cell
CELLS = {
    "tiny.selfjoin": ("power-512k.selfjoin", "tiny-1k",
                      {"join": "self", "check_rows": 16}),
    "tiny.ab-query": ("power-512k.ab-query", "tiny-1k",
                      {"join": "ab", "query_n": 256, "check_rows": 16}),
    "tiny.steady": ("corpus-64x32k.steady", "tiny-corpus",
                    {"rate_per_s": 4.0, "query_n": 128, "k": 1,
                     "deadline_s": 5.0, "check_queries": 3,
                     "check_rows": 8}),
}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench") / "bench"
    shutil.copytree(BENCH, root,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, cfg in CONFIGS.items():
        (root / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, (real, config, traffic) in CELLS.items():
        spec = json.loads((BENCH / "workloads" / f"{real}.json").read_text())
        spec.update(config=config, traffic=traffic)
        (root / "workloads" / f"{name}.json").write_text(json.dumps(spec))
    return root


def _run(bench, cell, *, trace=False, control=False, seconds=1.0):
    out = run.run_cell(cell, SEED, seconds, trace, bench=bench,
                       require_chip=False, control=control)
    json.dumps(out)                      # the result line serializes
    return out


@pytest.mark.parametrize("cell,metric", [("tiny.selfjoin", "join_s"),
                                         ("tiny.ab-query", "join_s"),
                                         ("tiny.steady", "query_p90_ms")])
def test_sound_run_is_correct(bench, cell, metric):
    out = _run(bench, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"setup_s", metric}
    assert out["metrics"][metric]["value"] > 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("cell,metrics", [
    ("tiny.selfjoin", {"idle_share.join", "stats_prep_s.join"}),
    ("tiny.steady", {"idle_share.serve", "late_ms.serve"}),
])
def test_traced_run_reads_its_layers(bench, cell, metrics):
    """On the CPU no op runs on a device plane, so the device's share
    reads idle and the work rates stay silent (they never read 0)."""
    out = _run(bench, cell, trace=True)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == metrics
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.mark.parametrize("cell", ["tiny.selfjoin", "tiny.ab-query",
                                  "tiny.steady"])
def test_control_is_not_correct(bench, cell):
    """bfloat16 streams (the program's own path for the joins, the
    bfloat16 reference in the service's place) fail the cell's limits."""
    out = _run(bench, cell, control=True)
    assert not out["correct"], out["checks"]


def _alter(res, **fields):
    """A ProfileResult with some fields replaced."""
    from repro.core.result import ProfileResult

    kw = {f: getattr(res, f) for f in ("p", "i", "b_p", "b_i")}
    kw.update(fields)
    return ProfileResult(kind=res.kind, window=res.window,
                         exclusion=res.exclusion, backend=res.backend, **kw)


def test_altered_selfjoin_answer_is_not_correct(bench, monkeypatch):
    from repro.kernels import ops

    real = ops.natsa_matrix_profile

    def broken(*a, **kw):
        res = real(*a, **kw)
        return _alter(res, p=res.p.at[1::2].multiply(1.5))

    monkeypatch.setattr(ops, "natsa_matrix_profile", broken)
    assert not _run(bench, "tiny.selfjoin")["correct"]


def test_altered_ab_answer_is_not_correct(bench, monkeypatch):
    import importlib

    mp = importlib.import_module("repro.core.matrix_profile")
    real = mp.ab_join

    def broken(*a, **kw):
        res = real(*a, **kw)
        return _alter(res, b_i=(res.b_i + 7) % res.b_i.shape[0])

    monkeypatch.setattr(mp, "ab_join", broken)
    assert not _run(bench, "tiny.ab-query")["correct"]


@pytest.mark.parametrize("fault", ["altered", "half_left_out"])
def test_broken_service_is_not_correct(bench, monkeypatch, fault):
    """The service's sweep returns altered distances, or leaves out the
    second half of each dispatch's (query, series) pairs."""
    import jax.numpy as jnp

    from repro.core import plan as plan_mod

    real = plan_mod.execute

    def broken(plan, stats):
        res = real(plan, stats)
        if plan.batch is None:
            return res
        d = np.asarray(res.dist)
        if fault == "altered":
            d = d * 1.5
        else:
            d = d.copy()
            d[d.shape[0] // 2:] = np.inf
        return dataclasses.replace(res, dist=jnp.asarray(d))

    monkeypatch.setattr(plan_mod, "execute", broken)
    assert not _run(bench, "tiny.steady")["correct"]


def test_new_metric_file_is_read_by_name(bench):
    """A per-layer metric is one new file; the harness reads it unedited."""
    (bench / "metrics" / "joins_seen.join.py").write_text(
        'UNIT = "joins"\n\n\ndef read(record, trace):\n'
        '    return record["joins"] if record["kind"] == "join" else None\n')
    try:
        out = _run(bench, "tiny.selfjoin", trace=True)
        assert out["metrics"]["joins_seen.join"]["value"] == out["attempted"]
    finally:
        (bench / "metrics" / "joins_seen.join.py").unlink()
