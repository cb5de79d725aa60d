"""The benchmark's yardstick on the CPU: cell counts, the trace reduction,
the peaks table, the file layout found by name, and BENCHMARK.json's names.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import pytest  # noqa: E402

import run  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402

DATA = BENCH / "tests" / "data"


@pytest.mark.parametrize("l,excl", [(1, 0), (7, 0), (7, 2), (12, 3),
                                    (5, 5), (5, 9)])
def test_selfjoin_cells_match_a_brute_force_count(l, excl):
    brute = sum(1 for i in range(l) for j in range(i, l) if j - i >= excl)
    assert work.selfjoin_cells(l, excl) == brute
    assert work.selfjoin_cells(l, excl) == sum(l - k for k in range(excl, l))


def test_ab_and_service_cells_match_a_brute_force_count():
    assert work.ab_cells(3, 5) == sum(1 for _ in range(3) for _ in range(5))
    assert work.service_cells(4, 3, 5) == 4 * work.ab_cells(3, 5)


SYNTHETIC = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2500000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 12000000 duration_ps: 500000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 9000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "natsa_rowmax" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "jit_sweep" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "main"
    timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 500000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 4500000 duration_ps: 1000000 }
    events { metadata_id: 4 offset_ps: 4600000 duration_ps: 100000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "bench.block" } }
  event_metadata { key: 3 value { id: 3 name: "bench.entry" } }
  event_metadata { key: 4 value { id: 4 name: "other.span" } }
}
"""


def test_trace_reduce_on_a_synthetic_trace():
    """Window [1000, 11000] ns; ops at [2000, 4000], [3500, 4500] (they
    overlap), [7000, 7500], and one at [13000, 13500] outside the window.
    Busy is the union: 2500 + 500 ns. The module line is not an op line."""
    from jax.profiler import ProfileData

    s = trace_reduce.reduce_profile(ProfileData.from_text_proto(SYNTHETIC))
    assert s.window_s == pytest.approx(1e-5)
    assert s.busy_s == pytest.approx(3e-6)
    assert dict(s.ops) == pytest.approx({"natsa_rowmax": 2e-6,
                                         "fusion.1": 1.5e-6})
    # gaps: [7500, 11000] in the loop itself, [4500, 7000] with its
    # midpoint in bench.entry [5500, 6500], [1000, 2000] in bench.block
    # [1500, 5500]; spans without the bench. prefix are not the benchmark's
    assert [g[0] for g in s.gaps] == ["bench.window", "bench.entry",
                                      "bench.block"]
    assert [g[1] for g in s.gaps] == pytest.approx([3.5e-6, 2.5e-6, 1e-6])


def test_trace_reduce_on_a_recorded_chip_trace():
    """A short trace recorded on one TPU v5e: two self-joins of 16384
    samples through the compiled kernel inside a `bench.window` span."""
    s = trace_reduce.reduce_dir(str(DATA))
    assert 0 < s.busy_s < s.window_s
    names = [n for n, _ in s.ops]
    assert any("natsa_rowmax" in n for n in names), names[:10]
    assert sum(t for _, t in s.ops) >= s.busy_s * (1 - 1e-9)
    assert all(label.startswith("bench.") for label, _ in s.gaps)
    assert s.busy_s + sum(t for _, t in s.gaps) == pytest.approx(s.window_s)


def test_unknown_device_kind_is_an_error():
    assert run.peaks_for(BENCH, "TPU v5 lite")["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="not in peaks.json"):
        run.peaks_for(BENCH, "TPU v99 imaginary")


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "power-512k.selfjoin", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        timeout=300)
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in proc.stdout.splitlines())


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_names_units_and_moves():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = {w["name"]: w for w in spec["workloads"]}
    configs = {c["name"] for c in spec["configs"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
    for w in spec["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs
        cell = json.loads((BENCH / "workloads" / f"{w['name']}.json")
                          .read_text())
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
    for c in spec["configs"]:
        assert NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])
        assert (ROOT / c["file"]).is_file()

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        for cell in m.get("workloads", cells):
            assert reports(e2e[m["moves"]], cell), (m["name"], cell)
