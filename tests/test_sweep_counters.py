"""The sweep's tile-cell counter and the backend on its span: every path of
`plan.execute` adds `plan.tile_cells(plan)` to `mp.tile_cells.<backend>`,
whole tiles with their masked cells, never fewer than the cells the answer
needs (the benchmark's yardstick, `bench/work.py`); kernel paths add the
grid steps they skip, `plan.skipped_tiles(plan)`, to
`mp.skipped_tiles.kernel`."""

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import plan as plan_mod
from repro.core.matrix_profile import (
    ab_join, batch_ab_join, batch_profile, matrix_profile,
)
from repro.kernels import ops

_spec = importlib.util.spec_from_file_location(
    "bench_work", Path(__file__).resolve().parents[1] / "bench" / "work.py")
work = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(work)

M = 16
N_SELF, N_A, N_B = 300, 200, 100                # l = 285; l_a 185, l_b 85
L, L_A, L_B = N_SELF - M + 1, N_A - M + 1, N_B - M + 1
EXCL = 4                                         # default_exclusion(16)


def _walk(n, seed):
    return np.cumsum(np.random.default_rng(seed).normal(size=n)) \
        .astype(np.float32)


def _ab_plan_run(backend, k=1, batch=None):
    """An AB plan forced onto `backend`, executed on fresh stats."""
    from repro.core.result import build_result

    a, b = _walk(N_A, 1), _walk(N_B, 2)
    plan = plan_mod.plan_sweep(M, L_A, L_B, backend=backend, k=k,
                               batch=batch)
    cross = plan_mod.cross_stats_for(plan, a, b)
    if batch is not None:
        cross = jax.tree.map(lambda x: jnp.stack([x] * batch), cross)
    return build_result(plan, plan_mod.execute(plan, cross), cross)


# (path, run, backend, cells by hand, cells the answer needs, kernel grid
# steps skipped). Band 256: self sweeps take ceil(281 / 256) = 2 bands of
# (256, 285); AB engine tiles are (256, min(185, 85 + 255)) over
# ceil(269 / 256) = 2 bands; the kernel's grid is ceil(rows / 1024) x
# ceil(diagonals / 64) tiles of 1024 x 64 (AB: B's 85 rows, 269
# diagonals), all live; at 128 x 8 its 3 x 36 grid has 36 + 20 + 4 live
# tiles, the row tiles at 0, 128, 256 ending at diagonal tile
# ceil((285 - i0 - 4) / 8); the bf16 route takes one 512 x 512 tile pair.
SELF = work.selfjoin_cells(L, EXCL)
AB = work.ab_cells(L_A, L_B)
PATHS = [
    ("self-engine", lambda: matrix_profile(_walk(N_SELF, 0), M),
     "engine", 2 * 256 * L, SELF, 0),
    ("self-engine-topk", lambda: matrix_profile(_walk(N_SELF, 0), M, k=3),
     "engine", 2 * 256 * L, SELF, 0),
    ("self-bf16-tiles",
     lambda: matrix_profile(_walk(N_SELF, 0), M, precision="bf16"),
     "engine", 512 * 512, SELF, 0),
    ("self-kernel", lambda: ops.natsa_matrix_profile(_walk(N_SELF, 0), M),
     "kernel", 1024 * 5 * 64, SELF, 0),
    ("self-kernel-skips",
     lambda: ops.natsa_matrix_profile(_walk(N_SELF, 0), M, it=128, dt=8),
     "kernel", (36 + 20 + 4) * 128 * 8, SELF, 3 * 36 - (36 + 20 + 4)),
    ("self-nonnorm",
     lambda: matrix_profile(_walk(N_SELF, 0), M, normalize=False),
     "engine", 2 * 256 * L, SELF, 0),
    ("self-batched",
     lambda: batch_profile(np.stack([_walk(N_SELF, s) for s in range(3)]), M),
     "engine", 3 * 2 * 256 * L, 3 * SELF, 0),
    ("ab-rowstream", lambda: ab_join(_walk(N_A, 1), _walk(N_B, 2), M),
     "rowstream", L_A * L_B, AB, 0),
    ("ab-rowstream-batched", lambda: _ab_plan_run("rowstream", batch=2),
     "rowstream", 2 * L_A * L_B, 2 * AB, 0),
    ("ab-engine", lambda: _ab_plan_run("engine"),
     "engine", 2 * 256 * L_A, AB, 0),
    ("ab-engine-topk", lambda: _ab_plan_run("engine", k=2),
     "engine", 2 * 256 * L_A, AB, 0),
    ("ab-engine-batched",
     lambda: batch_ab_join(np.stack([_walk(N_A, 1)] * 2),
                           np.stack([_walk(N_B, 2)] * 2), M),
     "engine", 2 * 2 * 256 * L_A, 2 * AB, 0),
    ("ab-kernel", lambda: ops.natsa_ab_join(_walk(N_A, 1), _walk(N_B, 2), M),
     "kernel", 1024 * 5 * 64, AB, 0),
    ("ab-nonnorm",
     lambda: ab_join(_walk(N_A, 1), _walk(N_B, 2), M, normalize=False),
     "engine", 2 * 256 * L_A, AB, 0),
]


@pytest.mark.parametrize("run,backend,cells,needed,skipped",
                         [p[1:] for p in PATHS], ids=[p[0] for p in PATHS])
def test_each_execute_path_counts_its_tiles(run, backend, cells, needed,
                                            skipped):
    before = obs.counters()
    jax.block_until_ready(run().p)
    after = obs.counters()

    def grew(prefix):
        return {k: after[k] - before.get(k, 0) for k in after
                if k.startswith(prefix) and after[k] != before.get(k, 0)}

    assert grew("mp.tile_cells.") == {f"mp.tile_cells.{backend}": cells}
    assert grew("mp.skipped_tiles.") == (
        {"mp.skipped_tiles.kernel": skipped} if skipped else {})
    assert cells >= needed


@pytest.mark.parametrize("window,l,backend,cells,ratio", [
    # seismology-64k on the default route: 255 bands of (256, 65281)
    (256, 65281, None, 4_261_543_680, 2.004),
    # power-512k through the kernel: the live tiles of 1024 x 64 in its
    # 512 x 8173 grid; the 2,093,053 others lie past column l - 1
    (1024, 523265, "kernel", 137_070_051_328, 1.002),
])
def test_tile_cells_at_paper_sizes(window, l, backend, cells, ratio):
    plan = plan_mod.plan_sweep(window, l, backend=backend)
    assert plan_mod.tile_cells(plan) == cells
    needed = work.selfjoin_cells(l, plan.exclusion)
    assert cells / needed == pytest.approx(ratio, abs=0.002)
    if backend == "kernel":
        assert plan_mod.skipped_tiles(plan) == 512 * 8173 - cells // 65536
        assert plan_mod.skipped_tiles(plan) == 2_093_053


def test_distributed_plans_are_not_counted():
    plan = plan_mod.plan_sweep(M, L, backend="distributed")
    with pytest.raises(ValueError, match="not counted"):
        plan_mod.tile_cells(plan)


def test_a_lazy_b_side_reexecute_counts_its_second_sweep():
    """An engine AB plan without B's harvest answers `b_p` by executing the
    plan again, two-sided: that second sweep is counted too."""
    a, b = _walk(N_A, 1), _walk(N_B, 2)
    plan = plan_mod.plan_sweep(M, L_A, L_B, backend="engine")
    stats = plan_mod.cross_stats_for(plan, a, b)
    from repro.core.result import build_result

    before = obs.counters().get("mp.tile_cells.engine", 0)
    res = build_result(plan, plan_mod.execute(plan, stats), stats)
    jax.block_until_ready(res.b_p)
    grew = obs.counters()["mp.tile_cells.engine"] - before
    assert grew == 2 * plan_mod.tile_cells(plan)

