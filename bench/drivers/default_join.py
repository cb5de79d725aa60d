"""Closed-loop self-joins through the library's default entry.

The traffic and the comparison are `join.py`'s (`join: "self"`); only the
call differs: `repro.core.matrix_profile.matrix_profile(x, m)` with no
other argument, so the planner chooses the sweep and the exclusion zone.
The control passes `precision="bf16"` (the bfloat16 tile route).

Each window's record also holds `tile_cells`: what the window added to the
program's `mp.tile_cells.*` counters, the cells its sweeps computed with
masked cells included (0 where the program keeps no such counter).
"""

from __future__ import annotations

import importlib
import importlib.util
from pathlib import Path


def _sibling(name: str):
    """A driver file beside this one, imported by path as the harness
    imports drivers (the directory is not on the import path)."""
    spec = importlib.util.spec_from_file_location(
        f"bench_driver_{name}", Path(__file__).with_name(f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


join = _sibling("join")


def _tile_cells() -> int:
    from repro import obs

    return sum(v for k, v in obs.counters().items()
               if k.startswith("mp.tile_cells."))


class DefaultJoin(join.Join):
    def __init__(self, cfg: dict, cell: dict, seed: int, span,
                 control: bool = False):
        super().__init__(cfg, cell, seed, span, control)
        mp = importlib.import_module("repro.core.matrix_profile")
        m = cfg["window"]
        # the call passes no exclusion: the library's default must be the
        # deployment's, which the comparison uses
        assert mp.default_exclusion(m) == cfg["exclusion"], cfg
        kw = {"precision": "bf16"} if control else {}

        def call(x):
            # looked up at call time, so a test can put a broken entry in
            # its place
            res = mp.matrix_profile(x, m, **kw)
            return res.p, res.i

        self.call = call

    def window(self, seconds: float) -> dict:
        before = _tile_cells()
        record = super().window(seconds)
        record["tile_cells"] = _tile_cells() - before
        return record


def make(cfg, cell, seed, span, control=False) -> DefaultJoin:
    return DefaultJoin(cfg, cell, seed, span, control)
