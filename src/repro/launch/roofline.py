"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), all **seconds per step, per chip**:

    compute    = HLO_FLOPs / peak_FLOPs            (197 TFLOP/s bf16, v5e)
    memory     = HLO_bytes / HBM_bw                (819 GB/s)
    collective = wire_bytes / link_bw              (~50 GB/s/link ICI)

HLO_FLOPs/bytes come from compiled.cost_analysis() of (a) the full step and
(b) per-layer sub-programs x layer count — XLA counts a while/scan body ONCE,
so (a) alone undercounts by ~L; both are recorded and (b) is authoritative.

Wire bytes: every collective op in the post-SPMD per-device HLO, weighted by
ring-algorithm cost: all-reduce 2(n-1)/n, all-gather/reduce-scatter (n-1)/n
(x result/operand size respectively), all-to-all (n-1)/n, collective-permute
1. Per-layer collectives are multiplied by layer count.
"""

from __future__ import annotations

import dataclasses
import json
import re

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "tuple": 0, "token": 0, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*((?:\([^)]*\)|\S+))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", )
_GROUPS_RE = re.compile(r"replica_groups=\{([^}]*(?:\{[^}]*\}[^}]*)*)\}")
_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string (handles tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _IOTA_RE.search(line)
    if m:
        return int(m.group(2))      # [num_groups, group_size]
    m = _GROUPS_RE.search(line)
    if m:
        first = m.group(1).split("}")[0].lstrip("{")
        ids = [x for x in first.split(",") if x.strip() != ""]
        return max(1, len(ids))
    return default


@dataclasses.dataclass
class Collective:
    kind: str
    result_bytes: int
    group: int

    @property
    def wire_bytes(self) -> float:
        n = max(self.group, 2)
        if self.kind == "all-reduce":
            return 2 * self.result_bytes * (n - 1) / n
        if self.kind == "all-gather":
            return self.result_bytes * (n - 1) / n
        if self.kind == "reduce-scatter":
            return self.result_bytes * (n - 1)      # result is the shard
        if self.kind == "all-to-all":
            return self.result_bytes * (n - 1) / n
        return float(self.result_bytes)             # collective-permute


def parse_collectives(hlo_text: str, default_group: int) -> list[Collective]:
    out = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done" in line.split("=")[1][:40]:
            continue
        out.append(Collective(kind=m.group(2),
                              result_bytes=shape_bytes(m.group(1)),
                              group=_group_size(line, default_group)))
    return out


def collective_wire_bytes(hlo_text: str, default_group: int) -> float:
    return sum(c.wire_bytes for c in parse_collectives(hlo_text, default_group))


@dataclasses.dataclass
class RooflineTerms:
    flops_per_chip: float
    bytes_per_chip: float
    wire_bytes_per_chip: float
    model_flops_total: float
    n_chips: int

    @property
    def t_compute(self) -> float:
        return self.flops_per_chip / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.bytes_per_chip / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.wire_bytes_per_chip / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """No-overlap upper bound estimate: max of the three terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (total) — remat/redundancy waste."""
        hlo_total = self.flops_per_chip * self.n_chips
        return self.model_flops_total / hlo_total if hlo_total else 0.0

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilization ceiling implied by the dominant term."""
        t = self.step_time
        if t <= 0:
            return 0.0
        return (self.model_flops_total / self.n_chips / t) / PEAK_FLOPS

    def to_dict(self) -> dict:
        return {
            "flops_per_chip": self.flops_per_chip,
            "bytes_per_chip": self.bytes_per_chip,
            "wire_bytes_per_chip": self.wire_bytes_per_chip,
            "model_flops_total": self.model_flops_total,
            "n_chips": self.n_chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_ratio": self.useful_ratio,
            "mfu_bound": self.mfu_bound,
        }


def matrix_profile_roofline(l: int, excl: int, it: int | None = None,
                            dt: int | None = None,
                            n_chips: int = 1,
                            stream_bytes: int = 4) -> RooflineTerms:
    """`RooflineTerms` for one NATSA matrix-profile sweep of `l` rows.

    Bridges the kernel's analytic data-movement model into the same
    roofline vocabulary the LM dry-run tooling uses: FLOPs/chip from the
    per-cell work model (`ops.FLOPS_PER_CELL` over the admissible
    triangle), HBM bytes/chip from `ops.hbm_bytes_per_cell` under the
    kernel's ACTUAL tile geometry (`repro.kernels.DEFAULT_IT/DT` unless
    overridden — the same constants the launch signatures default to), and
    zero wire bytes for the single-chip sweep (the distributed scheduler's
    profile merges are O(l) per round, negligible next to the O(l^2)
    streaming traffic). The matrix-profile work model counts f32 MACs, so
    times are optimistic by the bf16/f32 peak gap; the BOTTLENECK verdict
    — NATSA's motivating claim that the sweep is memory-bound on a
    conventional memory system once tiles outgrow VMEM residency — is what
    this function is for, not absolute seconds.

    `stream_bytes` is the per-element width of the df/dg/invn streams (4
    for the f32 default, 2 under a reduced `PrecisionSpec`); seeds,
    profiles and column banks stay 4-byte regardless — see
    `ops.hbm_bytes_per_cell`.
    """
    from repro.kernels import DEFAULT_DT, DEFAULT_IT, ops

    it = DEFAULT_IT if it is None else it
    dt = DEFAULT_DT if dt is None else dt
    # admissible pairs, each visited ONCE (the fused sweep harvests both
    # profile sides per cell) — the same count kernel_roofline uses
    cells = float(sum(l - k for k in range(excl, l)))
    flops = cells * ops.FLOPS_PER_CELL
    hbm_bytes = cells * ops.hbm_bytes_per_cell(l, excl, it=it, dt=dt,
                                               stream_bytes=stream_bytes)
    return RooflineTerms(flops_per_chip=flops / n_chips,
                         bytes_per_chip=hbm_bytes / n_chips,
                         wire_bytes_per_chip=0.0,
                         model_flops_total=flops,
                         n_chips=n_chips)

