"""The one reduction from a profiler trace (`.xplane.pb`) to numbers.

Read through `jax.profiler.ProfileData`. From the device planes it takes
the op events, and from the host planes the benchmark's own spans (names
starting `bench.`); the `bench.window` span marks the measured window.
Within that window it gives:

  busy_s    the union of the intervals in which an op ran on a device,
            averaged over the devices that ran any;
  ops       device seconds per op name, largest first;
  gaps      the device's idle intervals, each labelled by the innermost
            benchmark span around its midpoint, longest first.
"""

from __future__ import annotations

import dataclasses
import glob
import os

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
# the device line whose events are the ops that ran
OP_LINE = "XLA Ops"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    ops: list            # [[name, seconds], ...] largest first
    gaps: list           # [[label, seconds], ...] longest first


def _union(intervals):
    """Merge (start, end) intervals into disjoint, sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _op_name(event_name: str) -> str:
    """An op event on a TPU is named by its whole HLO instruction
    (`%natsa_rowmax.1 = (f32[...]) ...`); keep the instruction's name."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CPU")


def reduce_profile(pd) -> TraceSummary:
    spans = []           # (start, end, name) of the benchmark's host spans
    devices = {}         # plane name -> [(start, end, op name)]
    for plane in pd.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                if line.name == OP_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (e.start_ns, e.end_ns, _op_name(e.name))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.end_ns, e.name)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    windows = [(s, e) for s, e, n in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    w0, w1 = windows[0]
    spans = [(s, e, n) for s, e, n in spans if n != WINDOW]

    ops: dict = {}
    busy_total = 0.0
    merged_all = []
    for events in devices.values():
        clipped = [(max(s, w0), min(e, w1), n) for s, e, n in events
                   if e > w0 and s < w1]
        for s, e, n in clipped:
            ops[n] = ops.get(n, 0.0) + (e - s) * 1e-9
        merged = _union((s, e) for s, e, _ in clipped)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        merged_all.extend(merged)
    n_dev = max(1, sum(1 for ev in devices.values() if ev))
    busy = _union(merged_all)

    gaps = []
    t = w0
    for s, e in busy + [[w1, w1]]:
        if s > t:
            gaps.append([_label(spans, t, s), (s - t) * 1e-9])
        t = max(t, e)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy_total / n_dev,
        ops=sorted(([k, v] for k, v in ops.items()), key=lambda x: -x[1]),
        gaps=sorted(gaps, key=lambda x: -x[1]))


def _label(spans, g0: float, g1: float) -> str:
    """The innermost span around the gap's midpoint; outside every inner
    span the gap belongs to the benchmark's own loop (`bench.window`)."""
    mid = 0.5 * (g0 + g1)
    around = [(e - s, n) for s, e, n in spans if s <= mid < e]
    return min(around)[1] if around else WINDOW


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def reduce_dir(trace_dir: str) -> TraceSummary:
    """Reduce the one `.xplane.pb` that `jax.profiler` wrote under a dir."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    return reduce_file(paths[0])
