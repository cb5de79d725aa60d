"""Spans and counters at the layer boundaries of the profile path.

There is one recorder, the JAX profiler. `span(name, **ids)` is a
`jax.profiler.TraceAnnotation`: it lands on the profiler's own host plane,
on the same clock as the device's ops, so an idle gap on the device can be
attributed to the host span around it. When no trace is running the
profiler keeps nothing and an entry costs about a microsecond.

Span names carry the `mp.` prefix. A span's self time is its duration minus
the time its child `mp.` spans on the same thread cover:

  mp.stats           the f64 host prep of one series (the window pass in
                     row blocks: norms and probe dots; the streams), or of
                     both sides under one span in `compute_cross_stats_host`;
                     holds mp.seeds and mp.put. No window matrix is built
                     unless the caller keeps one (`return_centered_windows`)
  mp.seeds           on the fresh entries a near-empty span: the seed dots
                     are taken inside the window pass (mp.stats), and this
                     span only picks `cov0` or orders the `cov0s`; on the
                     resident paths, the f64 seed dots over stored window
                     matrices (`zstats.seed_dots`)
  mp.put             enqueuing the host -> device copies of streams and
                     seeds (the copies themselves run asynchronously)
  mp.sweep           `plan.execute` up to and including the sweep's launch;
                     holds mp.finish; its argument `backend` names the
                     plan's backend (engine, rowstream, kernel)
  mp.finish          merges, distance conversion and result assembly after
                     the launch, and the lazy finish on first access
  mp.serve.step      one `ProfileService.step`
  mp.serve.take      taking expired queries or a batch from the queue
  mp.serve.assemble stacking one group's (query, series) pairs
  mp.serve.dispatch  launching one group's sweep into the round loop
  mp.serve.deliver   the host's block on one round's device arrays
  mp.serve.merge     the union merge of a batch's groups into answers

Counters are plain running sums, kept where the work happens:

  mp.window_bytes    bytes of the f64 (l, m) centered-window matrices built
                     (only callers that keep one: the resident sides, the
                     service's queries)
  mp.window_blocks   row blocks of centered windows the streamed window
                     pass built (`zstats._window_pass`)
  mp.put_bytes       bytes the mp.put spans copy to the device
  mp.tile_cells.<backend>
                     distance-matrix cells the sweeps `plan.execute`
                     launched computed, masked cells included
                     (`plan.tile_cells`; the kernel computes only its live
                     tiles); the distributed rounds of
                     `plan.round_executor` are not counted
  mp.skipped_tiles.kernel
                     grid steps of the kernel launches `plan.execute` made
                     that skipped their tile, which holds no valid cell
                     (`plan.skipped_tiles`); skipped / (skipped + live
                     tiles) is how often the skip engages
"""

from __future__ import annotations

import jax

_counters: dict[str, int] = {}


def span(name: str, **ids):
    """A host span for the profiler's trace; `ids` (say `batch=3`) are kept
    as the event's arguments. Use as a context manager."""
    return jax.profiler.TraceAnnotation(name, **ids)


def count(name: str, n: int) -> None:
    """Add `n` to the counter `name`."""
    _counters[name] = _counters.get(name, 0) + n


def counters() -> dict[str, int]:
    """A copy of every counter: take two and subtract for a window's share."""
    return dict(_counters)
