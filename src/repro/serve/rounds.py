"""Async round loop: double-buffered dispatch, block only at delivery.

The round-barrier bottleneck is host/device serialization: a loop that
dispatches round k, synchronizes, THEN starts assembling round k+1 leaves
the device idle through every host-side stats assembly and the host idle
through every device sweep. JAX dispatch is
asynchronous — a jitted call returns device futures immediately — so the
fix is structural, not computational: keep up to `depth` dispatched rounds
in flight, do the host assembly of round k+1 while round k's sweep runs,
and call `jax.block_until_ready` ONLY when a result is actually delivered
to a consumer.

`RoundLoop` is that structure, factored so both the profile service's
batch rounds and ad-hoc callers share it. `dispatch(payload, meta)` hands
over already-launched device arrays (the caller runs its jitted/vmapped
sweep BEFORE calling, which is what enqueues the work) and returns
immediately unless the in-flight window is full — then the OLDEST round is
delivered first (bounded memory: at most `depth` rounds of device results
live at once). `drain()` delivers the rest in dispatch order.
"""

from __future__ import annotations

from collections import deque

from repro import obs


class RoundLoop:
    """Bounded in-flight window over asynchronously dispatched rounds."""

    def __init__(self, depth: int = 2, deliver=None):
        """`depth` — max rounds in flight (2 = classic double buffering:
        one executing, one assembling). `deliver(meta, payload)` — the
        result sink, called with the payload's arrays ready."""
        if depth < 1:
            raise ValueError(f"depth must be >= 1, got {depth}")
        self.depth = int(depth)
        self._deliver = deliver
        self._inflight: deque = deque()
        self.dispatched = 0
        self.delivered = 0

    def __len__(self) -> int:
        return len(self._inflight)

    def dispatch(self, payload, meta=None, ids=None) -> None:
        """Track one dispatched round. `payload` is any pytree of device
        arrays the caller's sweep already launched; delivery blocks on it.
        If the window is full, the oldest round is delivered (blocking on
        ITS arrays — by then usually already complete) before this one is
        admitted, so dispatch order == delivery order and memory stays
        bounded. `ids` (say `{"batch": 3}`) tag the round's
        `mp.serve.deliver` span."""
        while len(self._inflight) >= self.depth:
            self.deliver_next()
        self._inflight.append((meta, payload, ids or {}))
        self.dispatched += 1

    def deliver_next(self):
        """Block until the OLDEST in-flight round is ready and deliver it.
        This is the only place the loop synchronizes with the device."""
        import jax

        if not self._inflight:
            raise RuntimeError("no rounds in flight")
        meta, payload, ids = self._inflight.popleft()
        with obs.span("mp.serve.deliver", **ids):
            payload = jax.block_until_ready(payload)
            self.delivered += 1
            if self._deliver is not None:
                self._deliver(meta, payload)
            return meta, payload

    def drain(self) -> list:
        """Deliver every remaining in-flight round, dispatch order."""
        out = []
        while self._inflight:
            out.append(self.deliver_next())
        return out
