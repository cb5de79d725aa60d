"""Production mesh builders (function, not module constant — importing this
module must never touch jax device state)."""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with every axis in Auto sharding mode (the default
    became Explicit; the sweeps and the LM stack are written for Auto)."""
    return jax.make_mesh(shape, axes, (jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_worker_mesh(n: int | None = None, axis: str = "workers"):
    """1-D mesh over available devices for the matrix-profile engine."""
    devs = jax.devices()
    n = len(devs) if n is None else n
    return _auto_mesh((n,), (axis,), devices=devs[:n])


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
