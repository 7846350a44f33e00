"""Production mesh builders.

Functions, not module-level constants: importing this module must never touch
jax device state (device count is locked at first backend init, and only the
dry-run forces 512 host devices).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 two-pod (512 chips) mesh.

    Axes: ``data`` = FSDP + DP + EP, ``model`` = TP/SP, ``pod`` = cross-pod
    DP (gradient all-reduce on slow links; see distributed/collectives.py).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever this host has (CPU: 1 device) — smoke tests and examples."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"), (AxisType.Auto,) * 2)
