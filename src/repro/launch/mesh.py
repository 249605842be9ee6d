"""Production mesh construction.

Single pod: 16×16 = 256 chips over ("data", "model") — the CloudMatrix384
supernode analogue (the paper's 320-die decode instance ≈ one pod here).
Multi-pod: (2, 16, 16) = 512 chips with a leading "pod" axis — the paper's
RDMA scale-out plane maps to this axis (DP + KV handoff cross traffic only;
TP/EP stay inside a pod, §6.1.1).

A FUNCTION, not a module constant: importing this module never touches jax
device state (the dry-run must set XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(n_data: int = 2, n_model: int = 4, devices=None):
    """Small ("data", "model") mesh: 8 forced host devices in the CPU
    multi-device tests, or the first ``n_data * n_model`` of ``devices``."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto),
                         devices=devices)
