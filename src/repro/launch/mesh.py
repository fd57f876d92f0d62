"""Production mesh construction.

Kept as FUNCTIONS (never module-level constants) so importing this module
does not touch jax device state — the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before first jax init,
and tests/benches must keep seeing 1 real device.

Mesh shapes (TPU v5e pods):
  single-pod : (data=16, model=16)            = 256 chips
  multi-pod  : (pod=2, data=16, model=16)     = 512 chips

The `pod` axis is the paper's client axis: each pod is one federated
participant; cross-pod traffic is the scheduled uplink analogue.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(*, multi_pod: bool = False):
    """Small mesh for in-test dry-runs (8 forced host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
