"""Production mesh factory.

Defined as a FUNCTION (not module-level state) so importing this module never
touches jax device initialization — the dry-run must set XLA_FLAGS before the
first jax call, and tests/benches must keep seeing 1 device.

Mesh shapes (TPU v5e):
  single pod:  (data=16, model=16)           = 256 chips
  multi-pod:   (pod=2, data=16, model=16)    = 512 chips

Axis roles: 'pod' = pure DP across pods (slow inter-pod links carry only the
gradient all-reduce, int8-compressible); 'data' = FSDP batch+param shards;
'model' = TP/EP/SP within a pod row (fast ICI).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1) -> jax.sharding.Mesh:
    """Whatever-devices-exist mesh for tests/examples (1 CPU here)."""
    n = len(jax.devices())
    data = n // model
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
