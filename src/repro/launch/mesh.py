"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches JAX device state — required for the smoke tests, which must
see one CPU device, while the dry-run forces 512 host devices *before* any
jax import.

Every mesh has ``Auto`` axes: the model code shards through
``in_shardings``/``shard_map`` specs and the automatic partitioner, while
``jax.make_mesh`` would default to ``Explicit`` axes whose sharding-in-types
rejects the embedding and pipeline gathers.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` over the local devices with ``Auto`` axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16×16 = 256 chips (data, model).
    Multi-pod: 2×16×16 = 512 chips (pod, data, model) — the pod axis is the
    slowest (DCN-connected) dimension."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
