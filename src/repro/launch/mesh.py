"""Mesh construction.

Every mesh here is a FUNCTION result (not a module constant) so importing
this module never touches jax device state — required for the dry-run's
512-placeholder-device trick to work (device count locks on first use).

All axes are ``Auto``: the model code places arrays through ``jit``
shardings and ``with_sharding_constraint`` and lets the partitioner
propagate the rest, which ``jax.make_mesh``'s default ``Explicit`` axes
refuse (an embedding gather on an explicitly sharded table must name its
output sharding).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None):
    """``jax.make_mesh`` over ``devices`` (default: all) with Auto axes."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the leading "pod"
    axis maps onto the slow inter-pod (DCN/ICI-bridge) links and only ever
    carries data-parallel gradient traffic (and optionally compressed —
    see repro/dist/compression.py)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_axis: int = 1, n_devices: Optional[int] = None):
    """(data, model) mesh over the first ``n_devices`` local devices
    (default: all of them)."""
    devices = jax.devices()[:n_devices]
    data = len(devices) // model_axis
    return make_mesh((data, model_axis), ("data", "model"),
                     devices=devices[:data * model_axis])
