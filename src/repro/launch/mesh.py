"""The one place meshes are built (functions, never module-level constants —
jax device state must not be touched at import time).

Every axis is ``AxisType.Auto``.  The programs of this repo place their
collectives by hand inside ``shard_map`` and let XLA propagate everything
else; ``jax.make_mesh`` defaults to *Explicit* axes since jax 0.9, whose
sharding-in-types rules reject plain array code such as the serving tier's
slot write (an update typed ``{Explicit: ('data', 'model')}`` into an
unsharded cache).
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices: Optional[Sequence] = None) -> Mesh:
    """Mesh of ``shape`` named ``axes`` with every axis ``Auto``.

    ``devices=None`` lets ``jax.make_mesh`` pick the first ``prod(shape)``
    devices in a topology-aware order.  An explicit ``devices`` sequence is
    used in the order given (its first ``prod(shape)`` entries) — the
    resilience paths rely on that order to map slots onto survivors."""
    shape, axes = tuple(int(s) for s in shape), tuple(axes)
    types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    devs = np.asarray(devices, dtype=object).reshape(-1)[: math.prod(shape)]
    return Mesh(devs.reshape(shape), axes, axis_types=types)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 = 256 chips/pod (TPU v5e); 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)

