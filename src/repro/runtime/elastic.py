"""Elastic scaling: rebuild the mesh for whatever devices survive and
re-shard state onto it.

The pieces that make this cheap in this framework:
* checkpoints are logical (path → full array), so restoring onto a new
  mesh is just device_put with fresh shardings (checkpoint/manager.py);
* the data cursor is a single integer (data/pipeline.py), valid for any
  host count;
* sharding rules are functions of (path, shape, mesh axes), not baked
  layouts (parallel/sharding.py).

So "elastic restart" = best_mesh_shape(n_alive) → make mesh → restore.
"""

from __future__ import annotations

import math
from typing import Optional

import jax


def best_mesh_shape(n_devices: int, *, prefer_model: int = 16
                    ) -> tuple[int, int]:
    """(data, model) factorization: model axis as close to prefer_model
    as divisibility allows, remainder to data."""
    model = math.gcd(n_devices, prefer_model)
    for m in range(min(prefer_model, n_devices), 0, -1):
        if n_devices % m == 0:
            model = m
            break
    return n_devices // model, model


def remesh(n_devices: Optional[int] = None, *, prefer_model: int = 16):
    """Build the largest healthy (data, model) mesh."""
    devs = jax.devices()
    n = n_devices or len(devs)
    data, model = best_mesh_shape(n, prefer_model=prefer_model)
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=devs[:data * model],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
