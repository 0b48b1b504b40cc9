"""Production mesh builders (TPU v5e).

Single pod: (data=16, model=16) = 256 chips.
Multi-pod:  (pod=2, data=16, model=16) = 512 chips — the pod axis extends
data parallelism, so cross-pod (DCN) traffic in PEFT training is only the
adapter gradient all-reduce (~MBs), per DESIGN.md §4.

Functions, not module constants: importing this module never touches jax
device state (the dry-run sets XLA_FLAGS before any jax init).  Axes are
Auto-typed: the models place their arrays through sharding constraints
and let GSPMD propagate the rest, which Explicit axes (the default of
``jax.make_mesh`` since jax 0.7) refuse.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return jax.make_mesh(shape, axes, devices=devices[:n],
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever local devices exist (tests)."""
    n = data * model
    return jax.make_mesh((data, model), ("data", "model"),
                         devices=jax.devices()[:n],
                         axis_types=(AxisType.Auto,) * 2)
