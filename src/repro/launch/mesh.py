"""Production meshes (brief: MULTI-POD DRY-RUN step 1).

Functions, not module-level constants — importing this module never touches
jax device state.  Target hardware: TPU v5e pods, 256 chips/pod.
  single-pod : (16, 16)        axes ("data", "model")
  multi-pod  : (2, 16, 16)     axes ("pod", "data", "model")
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes, devices):
    """Mesh with Auto axes: ``shard_hint`` constrains activations through
    ``with_sharding_constraint``, which accepts only Auto axes (the default
    axis type of ``jax.make_mesh`` is Explicit)."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)} — "
            f"run under launch/dryrun.py (forces "
            f"--xla_force_host_platform_device_count=512)")
    return _auto_mesh(shape, axes, devices[:n])


def make_debug_mesh(model: int = 2, data: int = 2):
    """Tiny mesh for CPU sharding tests (requires forced host devices)."""
    n = model * data
    return _auto_mesh((data, model), ("data", "model"), jax.devices()[:n])
