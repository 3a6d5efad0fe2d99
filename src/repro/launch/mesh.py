"""Production meshes.

Single pod : (16, 16)    = ("data", "model")   — 256 chips (one v5e pod)
Multi-pod  : (2, 16, 16) = ("pod", "data", "model") — 512 chips

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before first jax init; smoke tests see
the real single CPU device).

Mesh-axis roles (DESIGN.md §6):
  pod   — pure data parallelism; params replicated per pod; the only
          cross-pod (DCN) collective is the gradient all-reduce
  data  — batch DP + FSDP (params/optimizer sharded ZeRO-3 style)
  model — tensor parallelism (heads / ff / vocab / experts / lru)
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devs)} — run via "
            f"launch/dryrun.py (which sets xla_force_host_platform_device_count)")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devs[:n])


def make_test_mesh(shape: Tuple[int, ...] = (1, 1),
                   axes: Tuple[str, ...] = ("data", "model")):
    """A trivial mesh on however many devices exist (CPU tests)."""
    import jax
    n = int(np.prod(shape))
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:n])


def dp_axes(mesh) -> Tuple[str, ...]:
    """Mesh axes that carry the batch dimension."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
