"""Production mesh construction (assignment spec).

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module does not touch jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import (see dryrun.py); smoke tests and benchmarks see the real single
device.
"""
from __future__ import annotations

from typing import Sequence

import jax
import numpy as np


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Arbitrary mesh for tests/examples (e.g. (1,1) on CPU)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def slot_pool_mesh(n_shards: int):
    """1-D mesh backing the serving engine's sharded slot pool.

    One mesh device = one engine shard (``repro/service/sharding.py``).
    Requires ``n_shards <= len(jax.devices())``; the service layer falls
    back to round-robin logical shards when oversubscribed (CPU tests
    without ``XLA_FLAGS=--xla_force_host_platform_device_count``).
    """
    return make_mesh((n_shards,), ("pool",))


def local_test_mesh(model: int = 1):
    """Mesh over whatever devices exist locally (CPU smoke/integration)."""
    n = len(jax.devices())
    return make_mesh((n // model, model), ("data", "model"))


def dp_axes(mesh) -> tuple:
    """Axes used for batch/FSDP sharding ('pod' folds into DP)."""
    return tuple(a for a in mesh.axis_names if a != "model")


def mesh_size(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
