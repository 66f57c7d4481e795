"""Production mesh construction.

A function, not a module-level constant, so importing this module never
touches jax device state (the dry-run sets XLA_FLAGS before first init).

Every mesh is built by :func:`make_mesh`, with ``Auto`` axis types: the
sharding rules (``distributed/sharding.py``) place arrays with
``with_sharding_constraint``, which accepts only ``Auto`` axes, and
``jax.make_mesh`` defaults to ``Explicit`` ones.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    The "pod" axis is an outer data-parallel axis: batch shards over
    ("pod", "data"), so the only cross-pod (DCN) traffic is the gradient
    all-reduce — see distributed/sharding.py DEFAULT_RULES.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh for CPU tests/examples (1 device)."""
    return make_mesh(shape, axes)


def make_cstep_mesh(n_data: int | None = None):
    """Data-only mesh for the sharded grouped C step.

    The C step's packed item axes shard over "data"
    (``distributed/sharding.py`` rule ``"items"``), so a bench or test
    that only exercises the C step wants every local device on that
    axis. Defaults to all visible devices; on a forced-host-device CPU
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) that is the
    8 fake devices, on a real single-device CPU it degrades to (1, 1)
    and the sharded path becomes an annotated no-op.
    """
    n = n_data if n_data is not None else len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
