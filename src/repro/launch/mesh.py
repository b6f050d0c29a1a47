"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with ``Auto`` axes.

    The model code places activations with ``with_sharding_constraint``
    (``models.common.constrain``), which binds only to ``Auto`` axes;
    ``jax.make_mesh`` defaults to ``Explicit`` ones.
    """
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e: 16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_auto_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for CPU smoke tests."""
    return make_auto_mesh((1, 1), ("data", "model"))


def make_iru_mesh(n_partitions: int = 4):
    """1-D mesh for the banked IRU engine's ``shard_map`` row stage.

    Partitions shard over the ``part`` axis, so the axis size must divide
    ``n_partitions``; this picks the largest such device count available
    (e.g. 4 partitions on 8 devices -> 4-device mesh, on 1 device -> the
    degenerate 1-device mesh, which is how single-host tests exercise the
    multi-device code path).
    """
    import numpy as np

    devices = jax.devices()
    d = max(k for k in range(1, min(n_partitions, len(devices)) + 1)
            if n_partitions % k == 0)
    return jax.sharding.Mesh(np.asarray(devices[:d]), ("part",))


def make_graph_mesh(n_parts: int):
    """1-D mesh for the edge-partitioned frontier pipeline.

    One graph shard per device over the ``gpart`` axis
    (``dist.graph_partition``), so exactly ``n_parts`` devices are
    required — the partition's stacked [P, ...] arrays shard one row per
    device and the boundary all-to-all runs over this axis.  On CPU, force
    host devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    import numpy as np

    devices = jax.devices()
    if len(devices) < n_parts:
        raise ValueError(
            f"make_graph_mesh: need {n_parts} devices for {n_parts} graph "
            f"shards, have {len(devices)} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_parts} on CPU)")
    return jax.sharding.Mesh(np.asarray(devices[:n_parts]), ("gpart",))
