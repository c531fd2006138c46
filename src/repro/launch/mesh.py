"""Production meshes (DESIGN.md §6 model sharding, §10 column sharding).

Three mesh families, one per consumer:

* :func:`make_production_mesh` — the (pod, data, model) training/serving
  meshes whose axes the logical-axis rules of
  :mod:`repro.parallel.sharding` map onto (DESIGN.md §6).
* :func:`make_column_mesh` — the 1-axis ``("columns",)`` mesh the
  column-sharded sweep engine partitions stencil grids over
  (:mod:`repro.parallel.shard_columns`, DESIGN.md §10).
* :func:`make_test_mesh` — a tiny mesh over whatever (CPU) devices exist,
  for unit tests.

Functions, not module-level constants — importing this module never
touches jax device state (required so smoke tests see 1 CPU device while
the dry-run sees 512 placeholder devices).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 (2 pods, 512 chips).

    With the dry-run's 512 placeholder devices the single-pod mesh uses the
    first 256; on real hardware the slice is the pod's own device list.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devs)} — the "
            "dry-run must set XLA_FLAGS=--xla_force_host_platform_device_count=512 "
            "before any jax import"
        )
    return jax.make_mesh(
        shape, axes, devices=devs[:n], axis_types=_auto(len(axes))
    )


def make_column_mesh(num_shards: int, axis_name: str = "columns",
                     devices=None):
    """1-axis mesh for the §10 column-sharded stencil launch: the sweep
    engine partitions cross-axis tile columns over exactly this axis.

    On CPU the host platform exposes one device unless
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` is set before
    the first jax import (how the parity tests and ``benchmarks/
    shard_columns.py`` build their test meshes)."""
    devs = list(devices) if devices is not None else jax.devices()
    num_shards = int(num_shards)
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if len(devs) < num_shards:
        raise RuntimeError(
            f"column mesh needs {num_shards} devices, found {len(devs)} — "
            "on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{num_shards} before any jax import"
        )
    return jax.make_mesh((num_shards,), (axis_name,),
                         devices=devs[:num_shards], axis_types=_auto(1))


def make_test_mesh(data: int = 1, model: int = 1):
    """Tiny mesh over however many (CPU) devices exist — for unit tests."""
    devs = jax.devices()
    data = min(data, len(devs))
    model = min(model, len(devs) // data)
    return jax.make_mesh(
        (data, model), ("data", "model"), devices=devs[: data * model],
        axis_types=_auto(2),
    )


def _auto(n: int) -> tuple:
    """Auto axis types: shardings are propagated by the compiler and the
    logical-axis constraints, as every mesh consumer here expects (JAX
    makes Explicit axes by default)."""
    return (AxisType.Auto,) * n
