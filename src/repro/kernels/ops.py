"""Modeled tile decisions for logging and reports.

``plan_tiles`` exposes the cache-fitting tile decision so callers can log
its statistics (traffic vs. isoperimetric bound), and ``traffic_report``
compares the sweep-reuse model against the per-tile-halo model.  Calls
themselves go through ``repro.entry`` (``stencil_iterate``, ``run``).
"""

from __future__ import annotations

from typing import Sequence

from repro.core.tiling import (
    TileChoice,
    DEFAULT_VMEM_BUDGET,
    select_tile,
)

__all__ = ["plan_tiles", "traffic_report"]


def plan_tiles(
    shape: Sequence[int],
    r: int,
    dtype_bytes: int = 4,
    n_operands: int = 2,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    sweep_axis: int | None | str = "auto",
) -> TileChoice:
    """Expose the cache-fitting tile decision (for logging / benchmarks)."""
    return select_tile(
        shape, [(r, r)] * len(shape), dtype_bytes=dtype_bytes,
        vmem_budget=vmem_budget, n_operands=n_operands,
        sweep_axis=sweep_axis,
    )


def traffic_report(
    shape: Sequence[int],
    r: int,
    dtype_bytes: int = 4,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    n_operands: int = 2,
    aligned: bool = True,
) -> dict:
    """Modeled HBM traffic: sweep-reuse vs. the per-tile-halo model, each
    with its own best tile under the same VMEM budget, plus the
    isoperimetric lower bound (all in bytes)."""
    halo = [(r, r)] * len(shape)
    naive = select_tile(
        shape, halo, dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
        n_operands=n_operands, sweep_axis=None, aligned=aligned,
    )
    swept = select_tile(
        shape, halo, dtype_bytes=dtype_bytes, vmem_budget=vmem_budget,
        n_operands=n_operands, sweep_axis="auto", aligned=aligned,
    )
    return {
        "shape": tuple(int(n) for n in shape),
        "radius": int(r),
        "vmem_budget_bytes": int(vmem_budget),
        "per_tile_halo": {
            "tile": naive.tile,
            "traffic_bytes": naive.traffic_bytes,
            "efficiency": naive.efficiency,
        },
        "sweep_reuse": {
            "tile": swept.tile,
            "sweep_axis": swept.sweep_axis,
            "traffic_bytes": swept.traffic_bytes,
            "efficiency": swept.efficiency,
        },
        "lower_bound_bytes": swept.lower_bound_bytes,
        "traffic_ratio": naive.traffic_bytes / max(swept.traffic_bytes, 1),
    }
