"""Public jit'd API for the stencil kernels.

``apply_stencil`` is what the rest of the framework calls (examples,
benchmarks, the Mamba2/Whisper conv frontends fall back to it for their
1-D stencils).  It reports the tile decision so callers can log the
cache-fitting statistics (traffic vs. isoperimetric bound), and
``traffic_report`` compares the sweep-reuse model against the per-tile-halo
model so the benchmark harness can track the HBM-traffic trajectory.
"""

from __future__ import annotations

from typing import Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.tiling import (
    TileChoice,
    DEFAULT_VMEM_BUDGET,
    select_tile,
)

from .ref import star_weights_2nd_order, stencil_ref
from .stencil import multi_stencil_pallas, stencil_iterate, stencil_pallas

__all__ = [
    "apply_stencil",
    "apply_star_2nd_order",
    "apply_multi_rhs",
    "plan_tiles",
    "traffic_report",
    "stencil_iterate",
    "stencil_ref",
    "star_weights_2nd_order",
]


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


def apply_stencil(
    u: jnp.ndarray,
    offsets: np.ndarray,
    weights: Sequence[float],
    tile: Sequence[int] | None = None,
    interpret: bool | None = None,
    sweep_axis: int | None = None,
    pipelined: bool = True,
    time_steps: int = 1,
) -> jnp.ndarray:
    """q = K u with zero boundary fill; sweep-pipelined Pallas tiles.
    ``time_steps=T > 1`` fuses T applications into the §8 trapezoid."""
    return stencil_pallas(
        u, offsets, weights, tile=tile, interpret=interpret,
        sweep_axis=sweep_axis, pipelined=pipelined, time_steps=time_steps,
    )


def apply_star_2nd_order(
    u: jnp.ndarray, tile: Sequence[int] | None = None,
    interpret: bool | None = None,
    sweep_axis: int | None = None,
) -> jnp.ndarray:
    """The paper's measured operator: second-order star (13-point in 3-D)."""
    offsets, weights = star_weights_2nd_order(u.ndim, r=2)
    return apply_stencil(
        u, offsets, weights, tile=tile, interpret=interpret,
        sweep_axis=sweep_axis,
    )


def apply_multi_rhs(
    us: Sequence[jnp.ndarray],
    offsets_list: Sequence[np.ndarray],
    weights_list: Sequence[Sequence[float]],
    tile: Sequence[int] | None = None,
    interpret: bool | None = None,
    sweep_axis: int | None = None,
) -> jnp.ndarray:
    """q = Σ_p K_p u_p (§5) with the per-operand VMEM budget split."""
    return multi_stencil_pallas(
        us, offsets_list, weights_list, tile=tile, interpret=interpret,
        sweep_axis=sweep_axis,
    )
