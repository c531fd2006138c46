"""RK2-style damped-Jacobi smoother pair as a fused stage-chain program.

The classic two-sweep smoother applies the damped Jacobi operator

    u  <-  (1 - omega) u + (omega / 2d) * sum(neighbors)

twice with *distinct* damping factors (omega_1, omega_2) — the same
shape as an RK2 sub-step pair for du/dt = L u: two linear stages, one
operator footprint, different per-stage weights.  PR4's stage-chain
engine fuses both sweeps into a single HBM pass (DESIGN.md §9): the VMEM
window carries the two-stage dependency cone, and the intermediate
iterate lives in a streaming frontier ring that persists across sweep
steps, so neither stage is ever recomputed inside the window overlap.

Run:  PYTHONPATH=src python examples/rk2_damped_jacobi.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache_fitting import star_stencil
from repro.entry import stencil_iterate
from repro.kernels.ref import stencil_ref
from repro.plan import PlanCache, Planner

SHAPE = (48, 64, 96)
OMEGAS = (0.8, 0.5)   # distinct per-stage damping: the "RK2" pair


def damped_jacobi_stage(d: int, omega: float):
    """(offsets, weights) of one damped-Jacobi sweep of the 2d-point
    Laplacian: contraction for omega in (0, 1]."""
    offs = star_stencil(d, 1)
    weights = [
        (1.0 - omega) if not any(off) else omega / (2 * d) for off in offs
    ]
    return offs, weights


def main() -> None:
    d = len(SHAPE)
    stages = [damped_jacobi_stage(d, w) for w in OMEGAS]
    u = jax.random.normal(jax.random.PRNGKey(0), SHAPE, jnp.float32)

    # Plan the chain explicitly so we can show the planner's reasoning;
    # stencil_iterate would consult the same planner implicitly.
    planner = Planner(cache=PlanCache(persistent=False))
    plan = planner.plan(
        shape=SHAPE, stages=[offs for offs, _ in stages],
        # A 1 MiB budget keeps the window smaller than the grid, so the
        # engine actually sweeps — and the frontier ring actually streams.
        vmem_budget=1 << 20, aligned=True,
    )
    print(f"grid {SHAPE}, {len(stages)}-stage damped-Jacobi chain "
          f"(omegas {OMEGAS})")
    print(f"  tile {plan.tile}, sweep axis {plan.sweep_axis}, "
          f"fused depth {plan.fused_depth}")
    print(f"  modeled traffic {plan.traffic_bytes / (1 << 20):.2f} MiB "
          f"(single-pass chain: "
          f"{plan.single_pass_traffic_bytes / (1 << 20):.2f} MiB -> "
          f"{plan.single_pass_traffic_bytes / plan.traffic_bytes:.2f}x cut)")
    print(f"  modeled flops: streaming {plan.modeled_flops:,} vs recompute "
          f"{plan.recompute_flops:,} "
          f"({plan.recompute_flops / max(plan.modeled_flops, 1):.2f}x saved)")

    fused = stencil_iterate(u, stages=stages, plan=plan)

    ref = u
    for offs, w in stages:
        ref = stencil_ref(ref, offs, w)
    err = float(jnp.abs(fused - ref).max())
    print(f"  max |fused - iterated reference| = {err:.2e}")
    assert err < 1e-5, "fused chain diverged from the iterated reference"
    resid = float(jnp.abs(fused).max() / jnp.abs(u).max())
    print(f"  smoother contraction (max-norm ratio) = {resid:.3f}")
    print("OK")


if __name__ == "__main__":
    main()
