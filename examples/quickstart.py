"""Quickstart: the paper's machinery end to end on one grid.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    access_stream, is_unfavorable, lower_bound_loads,
    natural_order, pad_grid, simulate_misses, star_stencil,
    upper_bound_loads,
)
from repro.core.cache_fitting import plan_schedule
from repro.core.lattice import CacheGeometry, InterferenceLattice
from repro.core.tiling import select_tile
from repro.entry import stencil_iterate
from repro.kernels.ref import star_weights_2nd_order, stencil_ref
from repro.plan import PlanCache, Planner


def main():
    geom = CacheGeometry(2, 512, 4)  # the paper's R10000
    S = geom.size_words
    dims = (45, 91, 60)  # the paper's unfavorable example

    lat = InterferenceLattice(dims, S)
    print(f"grid {dims}, cache S={S} words")
    print(f"  shortest lattice vector: {lat.shortest(norm='l1')} "
          f"(unfavorable: {is_unfavorable(dims, S, diameter=5)})")

    padded, info = pad_grid(dims, S, diameter=5)
    print(f"  padding advisor: {dims} -> {padded} "
          f"(+{info['extra_words']} words, shortest {info['shortest_before']}"
          f" -> {info['shortest_after']})")

    K = star_stencil(3, 2)  # the 13-point star
    # Fig. 4 story: on favorable grids cache-fitting wins ~2x; on the
    # unfavorable n1=45 grid it spikes (paper shows it can even lose);
    # padding recovers — misses/point is the comparable metric.
    for name, d in (("unfavorable", dims), ("padded", padded),
                    ("favorable n1=64", (64, 91, 60))):
        order, bq, info = plan_schedule(d, S, 2, geom=geom)
        pts = (d[0] - 4) * (d[1] - 4) * (d[2] - 4)
        nat = simulate_misses(
            access_stream(d, natural_order(d, 2), K, base_q=bq), geom)
        fit = simulate_misses(access_stream(d, order, K, base_q=bq), geom)
        print(f"  {name}: natural={nat/pts:.3f}/pt cache-fitting="
              f"{fit/pts:.3f}/pt ratio={nat/fit:.2f}")

    lb = lower_bound_loads(padded, S)["bound"]
    ub = upper_bound_loads(padded, S, 2)["bound"]
    print(f"  bounds (padded grid): lower={lb:.0f} <= measured <= upper={ub:.0f}")

    # TPU adaptation: pick a VMEM tile and run the Pallas kernel
    choice = select_tile((64, 128, 512), [(2, 2)] * 3, dtype_bytes=4,
                         n_operands=2)
    print(f"  VMEM tile for (64,128,512): {choice.tile} "
          f"traffic={choice.traffic_bytes/1e6:.1f}MB "
          f"efficiency_vs_isoperimetric={choice.efficiency:.2f}")

    u = jax.random.normal(jax.random.PRNGKey(0), (24, 40, 256), jnp.float32)
    offs, w = star_weights_2nd_order(3, 2)
    out = stencil_iterate(u, offs, w, 1)
    ref = stencil_ref(u, offs, w)
    print(f"  pallas kernel max|err| vs oracle: "
          f"{float(jnp.abs(out - ref).max()):.2e}")

    # The plan compiler: the whole pipeline (lattice -> LLL -> unfavorable
    # detection -> padding -> tiling) as one cached call.  Same machinery,
    # one entry point; `python -m repro.plan.explain 45x91x60` prints the
    # full report.
    planner = Planner(cache=PlanCache(persistent=False))
    plan = planner.plan(shape=dims, offsets=star_stencil(3, 2),
                        geometry=(geom.a, geom.z, geom.w),
                        vmem_budget=S * 4, aligned=False)
    print(f"  plan compiler: pad {plan.pad.pad} -> {plan.pad.padded_shape}, "
          f"tile {plan.tile} sweep axis {plan.sweep_axis}")
    print(f"    planned/legacy traffic = {plan.traffic_vs_legacy:.3f}, "
          f"efficiency vs isoperimetric bound = {plan.efficiency:.2f}")
    plan_again = planner.plan(shape=dims, offsets=star_stencil(3, 2),
                              geometry=(geom.a, geom.z, geom.w),
                              vmem_budget=S * 4, aligned=False)
    assert plan_again == plan  # warm cache hit: O(1), no recompute
    print(f"    warm cache hit: {planner.last_plan_seconds * 1e3:.2f} ms "
          f"(stats {planner.cache.stats['hits']} hits / "
          f"{planner.cache.stats['misses']} misses)")

    # Run the kernel with a plan as the single source of truth (un-planned
    # calls consult the default planner internally).
    from repro.entry import stencil_iterate

    offs, w = star_weights_2nd_order(3, 2)
    tpu_plan = planner.plan(shape=u.shape, offsets=offs)
    out_planned = stencil_iterate(u, offs, w, 1, plan=tpu_plan)
    print(f"  planned kernel max|err| vs oracle: "
          f"{float(jnp.abs(out_planned - ref).max()):.2e}")


if __name__ == "__main__":
    main()
