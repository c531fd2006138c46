"""The paper's application: Jacobi-style sweeps of the 13-point operator
over a 3-D structured grid, with cache-fitting tiles and padding advice.

    PYTHONPATH=src python examples/stencil_pipeline.py --iters 10
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.padding import advise_dim
from repro.core.tiling import select_tile
from repro.entry import stencil_iterate
from repro.kernels.ops import plan_tiles
from repro.kernels.ref import star_weights_2nd_order, stencil_ref


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", type=int, nargs=3, default=(32, 64, 256))
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    shape = tuple(args.shape)

    # layout advice (the §6 adaptation): is the minor dim lane-aligned?
    adv = advise_dim(shape[-1], 128)
    print(f"minor dim {shape[-1]}: {'pad to ' + str(adv['padded']) if adv['unfavorable'] else 'favorable'}")
    plan = plan_tiles(shape, r=2)
    print(f"tile plan: {plan.tile} grid={plan.grid} "
          f"traffic={plan.traffic_bytes/1e6:.1f}MB efficiency={plan.efficiency:.2f}")

    u = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.float32)
    # one verification sweep against the oracle (keep the planner's sweep
    # axis — the tile shape was optimized for it)
    offs, w = star_weights_2nd_order(3, 2)
    out = stencil_iterate(u, offs, w, 1, tile=plan.tile,
                          sweep_axis=plan.sweep_axis)
    ref = stencil_ref(u, offs, w)
    err = float(jnp.abs(out - ref).max())
    assert err < 1e-3, err
    print(f"verified vs oracle (max|err|={err:.2e}); running {args.iters} sweeps")

    t0 = time.time()
    x = u
    for _ in range(args.iters):
        x = stencil_iterate(x, offs, w, 1, tile=plan.tile,
                            sweep_axis=plan.sweep_axis)
        x = x / jnp.maximum(jnp.abs(x).max(), 1e-6)  # keep finite
    x.block_until_ready()
    dt = time.time() - t0
    pts = np.prod(shape) * args.iters
    print(f"{dt:.2f}s total, {pts/dt/1e6:.1f} Mpoint/s (interpret mode, CPU)")
    return x


if __name__ == "__main__":
    main()
