"""Column-sharded stencil launches: ``jax.shard_map`` over sweep columns.

Implements DESIGN.md §10.  The paper's cache-fitting decomposition makes
cross-axis tile columns independent by construction, and the §9 frontier
rings keep them that way (each sweep column warms its own rings at
``k == 0``), so the sweep engine parallelizes over cores by *partitioning
columns*, not by changing the kernel: this module splits one cross axis
of the grid over a 1-axis device mesh, runs the unmodified
:func:`repro.kernels.stencil._padded_call` sweep kernel on each shard's
column slab, and exchanges only the shard-boundary halos.

Mechanics, per launch of a (possibly stage-fused) stencil program:

* **Partition**: the shard axis ``a`` is a cross axis (never the sweep
  axis).  Columns are rounded up so every shard owns ``k`` whole tile
  columns (``C = k·tile_a`` rows) and the chain's dependency cone along
  ``a`` fits inside one neighbor (``C ≥ max(lo_a, hi_a)``); round-up
  slack computes zeros and is trimmed, exactly like the single-device
  pad path, so non-divisible column counts need no special casing.
* **Halo exchange**: each shard ``ppermute``s its trailing ``lo_a`` rows
  to the next shard and its leading ``hi_a`` rows to the previous one —
  the only cross-device traffic.  Mesh-edge shards receive ``ppermute``'s
  zero fill, which is bit-identical to the zero pad the single-device
  launch reads there, so the sharded result equals the single-device
  result **bit-wise** (same windows, same f32 accumulation order).
* **Global masks**: the §8/§9 intermediate-stage domain masks need
  true-grid coordinates; each shard passes its column offset
  (``axis_index · C``) into the kernel's SMEM domain-offset vector, so
  the one SPMD trace masks correctly on every shard.

The planner prices this decomposition (plan schema v4:
``PlanRequest.num_shards``, ``StencilPlan.shard_axis`` /
``per_shard_traffic_bytes`` / ``halo_exchange_bytes``); the kernel
frontends (``stencil_pallas(num_shards=...)``) route launches here.

§14 rides along unchanged: ``window_kind``/``dtypes_w`` pass straight
through to ``_padded_call``, and the exchanged halo bands are slices of
the launch's *input* arrays — a mixed-precision chain's later launches
therefore exchange at the previous stage's output dtype for free (the
band inherits the array's element width).
"""

from __future__ import annotations

import functools
from math import prod

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import obs

__all__ = ["column_launcher", "pick_shard_axis", "sharded_stencil_call"]


def pick_shard_axis(shape, tile, sweep_axis) -> int:
    """Default shard axis: the cross axis with the most tile columns
    (ties to the lowest index) — never the sweep axis, whose columns are
    the unit of the engine's halo reuse, not a partitionable extent."""
    d = len(shape)
    cross = [i for i in range(d) if i != sweep_axis]
    if not cross:
        raise ValueError(
            f"column sharding needs a cross axis: grid {tuple(shape)} has "
            f"none besides sweep axis {sweep_axis}"
        )
    ncols = {i: -(-int(shape[i]) // int(tile[i])) for i in cross}
    return max(cross, key=lambda i: (ncols[i], -i))


def column_launcher(num_shards=None, shard_axis=None, mesh=None):
    """A drop-in for ``kernels.stencil._stencil_call`` that runs every
    launch column-sharded — what ``multi_stencil_pallas`` substitutes
    when the call (or its plan) asks for more than one shard."""

    def launch(us, offsets_w, tile, sweep, pipelined, interpret,
               stages_w=None, bcs_w=None, dtypes_w=None,
               window_kind="ring", quants_w=None, in_quant=None):
        return sharded_stencil_call(
            us, offsets_w, tile, sweep, pipelined, interpret,
            stages_w=stages_w, bcs_w=bcs_w, dtypes_w=dtypes_w,
            window_kind=window_kind, quants_w=quants_w, in_quant=in_quant,
            num_shards=num_shards, shard_axis=shard_axis, mesh=mesh,
        )

    return launch


def sharded_stencil_call(
    us, offsets_w, tile, sweep, pipelined, interpret, stages_w=None,
    bcs_w=None, dtypes_w=None, window_kind="ring", quants_w=None,
    in_quant=None, num_shards=None, shard_axis=None, mesh=None,
):
    """One column-sharded launch; signature and result match
    ``_stencil_call`` exactly (bit-wise).  ``mesh`` must be a 1-axis
    mesh; ``mesh=None`` builds one over the first ``num_shards`` devices
    (:func:`repro.launch.mesh.make_column_mesh`).  A 1-shard request
    falls back to the plain single-device call."""
    from repro.kernels.stencil import _stencil_call

    us = tuple(us)
    u0 = us[0]
    d = u0.ndim
    tile = tuple(int(t) for t in tile)
    sweep = int(sweep)
    if mesh is None:
        num_shards = 1 if num_shards is None else int(num_shards)
        if num_shards == 1:
            return _stencil_call(
                us, offsets_w, tile, sweep, pipelined, interpret,
                stages_w=stages_w, bcs_w=bcs_w, dtypes_w=dtypes_w,
                window_kind=window_kind, quants_w=quants_w,
                in_quant=in_quant,
            )
        from repro.launch.mesh import make_column_mesh

        mesh = make_column_mesh(num_shards)
    else:
        size = int(prod(mesh.shape[a] for a in mesh.axis_names))
        if num_shards is not None and int(num_shards) != size:
            raise ValueError(
                f"num_shards={num_shards} contradicts mesh of {size} devices"
            )
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"column sharding wants a 1-axis mesh, got axes "
                f"{mesh.axis_names}"
            )
        if size == 1:
            return _stencil_call(
                us, offsets_w, tile, sweep, pipelined, interpret,
                stages_w=stages_w, bcs_w=bcs_w, dtypes_w=dtypes_w,
                window_kind=window_kind, quants_w=quants_w,
                in_quant=in_quant,
            )
    if shard_axis is None:
        shard_axis = pick_shard_axis(u0.shape, tile, sweep)
    a = int(shard_axis)
    if not 0 <= a < d:
        raise ValueError(f"shard_axis {a} out of range for {d}-d grid")
    if a == sweep:
        raise ValueError(
            f"shard_axis {a} is the sweep axis: columns are partitioned "
            "across the sweep, not along it"
        )
    run = _build_sharded(
        mesh, a, tile, sweep, bool(pipelined), bool(interpret), offsets_w,
        stages_w, bcs_w, dtypes_w, str(window_kind), quants_w, in_quant,
        tuple(int(n) for n in u0.shape), str(u0.dtype), len(us),
    )
    if obs.enabled():
        # The exchange itself runs inside the jitted SPMD program, so the
        # Python layer records the *modeled* geometry (same arithmetic as
        # _build_sharded): ppermute rounds and cross-device bytes per
        # launch.  The span wraps the whole sharded dispatch.
        from repro.kernels.stencil import _launch_geometry, _round_up

        S = int(mesh.shape[mesh.axis_names[0]])
        *_, lo_w, hi_w = _launch_geometry(
            offsets_w, stages_w, tile, bcs_w=bcs_w
        )
        lo_a, hi_a = int(lo_w[a]), int(hi_w[a])
        padded = [_round_up(int(n), t) for n, t in zip(u0.shape, tile)]
        cross_ext = prod(
            padded[i] + lo_w[i] + hi_w[i] for i in range(d) if i != a
        )
        rounds = len(us) * (int(lo_a > 0) + int(hi_a > 0))
        xbytes = (
            len(us) * (S - 1) * (lo_a + hi_a) * cross_ext
            * u0.dtype.itemsize
        )
        obs.add("halo_exchange_rounds", rounds)
        obs.add("halo_exchange_bytes", xbytes)
        with obs.span(
            "halo_exchange", shard_axis=a, num_shards=S,
            rows_lo=lo_a, rows_hi=hi_a,
            exchange_rounds=rounds, exchange_bytes=xbytes,
        ):
            return run(*us)
    return run(*us)


@functools.lru_cache(maxsize=128)
def _build_sharded(mesh, a, tile, sweep, pipelined, interpret, offsets_w,
                   stages_w, bcs_w, dtypes_w, window_kind, quants_w,
                   in_quant, shape, dtype, p):
    """Build (and cache) the jitted shard_map'd launch for one static
    configuration — meshes and the offset/stage/boundary specs are
    hashable, so repeated shapes re-enter the compiled function
    directly."""
    from repro.core.tiling import window_extents
    from repro.kernels.stencil import (
        _launch_geometry,
        _padded_call,
        embed_inputs,
        launch_pads,
    )

    itemsize = jnp.dtype(dtype).itemsize
    d = len(shape)
    axis_name = mesh.axis_names[0]
    S = int(mesh.shape[axis_name])
    offsets, weights, stages, lo_w, hi_w = _launch_geometry(
        offsets_w, stages_w, tile, bcs_w=bcs_w, dtypes_w=dtypes_w,
        quants_w=quants_w,
    )
    t_a = tile[a]
    lo_a, hi_a = lo_w[a], hi_w[a]
    ncols = -(-shape[a] // t_a)
    # Whole columns per shard: enough to cover the columns evenly AND to
    # contain the chain's cone within one neighbor (halo exchange is
    # nearest-neighbor only); the round-up slack computes zeros and is
    # trimmed, like the single-device pad path.
    k = max(-(-ncols // S), -(-lo_a // t_a), -(-hi_a // t_a), 1)
    C = k * t_a
    # Host pad: the launch pads on every dim except the shard axis, whose
    # boundary rows come from the exchange (or its zero fill at the ends);
    # it only rounds up to whole shards.
    pads = launch_pads(shape, tile, lo_w, hi_w, itemsize)
    pads[a] = (0, S * C - shape[a])
    # Behind the received hi band, each local slab needs the DMA grain's
    # slack for its last window (``window_extents``).
    slack_a = (
        window_extents(tile, list(zip(lo_w, hi_w)), itemsize)[a]
        - t_a - lo_a - hi_a
    )
    # Periodic wrap (§15): the ghost fill on non-shard axes happens in
    # the embed below; on the shard axis the exchange ring closes —
    # extra ppermute links (S−1 → 0 forward, 0 → S−1 backward) carry the
    # wrap bands that the mesh edges otherwise zero-fill.
    periodic = bcs_w is not None and any(
        bc is not None and bc[0] == "periodic" for bc in bcs_w
    )
    n_a = shape[a]
    # The domain ring closes over the shards that own true rows: shard
    # ``last`` holds the domain's trailing rows (round-up slack may
    # leave later shards with none), so the wrap links are
    # (last → 0) forward and (0 → last) backward — and shard last's
    # normal forward send retargets from its slack neighbor to shard 0
    # (a ppermute destination appears at most once).
    last = -(-n_a // C) - 1
    n_last = n_a - last * C  # true rows owned by shard ``last``
    if periodic and n_last < max(lo_a, hi_a, 1):
        raise ValueError(
            f"periodic shard axis {a}: the trailing shard owns {n_last} "
            f"true rows but the wrap bands need max(lo, hi) = "
            f"{max(lo_a, hi_a)} — the wrap would span more than one "
            "neighbor; use fewer shards or a smaller tile"
        )
    if periodic:
        fwd = [(s, s + 1) for s in range(S - 1) if s + 1 <= last]
        fwd.append((last, 0))
        bwd = [(s + 1, s) for s in range(S - 1) if s <= last - 1]
        bwd.append((0, last))
    else:
        fwd = [(s, s + 1) for s in range(S - 1)]
        bwd = [(s + 1, s) for s in range(S - 1)]
    # Non-divisible extents leave round-up slack on shard ``last``: its
    # wrap-band send starts at the end of its *true* rows, and the wrap
    # band it receives lands right after them — traced (axis_index-
    # dependent) offsets, static everywhere the extent divides.
    ragged = periodic and n_last != C

    def local_fn(*blocks):
        idx = jax.lax.axis_index(axis_name)
        locs = []
        for b in blocks:
            parts = []
            recv_hi = None
            if lo_a:
                if ragged:
                    start = jnp.where(
                        idx == last, n_last - lo_a, C - lo_a
                    )
                    tail = jax.lax.dynamic_slice_in_dim(
                        b, start, lo_a, axis=a
                    )
                else:
                    tail = jax.lax.slice_in_dim(b, C - lo_a, C, axis=a)
                parts.append(jax.lax.ppermute(tail, axis_name, fwd))
            parts.append(b)
            if hi_a:
                head = jax.lax.slice_in_dim(b, 0, hi_a, axis=a)
                recv_hi = jax.lax.ppermute(head, axis_name, bwd)
                parts.append(
                    jnp.zeros_like(recv_hi) if ragged else recv_hi
                )
            if slack_a:
                ext = list(b.shape)
                ext[a] = slack_a
                parts.append(jnp.zeros(ext, b.dtype))
            loc = jnp.concatenate(parts, axis=a) if len(parts) > 1 else b
            if ragged and hi_a:
                pos = [0] * d
                pos[a] = jnp.where(idx == last, lo_a + n_last, lo_a + C)
                loc = jax.lax.dynamic_update_slice(loc, recv_hi, pos)
            locs.append(loc)
        # The shard's column offset, in true-grid coordinates: lifts the
        # kernel's intermediate-stage domain masks into the global frame.
        dom = jnp.zeros((d,), jnp.int32).at[a].set(
            idx.astype(jnp.int32) * C
        )
        return _padded_call(
            locs, dom, offsets, weights, stages, lo_w, hi_w, tile, sweep,
            pipelined, interpret, shape, window_kind=window_kind,
            in_quant=in_quant,
        )

    spec = P(*[axis_name if i == a else None for i in range(d)])
    sharded = jax.shard_map(
        local_fn, mesh=mesh, in_specs=(spec,) * p, out_specs=spec,
        check_vma=False,
    )

    pad_free = bcs_w is not None and any(bc is not None for bc in bcs_w)
    wrap = (
        tuple(
            (0, 0) if i == a else (lo_w[i], hi_w[i]) for i in range(d)
        )
        if periodic else None
    )
    fill = int(in_quant[1]) if in_quant is not None else 0

    sharding = NamedSharding(mesh, spec)

    def run(*arrays):
        ins = embed_inputs(arrays, pads, pad_free=pad_free, wrap=wrap,
                           fill=fill)
        # Build the launch buffers shard by shard, each on its own device,
        # rather than whole on one device before the split.
        ins = [jax.lax.with_sharding_constraint(x, sharding) for x in ins]
        out = sharded(*ins)
        return out[tuple(slice(0, n) for n in shape)]

    return jax.jit(run)
