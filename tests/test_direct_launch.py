"""The direct-input launch (DESIGN.md §16): a zero-fill launch on one
device hands the caller's array to the sweep kernel as it is, and the
kernel writes the zeros outside the grid into its VMEM window itself.

Each parity case runs one launch both ways — direct, and over the
zero-filled launch buffer the other launches build — and asserts the two
results are equal bit for bit.  The remaining cases pin which launches
go direct (``core.tiling.direct_input``), that a direct launch builds no
buffer, what the ``kernel_launch`` span and the ``direct_input_launches``
counter say, and what the planner charges.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ir, obs
from repro.core.cache_fitting import star_stencil
from repro.core.tiling import (
    chain_halo, direct_input, halo_from_offsets, kernel_vmem_bytes,
    window_extents,
)
from repro.kernels.ref import star_weights_2nd_order
from repro.kernels.stencil import (
    _launch_geometry, _padded_call, _stencil_call, embed_inputs,
    input_buffer, launch_pads, stencil_iterate, stencil_pallas,
)

KEY = jax.random.PRNGKey(7)
STAR, STAR_W = star_weights_2nd_order(3, 2)
JACOBI = star_stencil(2, 1)
JACOBI_W = [0.0, 0.25, 0.25, 0.25, 0.25]


def _spec(offs, wts):
    return (tuple(map(tuple, np.asarray(offs).tolist())),
            tuple(float(w) for w in wts))


def _launch(us, offsets_w, tile, sweep, direct, stages_w=None, bcs_w=None,
            dtypes_w=None, window_kind="ring", pipelined=True):
    """One interpreted launch, from the caller's arrays (``direct``) or
    from the zero-filled launch buffer, trimmed to the grid."""
    offsets, weights, stages, lo_w, hi_w = _launch_geometry(
        offsets_w, stages_w, tile, bcs_w, dtypes_w
    )
    u0 = us[0]
    if direct:
        ins = us
    else:
        pads = launch_pads(u0.shape, tile, lo_w, hi_w, u0.dtype.itemsize)
        ins = embed_inputs(us, pads, pad_free=bcs_w is not None)
    out = _padded_call(
        ins, jnp.zeros((u0.ndim,), jnp.int32), offsets, weights, stages,
        lo_w, hi_w, tile, sweep, pipelined, True, u0.shape,
        window_kind=window_kind, direct=direct,
    )
    return out[tuple(slice(0, n) for n in u0.shape)]


def _normal(shape, dtype=jnp.float32, key=KEY):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


STAR_SPEC = _spec(STAR, STAR_W)
# A halo of one whole sublane grain: its sublane tiles can go direct.
WIDE_SPEC = _spec(
    [(0, 0, 0), (-1, 0, 0), (1, 0, 0), (0, -8, 0), (0, 8, 0), (0, 0, -1),
     (0, 0, 1)],
    [0.4, 0.1, 0.1, 0.1, 0.1, 0.1, 0.1],
)
BOX_SPEC = _spec(
    [(i, j, k) for i in (-1, 0, 1) for j in (-1, 0, 1) for k in (-1, 0, 1)],
    np.linspace(-0.5, 0.5, 27),
)

# name -> (arrays, offsets_w, tile, sweep, keyword arguments)
PARITY = {
    # One tile across both grain axes (the star13_512.step1 layout).
    "star_one_tile_across": (
        lambda: [_normal((6, 16, 256))], (STAR_SPEC,), (1, 16, 256), 0, {},
    ),
    "star_one_tile_across_unpipelined": (
        lambda: [_normal((5, 8, 384))], (STAR_SPEC,), (2, 8, 384), 0,
        {"pipelined": False},
    ),
    # The whole grid in one window: lands early on the sweep axis too.
    "star_one_window": (
        lambda: [_normal((6, 16, 128))], (STAR_SPEC,), (6, 16, 128), 0, {},
    ),
    # Several tiles along the sublane (and lane) axis behind a whole-grain
    # halo, a non-divisible sweep tail.
    "wide_sublane_tiles": (
        lambda: [_normal((7, 32, 128))], (WIDE_SPEC,), (2, 8, 128), 0, {},
    ),
    "wide_sublane_sweep": (
        lambda: [_normal((3, 32, 256))], (WIDE_SPEC,), (3, 8, 256), 1,
        {"pipelined": False},
    ),
    # The fused depth-4 chain (the star13_512.smooth4 layout), both
    # frontier layouts.
    "star_T4_ring": (
        lambda: [_normal((6, 32, 256))], (STAR_SPEC,), (1, 16, 256), 0,
        {"stages_w": (STAR_SPEC,) * 4, "window_kind": "ring"},
    ),
    "star_T4_trapezoid": (
        lambda: [_normal((6, 32, 256))], (STAR_SPEC,), (1, 16, 256), 0,
        {"stages_w": (STAR_SPEC,) * 4, "window_kind": "trapezoid"},
    ),
    "multi_rhs_p2": (
        lambda: [_normal((5, 16, 128)),
                 _normal((5, 16, 128), key=jax.random.PRNGKey(8))],
        (STAR_SPEC, BOX_SPEC), (2, 16, 128), 0, {},
    ),
    "jacobi_2d": (
        lambda: [_normal((48, 256))], (_spec(JACOBI, JACOBI_W),), (48, 256),
        0, {},
    ),
    "bf16_chain": (
        lambda: [_normal((5, 32, 128), jnp.bfloat16)], (STAR_SPEC,),
        (1, 32, 128), 0,
        {"stages_w": (STAR_SPEC,) * 4,
         "dtypes_w": ("bfloat16", "bfloat16", "bfloat16", "float32")},
    ),
    "dirichlet_program": (
        lambda: [_normal((6, 16, 256))], (STAR_SPEC,), (1, 16, 256), 0,
        {"stages_w": (STAR_SPEC,) * 2, "bcs_w": (("dirichlet", 1.7),) * 2},
    ),
    "neumann_program": (
        lambda: [_normal((6, 16, 256))], (STAR_SPEC,), (4, 16, 256), 0,
        {"stages_w": (STAR_SPEC,) * 2, "bcs_w": (("neumann", 0.0),) * 2},
    ),
}


def _halo(offsets_w, stages_w=None):
    d = len(offsets_w[0][0][0])
    if stages_w is not None:
        return chain_halo([halo_from_offsets([o], d) for o, _ in stages_w])
    return halo_from_offsets([o for o, _ in offsets_w], d)


@pytest.mark.parametrize("case", sorted(PARITY))
def test_direct_launch_matches_padded_bitwise(case):
    make, offsets_w, tile, sweep, kw = PARITY[case]
    us = tuple(make())
    u0 = us[0]
    assert input_buffer(
        u0.shape, tile, _halo(offsets_w, kw.get("stages_w")),
        u0.dtype.itemsize, kw.get("bcs_w"),
    ) == "direct"
    direct = _launch(us, offsets_w, tile, sweep, True, **kw)
    padded = _launch(us, offsets_w, tile, sweep, False, **kw)
    assert direct.dtype == padded.dtype
    assert np.array_equal(np.asarray(direct), np.asarray(padded))


def _primitives(jaxpr, acc=None):
    """Names of every primitive in a jaxpr, nested jaxprs included."""
    acc = set() if acc is None else acc
    for eqn in jaxpr.eqns:
        acc.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, acc)
    return acc


def _call_jaxpr(u, tile, sweep=0, **kw):
    return jax.make_jaxpr(
        lambda x: _stencil_call(
            (x,), (STAR_SPEC,), tile, sweep, True, True, **kw
        )
    )(u).jaxpr


def test_direct_stencil_call_builds_no_buffer():
    u = jnp.zeros((6, 16, 256), jnp.float32)
    prims = _primitives(_call_jaxpr(u, (1, 16, 256)))
    assert "pallas_call" in prims
    assert not {"pad", "dynamic_update_slice"} & prims


# name -> (shape, dtype, tile, _stencil_call keywords, expected buffer)
BUFFERED = {
    "periodic": ((6, 16, 256), jnp.float32, (1, 16, 256),
                 {"stages_w": (STAR_SPEC,), "bcs_w": (("periodic", 0.0),)},
                 "wrap"),
    "int8_handoff": ((6, 32, 256), jnp.int8, (1, 32, 256),
                     {"stages_w": (STAR_SPEC,), "dtypes_w": ("float32",),
                      "in_quant": (0.05, 3)},
                     "pad"),
    "off_grain_lanes": ((6, 16, 130), jnp.float32, (1, 16, 130), {}, "pad"),
    "off_grain_tile": ((6, 24, 256), jnp.float32, (1, 12, 256), {}, "pad"),
    # Several sublane tiles behind the star's 2-row halo: each tile's
    # window would start 2 rows before a grain.
    "off_grain_tile_halo": ((6, 32, 256), jnp.float32, (1, 8, 256), {},
                            "pad"),
    "off_grain_boundary_program": (
        (6, 20, 256), jnp.float32, (1, 20, 256),
        {"stages_w": (STAR_SPEC,), "bcs_w": (("neumann", 0.0),)}, "embed",
    ),
}


@pytest.mark.parametrize("case", sorted(BUFFERED))
def test_buffered_launches_keep_their_buffer(case):
    shape, dtype, tile, kw, expect = BUFFERED[case]
    assert input_buffer(
        shape, tile, _halo((STAR_SPEC,), kw.get("stages_w")),
        jnp.dtype(dtype).itemsize, kw.get("bcs_w"), kw.get("in_quant"),
    ) == expect
    prims = _primitives(_call_jaxpr(jnp.zeros(shape, dtype), tile, **kw))
    assert {"pad", "dynamic_update_slice"} & prims


def test_sharded_launch_keeps_its_buffer():
    """A §10 sharded launch builds its slabs by the halo exchange over a
    launch buffer: never direct, and its span says so; a one-shard
    request falls back to the single-device launch and goes direct."""
    u = _normal((8, 16, 256))
    offs, wts = np.asarray(WIDE_SPEC[0]), list(WIDE_SPEC[1])
    halo = _halo((WIDE_SPEC,))
    assert input_buffer(u.shape, (1, 8, 256), halo, 4) == "direct"
    assert input_buffer(
        u.shape, (1, 8, 256), halo, 4, num_shards=2
    ) == "pad"
    with obs.recording() as rec:
        sharded = stencil_pallas(
            u, offs, wts, tile=(1, 8, 256), sweep_axis=0, num_shards=2,
            shard_axis=1,
        )
        one = stencil_pallas(
            u, offs, wts, tile=(1, 8, 256), sweep_axis=0, num_shards=1,
        )
    bufs = [s.args["input_buffer"] for s in rec.spans
            if s.name == "kernel_launch"]
    assert bufs == ["pad", "direct"]
    assert rec.counters["direct_input_launches"] == 1
    assert np.array_equal(np.asarray(sharded), np.asarray(one))


def test_direct_input_counter_and_span_attribute():
    """One count per direct launch; every launch's span names its input
    buffer, and ``repro.obs.report`` reconciles the two."""
    from repro.obs.report import reconcile, render, summarize

    u = _normal((6, 16, 256))
    periodic = ir.stencil_program(STAR, STAR_W, 1, d=3, boundary="periodic")
    with obs.recording() as rec:
        stencil_iterate(u, STAR, STAR_W, 2, tile=(1, 16, 256),
                        sweep_axis=0)
        stencil_pallas(u[:, :, :130], STAR, STAR_W, tile=(1, 16, 130),
                       sweep_axis=0)
        ir.run_program(periodic, u, tile=(1, 16, 256), sweep_axis=0)
    bufs = [s.args["input_buffer"] for s in rec.spans
            if s.name == "kernel_launch"]
    assert bufs == ["direct", "pad", "wrap"]
    assert rec.counters["launches"] == 3
    assert rec.counters["direct_input_launches"] == 1
    summary = summarize(rec.to_trace_events())
    assert [l["input_buffer"] for l in summary["launches"]] == bufs
    assert reconcile(summary) == []
    assert "direct_input_launches" in render(summary)
    summary["counters"]["direct_input_launches"] = 2
    assert any("direct_input_launches" in p for p in reconcile(summary))


STAR_HALO = [(2, 2)] * 3


@pytest.mark.parametrize("shape,tile,halo,itemsize,expect", [
    ((512, 512, 512), (1, 512, 512), STAR_HALO, 4, True),   # one tile across
    ((512, 512, 512), (1, 256, 512), [(8, 8)] * 3, 4, True),  # 4-deep chain
    ((512, 512, 512), (1, 256, 512), STAR_HALO, 4, False),  # 2-row halo
    ((512, 512, 512), (1, 260, 512), [(8, 8)] * 3, 4, False),  # tile off
    ((512, 512, 500), (1, 512, 500), STAR_HALO, 4, False),  # lanes off
    ((2800, 2800), (8, 2800), [(1, 1)] * 2, 4, False),      # jacobi-2d
    ((16384, 16384), (8, 16384), [(1, 1)] * 2, 4, False),   # row sweep
    ((256, 256, 256), (1, 256, 256), STAR_HALO, 2, True),   # bf16
    ((256, 8, 256), (1, 8, 256), STAR_HALO, 2, False),      # bf16: 16 rows
    ((70,), (70,), [(3, 0)], 4, False),
])
def test_direct_input_predicate(shape, tile, halo, itemsize, expect):
    assert direct_input(shape, tile, halo, itemsize) is expect
    if expect:
        assert not direct_input(shape, tile, halo, itemsize, num_shards=4)
        assert not direct_input(
            shape, tile, halo, itemsize, in_quant=(0.1, 0)
        )
        assert not direct_input(
            shape, tile, halo, itemsize, bcs=(None, ("periodic", 0.0))
        )
        assert direct_input(
            shape, tile, halo, itemsize, bcs=(("neumann", 0.0),)
        )


@pytest.mark.parametrize("shape,time_steps", [
    ((512, 512, 512), 1), ((512, 512, 512), 4), ((48, 256), 1),
])
def test_direct_launches_charged_what_they_allocate(shape, time_steps):
    """A direct launch allocates the windows and slabs of the buffered
    one (``window_extents``), so the planner's charge — and the plans of
    the star cells — hold for it: the plan goes direct, its tile and
    VMEM figure are the kernel model at that tile, and the kernel's
    scratch is what that model counts."""
    from repro.plan import PlanCache, Planner

    d = len(shape)
    offs = STAR if d == 3 else JACOBI
    plan = Planner(cache=PlanCache(persistent=False)).plan(
        shape=shape, offsets=offs, time_steps=time_steps,
    )
    halo = halo_from_offsets([offs], d)
    stage_halos = [halo] * plan.fused_depth
    win = chain_halo(stage_halos)
    if shape == (512, 512, 512):
        assert plan.tile == ((1, 512, 512) if time_steps == 1
                             else (1, 256, 512))
        assert direct_input(shape, plan.tile, win, 4)
    ext = window_extents(plan.tile, win, 4)
    s = plan.sweep_axis
    cross = int(np.prod([e for i, e in enumerate(ext) if i != s]))
    slabs = 2 * plan.tile[s] * cross if plan.pipelined else 0
    assert plan.vmem_bytes == 4 * (int(np.prod(ext)) + slabs)
    assert kernel_vmem_bytes(
        plan.tile, halo, 4, s, plan.pipelined, stage_halos=stage_halos,
        window_kind=plan.window_kind,
    ) <= 128 * 2**20
    # The plan report answers with the launcher's predicate.
    from repro.plan.explain import launch_input

    assert launch_input(plan) == "direct"
    sharded = Planner(cache=PlanCache(persistent=False)).plan(
        shape=shape, offsets=offs, time_steps=time_steps, num_shards=4,
    )
    assert launch_input(sharded) == "buffer"


# mg27 (NPB MG operator A, periodic, 4 shards over axis 0): the plans the
# buffered sharded path was planned with before direct launches existed.
MG27_PLANS = {
    512: ((128, 8, 512), 1, 10649600,
          "f70b5ebc60d0741be499369b2e2494fc128f3c839cf0cb673e92557cf3646a1f"),
    1024: ((256, 8, 512), 1, 21135360,
           "eb98064d87a2a06882a6279d3e44dbc48dbcf01e822b76cfbfeee4edaa0f5657"),
}


@pytest.mark.parametrize("n", sorted(MG27_PLANS))
def test_sharded_mg27_plans_unchanged(n):
    """The request ``ir.run_program(A, u, num_shards=4)`` plans with."""
    from repro.plan import PlanCache, Planner

    path = os.path.join(
        os.path.dirname(__file__), os.pardir, "bench", "configs",
        "mg27_512.json",
    )
    with open(path) as f:
        offs = np.asarray(json.load(f)["operator"]["offsets"])
    plan = Planner(cache=PlanCache(persistent=False)).plan(
        shape=(n,) * 3, dtype_bytes=4, n_operands=2, num_shards=4,
        window_kind="auto", stages=[offs], bcs=(("periodic", 0.0),),
    )
    tile, sweep, vmem, key = MG27_PLANS[n]
    assert (plan.tile, plan.sweep_axis, plan.vmem_bytes) == (
        tile, sweep, vmem
    )
    assert plan.request.cache_key() == key
