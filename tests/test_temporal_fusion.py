"""Temporal-blocked sweep fusion (DESIGN.md §8) + PR3 bugfix regressions.

Covers: fused-vs-iterated-reference equivalence across non-divisible
shapes, asymmetric (conv1d-style) halos and T ∈ {1, 2, 3}; the T-aware
traffic/VMEM model; planner fused-depth selection with its never-worse
guarantees; plan-mismatch validation; and the non-TPU/CPU backend
interpret fallback.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cache_fitting import star_stencil
from repro.core.tiling import (
    fused_halo,
    fused_stage_bytes,
    select_tile,
    tile_traffic_bytes,
    tile_vmem_bytes,
)
from repro import ir
from repro.entry import run, stencil_iterate
from repro.kernels.ref import stencil_ref
from repro.kernels.stencil import _stencil_call
from repro.plan import PlanCache, PlanMismatchError, Planner

KEY = jax.random.PRNGKey(0)


def iterate_ref(u, offsets, weights, time_steps):
    for _ in range(time_steps):
        u = stencil_ref(u, offsets, weights)
    return u


@pytest.fixture
def planner():
    return Planner(cache=PlanCache(persistent=False))


# ---------------------------------------------------------------------------
# Fused-kernel equivalence vs the iterated reference.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,tile,axis", [
    ((40,), (16,), 0),                 # 1-D, non-divisible (pad round-up)
    ((33, 129), (8, 64), 0),           # 2-D, both dims non-divisible
    ((21, 45), (6, 17), 1),            # sweep along the lane axis
    ((10, 24, 66), (4, 8, 33), 0),     # 3-D, non-divisible
])
@pytest.mark.parametrize("T", [1, 2, 3])
def test_fused_parity(shape, tile, axis, T):
    d = len(shape)
    u = jax.random.normal(KEY, shape, jnp.float32)
    offs = star_stencil(d, 1)
    w = np.linspace(-0.3, 0.4, len(offs)).tolist()
    out = stencil_iterate(u, offs, w, T, tile=tile, sweep_axis=axis)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(iterate_ref(u, offs, w, T)),
        atol=2e-5, rtol=2e-5,
    )


@pytest.mark.parametrize("T", [2, 3])
@pytest.mark.parametrize("pipelined", [True, False])
def test_fused_asymmetric_halo(T, pipelined):
    """conv1d-style halo (3, 0) on the sweep axis, (0, 1) cross — the
    trapezoid must grow per-side, not per-radius."""
    offs = np.array([[-3, 0], [-2, 0], [-1, 0], [0, 0], [0, 1]])
    w = [0.1, 0.2, 0.3, -0.2, 0.25]
    u = jax.random.normal(KEY, (50, 40), jnp.float32)
    spec = (tuple(map(tuple, offs.tolist())), tuple(w))
    out = _stencil_call((u,), (spec,), (8, 16), 0, pipelined, True,
                        stages_w=(spec,) * T)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(iterate_ref(u, offs, w, T)), atol=2e-5)


def test_fused_radius2_star_3d():
    """The paper's 13-point star, T=3, grid not divisible by the tile."""
    offs = star_stencil(3, 2)
    w = np.linspace(-0.1, 0.12, len(offs)).tolist()
    u = jax.random.normal(KEY, (14, 22, 70), jnp.float32)
    out = stencil_iterate(u, offs, w, 3, tile=(4, 8, 35), sweep_axis=0)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(iterate_ref(u, offs, w, 3)),
        atol=2e-5, rtol=2e-5,
    )


def test_fused_chunked_launches(planner):
    """A plan whose fused_depth < time_steps runs ceil(T/depth) launches
    and still matches the iterated oracle."""
    offs = star_stencil(2, 1)
    w = [0.15, 0.2, -0.25, 0.3, 0.1]
    u = jax.random.normal(KEY, (48, 64), jnp.float32)
    plan = planner.plan(shape=(48, 64), offsets=offs, vmem_budget=64 * 1024,
                        aligned=False, time_steps=5)
    out = stencil_iterate(u, offs, w, 5, plan=plan)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(iterate_ref(u, offs, w, 5)),
        atol=2e-5, rtol=2e-5,
    )


def test_time_steps_spelling_equals_program():
    offs = star_stencil(2, 1)
    w = [0.1, 0.2, 0.3, 0.4, -0.5]
    u = jax.random.normal(KEY, (30, 40), jnp.float32)
    a = stencil_iterate(u, offs, w, 2, tile=(8, 16), sweep_axis=0)
    b = run(ir.stencil_program(offs, w, 2, d=2), u, tile=(8, 16),
            sweep_axis=0)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_fusion_rejects_multi_rhs():
    u = jax.random.normal(KEY, (16, 16), jnp.float32)
    offs = star_stencil(2, 1)
    w = [0.1] * len(offs)
    # A second application of a multi-RHS sum has no launch form.
    rhs = ir.rhs_program([offs, offs], [w, w], d=2)
    twice = ir.Program(d=2, ops=rhs.ops[:-1] + (
        ir.Apply(result="v", operand="q",
                 offsets=tuple(map(tuple, offs.tolist())), weights=tuple(w)),
        ir.Store(operand="v"),
    ))
    with pytest.raises(ValueError, match="multi-RHS"):
        run(twice, [u, u], tile=(8, 8))
    with pytest.raises(ValueError, match="time_steps"):
        stencil_iterate(u, offs, w, 0, tile=(8, 8))


# ---------------------------------------------------------------------------
# T-aware traffic / VMEM model.
# ---------------------------------------------------------------------------

def test_fused_halo_scaling():
    assert fused_halo([(1, 2), (0, 3)], 3) == [(3, 6), (0, 9)]


def test_fused_traffic_exact():
    shape, tile, halo = (256, 256), (16, 64), [(2, 2), (2, 2)]
    t3 = tile_traffic_bytes(shape, tile, halo, 4, sweep_axis=0, time_steps=3)
    # sweep halo and cross halo both grow 3x; one pass pays for 3 steps
    ncols = 256 // 64
    assert t3 == ncols * (256 + 12) * (64 + 12) * 4
    # fusing 3 steps beats 3 single passes whenever halo << tile
    t1 = tile_traffic_bytes(shape, tile, halo, 4, sweep_axis=0)
    assert t3 < 3 * t1


def test_fused_vmem_accounting_split():
    """Per-operand footprint carries only the T-grown window; the staged
    trapezoid buffers are one shared set per launch (fused_stage_bytes) —
    folding them into the operand share would reserve them n_operands
    times and decline fusion at budgets where it actually fits."""
    tile, halo = (4, 32), [(2, 2), (2, 2)]
    base = tile_vmem_bytes(tile, halo, 4, sweep_axis=0, prefetch=False,
                           time_steps=1)
    t2 = tile_vmem_bytes(tile, halo, 4, sweep_axis=0, prefetch=False,
                         time_steps=2)
    window2 = (4 + 8) * (32 + 8)   # T=2: window halo doubles
    stage1 = (4 + 4) * (32 + 4)    # one stage of tile + 1*halo
    assert t2 == window2 * 4
    assert t2 > base
    assert fused_stage_bytes(tile, halo, 4, 2) == stage1 * 4
    assert fused_stage_bytes(tile, halo, 4, 1) == 0
    # T=3: stages narrow by one halo each
    assert fused_stage_bytes(tile, halo, 4, 3) == (
        ((4 + 8) * (32 + 8)) + ((4 + 4) * (32 + 4))
    ) * 4


def test_select_tile_fused_never_beats_lower_bound():
    c = select_tile((128, 128, 128), [(2, 2)] * 3, 4, vmem_budget=1 << 20,
                    aligned=False, time_steps=3)
    assert 0 < c.efficiency <= 1.0
    assert c.traffic_bytes >= c.lower_bound_bytes


# ---------------------------------------------------------------------------
# Planner fused-depth selection.
# ---------------------------------------------------------------------------

def test_planner_fuses_at_vmem_scale(planner):
    """The acceptance-criteria case: T=3 Jacobi, 13-pt star, 256³ — the
    fused plan must cut modeled traffic >= 1.5x vs its own single-pass
    choice."""
    plan = planner.plan(shape=(256, 256, 256), offsets=star_stencil(3, 2),
                        vmem_budget=16 << 20, aligned=True, time_steps=3)
    assert plan.time_steps == 3
    assert plan.fused_depth == 3
    assert plan.traffic_bytes <= plan.single_pass_traffic_bytes
    assert plan.single_pass_traffic_bytes / plan.traffic_bytes >= 1.5
    assert plan.traffic_vs_single_pass <= 1.0


@pytest.mark.parametrize("shape,budget,aligned,T", [
    ((256, 256, 256), 16 * 1024, False, 3),   # cache regime: fusion loses
    ((256, 256, 256), 16 << 20, True, 2),
    ((64, 128, 512), 16 << 20, True, 4),
    ((100, 100, 100), 1 << 20, False, 3),
])
def test_fused_never_worse_than_single_pass(planner, shape, budget, aligned, T):
    plan = planner.plan(shape=shape, offsets=star_stencil(3, 2),
                        vmem_budget=budget, aligned=aligned, time_steps=T)
    assert plan.traffic_bytes <= plan.single_pass_traffic_bytes
    assert plan.traffic_bytes <= plan.legacy_traffic_bytes
    assert 1 <= plan.fused_depth <= T


def test_cache_regime_declines_to_fuse(planner):
    """In the paper's 16 KiB cache-fitting regime the T=3 chain of the
    13-point star at 256³ does not pay: the planner declines to fuse, and
    so the plan neither cuts traffic nor has recompute to stream away."""
    plan = planner.plan(shape=(256, 256, 256), offsets=star_stencil(3, 2),
                        vmem_budget=16 * 1024, aligned=False, time_steps=3)
    assert plan.fused_depth == 1
    assert plan.single_pass_traffic_bytes / plan.traffic_bytes == 1.0
    assert plan.recompute_flops / plan.modeled_flops == 1.0


def test_plan_traffic_prices_executed_chain(planner):
    """The remainder launch reuses the plan's one tile, so the frozen
    traffic must equal the executed chain's model — not the cheaper figure
    a standalone rem-deep plan (with its own tile) would report."""
    from repro.core.tiling import halo_from_offsets, tile_traffic_bytes

    offs = star_stencil(2, 2)
    halo = halo_from_offsets([offs], 2)
    for budget in (6144, 16384, 32768):
        plan = planner.plan(shape=(96, 128), offsets=offs,
                            vmem_budget=budget, aligned=False, time_steps=5)
        executed, rem = 0, plan.request.time_steps
        while rem > 0:
            step = min(plan.fused_depth, rem)
            executed += tile_traffic_bytes(
                plan.pad.padded_shape, plan.tile, halo, 4, plan.sweep_axis,
                step)
            rem -= step
        assert plan.traffic_bytes == executed
        assert plan.traffic_bytes <= plan.single_pass_traffic_bytes


def test_fused_plan_roundtrip(planner):
    plan = planner.plan(shape=(64, 64, 64), offsets=star_stencil(3, 2),
                        vmem_budget=16 << 20, aligned=True, time_steps=3)
    from repro.plan import StencilPlan

    again = StencilPlan.from_json(plan.to_json())
    assert again == plan
    assert again.fused_depth == plan.fused_depth
    assert again.request.time_steps == 3


def test_time_steps_changes_cache_key():
    from repro.plan import PlanRequest

    offs = star_stencil(3, 2)
    k1 = PlanRequest.make(shape=(64, 64, 64), offsets=offs).cache_key()
    k3 = PlanRequest.make(shape=(64, 64, 64), offsets=offs,
                          time_steps=3).cache_key()
    assert k1 != k3


def test_request_rejects_multi_rhs_fusion():
    from repro.plan import PlanRequest

    o1, o2 = star_stencil(2, 1), star_stencil(2, 2)
    with pytest.raises(ValueError, match="single RHS"):
        PlanRequest.make(shape=(64, 64), offsets=[o1, o2], time_steps=2)


# ---------------------------------------------------------------------------
# Bugfix regressions: plan validation + backend fallback.
# ---------------------------------------------------------------------------

def test_plan_mismatch_shape(planner):
    offs = star_stencil(2, 1)
    w = [0.1] * len(offs)
    plan = planner.plan(shape=(32, 64), offsets=offs)
    u = jax.random.normal(KEY, (16, 64), jnp.float32)
    with pytest.raises(PlanMismatchError, match="shape"):
        stencil_iterate(u, offs, w, 1, plan=plan)


def test_plan_mismatch_offsets(planner):
    offs = star_stencil(2, 1)
    w = [0.1] * len(offs)
    plan = planner.plan(shape=(32, 64), offsets=offs)
    u = jax.random.normal(KEY, (32, 64), jnp.float32)
    other = star_stencil(2, 2)
    with pytest.raises(PlanMismatchError, match="offsets"):
        stencil_iterate(u, other, [0.1] * len(other), 1, plan=plan)


def test_plan_mismatch_dtype(planner):
    offs = star_stencil(2, 1)
    w = [0.1] * len(offs)
    plan = planner.plan(shape=(32, 64), offsets=offs, dtype_bytes=4)
    u = jax.random.normal(KEY, (32, 64), jnp.float32).astype(jnp.bfloat16)
    with pytest.raises(PlanMismatchError, match="dtype_bytes"):
        stencil_iterate(u, offs, w, 1, plan=plan)


def test_plan_mismatch_time_steps(planner):
    offs = star_stencil(2, 1)
    w = [0.1] * len(offs)
    plan = planner.plan(shape=(32, 64), offsets=offs, time_steps=3)
    u = jax.random.normal(KEY, (32, 64), jnp.float32)
    with pytest.raises(PlanMismatchError, match="time_steps"):
        stencil_iterate(u, offs, w, 2, plan=plan)


def test_matching_plan_accepted(planner):
    offs = star_stencil(2, 1)
    w = [0.2, 0.1, -0.1, 0.3, 0.15]
    plan = planner.plan(shape=(32, 64), offsets=offs, vmem_budget=128 * 1024)
    u = jax.random.normal(KEY, (32, 64), jnp.float32)
    out = stencil_iterate(u, offs, w, 1, plan=plan)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(stencil_ref(u, offs, w)), atol=1e-5)


def test_unsupported_backend_falls_back_to_interpret(monkeypatch):
    """A non-TPU, non-CPU backend no longer falls back to interpret mode:
    resolution refuses it, for the default and for explicit values
    alike.  The CPU keeps the interpreter (the test path) and honors
    explicit values."""
    from repro.kernels import _backend

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    for interpret in (None, True, False):
        with pytest.raises(_backend.UnsupportedBackendError, match="gpu"):
            _backend.resolve_interpret(interpret)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert _backend.resolve_interpret(None) is True
    assert _backend.resolve_interpret(False) is False
    assert _backend.resolve_interpret(True) is True


def test_unsupported_backend_kernel_end_to_end(monkeypatch):
    """The full kernel path on a 'gpu' backend raises the backend error
    before any launch, naming the kernel."""
    from repro.kernels import _backend

    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    offs = star_stencil(2, 1)
    w = [0.1, 0.2, 0.3, 0.4, -0.5]
    u = jax.random.normal(KEY, (24, 32), jnp.float32)
    with pytest.raises(_backend.UnsupportedBackendError, match="stencil"):
        stencil_iterate(u, offs, w, 1, tile=(8, 16), sweep_axis=0)


def test_conv1d_backend_fallback(monkeypatch):
    """conv1d shares the resolution: a 'rocm' backend is refused too."""
    from repro.kernels import _backend
    from repro.kernels.conv1d import causal_conv1d

    monkeypatch.setattr(jax, "default_backend", lambda: "rocm")
    x = jax.random.normal(KEY, (2, 32, 8), jnp.float32)
    cw = jax.random.normal(jax.random.PRNGKey(1), (4, 8), jnp.float32) * 0.3
    cb = jnp.zeros((8,), jnp.float32)
    with pytest.raises(_backend.UnsupportedBackendError, match="conv1d"):
        causal_conv1d(x, cw, cb, tile_s=16)
