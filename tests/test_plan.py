"""Plan compiler: pipeline correctness, legacy dominance, kernel wiring."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cache_fitting import star_stencil
from repro.core.padding import is_unfavorable
from repro.plan import (
    PadPlan,
    PlanCache,
    PlanRequest,
    Planner,
    StencilPlan,
    plan_stencil,
)

GEOM = (2, 512, 4)
S = GEOM[0] * GEOM[1] * GEOM[2]


@pytest.fixture
def planner():
    return Planner(cache=PlanCache(persistent=False))


def _plan(planner, shape, **kw):
    kw.setdefault("offsets", star_stencil(len(shape), 2))
    return planner.plan(shape=shape, **kw)


def test_plan_basics(planner):
    plan = _plan(planner, (64, 128, 512))
    assert len(plan.tile) == 3
    assert all(t >= 1 for t in plan.tile)
    assert plan.grid == tuple(-(-n // t) for n, t in zip((64, 128, 512), plan.tile))
    assert 0.0 < plan.efficiency <= 1.0
    assert plan.lower_bound_bytes <= plan.traffic_bytes
    # No geometry -> explicit-memory no-op pad, with the reason recorded.
    assert not plan.pad.nonzero
    assert "explicit-memory" in plan.pad.reason


@pytest.mark.parametrize(
    "shape,budget,aligned",
    [
        ((256, 256, 256), 16 * 1024, False),
        ((256, 256, 256), 16 << 20, True),
        ((100, 100, 100), 16 * 1024, False),
        ((45, 91, 64), 1 << 20, False),
        ((64, 128, 512), 16 << 20, True),
    ],
)
def test_planner_never_worse_than_legacy(planner, shape, budget, aligned):
    """The satellite gate: the planner's candidate set is a strict superset
    of the legacy heuristic's under the same traffic model."""
    plan = _plan(planner, shape, vmem_budget=budget, aligned=aligned)
    legacy = Planner(strategy="legacy", cache=PlanCache(persistent=False)).plan(
        shape=shape, offsets=star_stencil(3, 2), vmem_budget=budget,
        aligned=aligned,
    )
    assert plan.traffic_bytes <= legacy.traffic_bytes
    assert plan.legacy_traffic_bytes == legacy.traffic_bytes
    assert plan.traffic_vs_legacy <= 1.0


def test_unfavorable_grid_gets_favorable_pad(planner):
    """Acceptance: Fig. 5 grids (n1*n2 ~ k*S/2) get a nonzero PadPlan whose
    padded grid is favorable."""
    for dims in [(45, 91, 24), (90, 91, 24)]:
        plan = _plan(planner, dims, geometry=GEOM, vmem_budget=S * 4,
                     aligned=False)
        assert plan.lattice is not None and plan.lattice.unfavorable
        assert plan.pad.nonzero
        assert plan.pad.shortest_after >= plan.pad.threshold
        assert not is_unfavorable(plan.pad.padded_shape, S, diameter=5)
        assert plan.pad.padded_shape[-1] == dims[-1]  # last dim never padded


def test_favorable_grid_zero_pad(planner):
    plan = _plan(planner, (64, 91, 60), geometry=GEOM, aligned=False)
    assert plan.lattice is not None and not plan.lattice.unfavorable
    assert not plan.pad.nonzero
    assert plan.pad.padded_shape == (64, 91, 60)


def test_plan_roundtrip_json(planner):
    plan = _plan(planner, (45, 91, 24), geometry=GEOM, aligned=False)
    assert StencilPlan.from_json(plan.to_json()) == plan
    plan2 = _plan(planner, (64, 128, 512))
    assert StencilPlan.from_dict(plan2.to_dict()) == plan2


def test_request_canonicalization():
    offs = star_stencil(3, 2)
    r1 = PlanRequest.make(shape=(64, 64, 64), offsets=offs)
    r2 = PlanRequest.make(shape=[64, 64, 64], offsets=[offs])  # listy forms
    r3 = PlanRequest.make(shape=(64, 64, 64),
                          offsets=[[tuple(o) for o in offs]])
    assert r1 == r2 == r3
    assert r1.cache_key() == r3.cache_key()
    # different inputs -> different keys
    assert r1.cache_key() != PlanRequest.make(
        shape=(64, 64, 65), offsets=offs).cache_key()


def test_multi_rhs_request():
    o1 = star_stencil(2, 1)
    o2 = np.array([[0, 0], [1, 0], [0, 1]])
    r = PlanRequest.make(shape=(64, 128), offsets=[o1, o2])
    assert len(r.offsets) == 2
    assert r.n_operands == 3  # 2 inputs + output


def test_validate_reports_miss_reduction(planner):
    plan = _plan(planner, (45, 91, 24), geometry=GEOM, vmem_budget=S * 4,
                 aligned=False)
    v = planner.validate(plan)
    assert v["validated"]
    assert v["miss_reduction_x"] > 1.5  # the §6 remedy pays off


def test_kernel_accepts_plan(planner):
    """stencil_iterate(plan=...) drives the sweep engine with the planned
    tile and matches the oracle."""
    from repro.entry import stencil_iterate
    from repro.kernels.ref import star_weights_2nd_order, stencil_ref

    offs, w = star_weights_2nd_order(3, 2)
    plan = planner.plan(shape=(16, 24, 128), offsets=offs,
                        vmem_budget=256 * 1024)
    u = jax.random.normal(jax.random.PRNGKey(0), (16, 24, 128), jnp.float32)
    out = stencil_iterate(u, offs, w, 1, plan=plan, interpret=True)
    ref = stencil_ref(u, offs, w)
    assert float(jnp.abs(out - ref).max()) < 1e-4


def test_conv1d_planned_tile_matches_fixed():
    from repro.kernels.conv1d import causal_conv1d

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, 64), jnp.float32)
    cw = jax.random.normal(jax.random.PRNGKey(2), (4, 64), jnp.float32) * 0.1
    cb = jnp.zeros((64,), jnp.float32)
    planned = causal_conv1d(x, cw, cb)  # tile_s=None -> plan compiler
    fixed = causal_conv1d(x, cw, cb, tile_s=16)
    assert float(jnp.abs(planned - fixed).max()) < 1e-5


def test_plan_stencil_convenience():
    plan = plan_stencil((32, 64, 256), star_stencil(3, 1))
    assert isinstance(plan, StencilPlan)
    assert plan.request.shape == (32, 64, 256)


def test_padplan_zero_helper():
    p = PadPlan.zero((10, 20), reason="x")
    assert not p.nonzero and p.padded_shape == (10, 20) and p.extra_words == 0
