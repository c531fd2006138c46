"""§10 column sharding: the sharded launch must be *bit-wise* equal to
the single-device engine at the same geometry — sharding is an execution
knob, never a numerics knob.  Covers 2- and 4-shard CPU meshes,
non-divisible column counts, stage chains T ∈ {1, 3}, the planner-driven
path, and the shard-axis/mesh validation errors."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cache_fitting import star_stencil
from repro.entry import stencil_iterate
from repro.kernels.ref import stencil_ref
from repro.launch.mesh import make_column_mesh
from repro.parallel.shard_columns import pick_shard_axis
from repro.plan import PlanCache, Planner

N_DEV = len(jax.devices())

needs = lambda n: pytest.mark.skipif(
    N_DEV < n, reason=f"needs {n} devices (XLA_FLAGS forces 4 on CPU)"
)

OFFS = star_stencil(3, 1)
WEIGHTS = [0.05 * (i + 1) for i in range(len(OFFS))]


def _u(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


@needs(2)
@pytest.mark.parametrize("num_shards", [2, 4])
@pytest.mark.parametrize(
    "shape,tile",
    [
        ((16, 24, 130), (4, 8, 64)),   # 3 columns on axis 1: non-divisible
        ((12, 32, 130), (4, 8, 128)),  # 4 columns on axis 1: divisible by 2
    ],
)
def test_sharded_bitwise_parity_t1(shape, tile, num_shards):
    if N_DEV < num_shards:
        pytest.skip(f"needs {num_shards} devices")
    u = _u(shape)
    base = stencil_iterate(u, OFFS, WEIGHTS, 1, tile=tile, sweep_axis=0)
    sh = stencil_iterate(
        u, OFFS, WEIGHTS, 1, tile=tile, sweep_axis=0, num_shards=num_shards,
    )
    assert bool(jnp.all(sh == base))


@needs(2)
@pytest.mark.parametrize("time_steps", [1, 3])
def test_sharded_bitwise_parity_stage_chain(time_steps):
    """Fused stage chains shard exactly like single applications: the
    frontier rings are per-column state and the intermediate masks are
    lifted into global coordinates by the shard's domain offset."""
    u = _u((16, 24, 130), seed=1)
    tile = (4, 8, 64)
    base = stencil_iterate(
        u, OFFS, WEIGHTS, time_steps=time_steps, tile=tile, sweep_axis=0,
    )
    sh = stencil_iterate(
        u, OFFS, WEIGHTS, time_steps=time_steps, tile=tile, sweep_axis=0,
        num_shards=2,
    )
    assert bool(jnp.all(sh == base))
    # ... and the chain still matches the iterated zero-fill oracle.
    r = u
    for _ in range(time_steps):
        r = stencil_ref(r, OFFS, WEIGHTS)
    assert float(jnp.abs(sh - r).max()) < 1e-4


@needs(2)
def test_sharded_heterogeneous_stage_chain():
    """Distinct per-stage operators (r=1 star then asymmetric shift):
    per-launch cones differ and the exchange must carry the chain cone."""
    u = _u((16, 24, 130), seed=2)
    shift = np.array([[0, 0, 0], [1, 0, 0], [0, 2, 0]])
    stages = [(OFFS, WEIGHTS), (shift, [0.5, 0.25, 0.25])]
    tile = (4, 8, 64)
    base = stencil_iterate(u, stages=stages, tile=tile, sweep_axis=0)
    sh = stencil_iterate(
        u, stages=stages, tile=tile, sweep_axis=0, num_shards=2,
    )
    assert bool(jnp.all(sh == base))


@needs(2)
def test_planner_driven_sharded_launch():
    """No explicit tile: the v4 plan (slab tile, shard axis) drives the
    sharded launch; num_shards=1 on the same geometry is the bit-wise
    reference."""
    u = _u((32, 48, 130), seed=3)
    planner = Planner(cache=PlanCache(persistent=False))
    plan = planner.plan(
        shape=u.shape, offsets=OFFS, vmem_budget=1 << 20, num_shards=2,
    )
    assert plan.num_shards == 2 and plan.shard_axis is not None
    sh = stencil_iterate(u, OFFS, WEIGHTS, 1, plan=plan)  # plan carries shards
    base = stencil_iterate(u, OFFS, WEIGHTS, 1, plan=plan, num_shards=1)
    assert bool(jnp.all(sh == base))


@needs(2)
def test_explicit_mesh_matches_num_shards():
    u = _u((16, 24, 130), seed=4)
    tile = (4, 8, 64)
    mesh = make_column_mesh(2)
    a = stencil_iterate(
        u, OFFS, WEIGHTS, 1, tile=tile, sweep_axis=0, mesh=mesh,
    )
    b = stencil_iterate(
        u, OFFS, WEIGHTS, 1, tile=tile, sweep_axis=0, num_shards=2,
    )
    assert bool(jnp.all(a == b))


@needs(2)
def test_more_shards_than_columns():
    """More shards than tile columns: surplus shards compute trimmed
    slack — wasteful but exact."""
    u = _u((16, 24, 130), seed=5)
    tile = (4, 16, 64)  # 2 columns on axis 1 < 4 shards
    if N_DEV < 4:
        pytest.skip("needs 4 devices")
    base = stencil_iterate(u, OFFS, WEIGHTS, 1, tile=tile, sweep_axis=0)
    sh = stencil_iterate(
        u, OFFS, WEIGHTS, 1, tile=tile, sweep_axis=0, num_shards=4,
        shard_axis=1,
    )
    assert bool(jnp.all(sh == base))


def test_one_shard_is_the_single_device_path():
    """num_shards=1 never touches shard_map (no mesh, no devices needed)."""
    u = _u((16, 24, 130), seed=6)
    tile = (4, 8, 64)
    a = stencil_iterate(u, OFFS, WEIGHTS, 1, tile=tile, sweep_axis=0)
    b = stencil_iterate(
        u, OFFS, WEIGHTS, 1, tile=tile, sweep_axis=0, num_shards=1,
    )
    assert bool(jnp.all(a == b))


@needs(2)
def test_explicit_axis_pin_survives_planner_collision():
    """Pinning shard_axis (or sweep_axis) without a tile must not crash
    when the planner's independent choice of the other axis collides —
    the explicit pin wins and the free axis is re-derived."""
    u = _u((64, 24, 16), seed=8)
    base = stencil_iterate(u, OFFS, WEIGHTS, 1, vmem_budget=1 << 20)
    pinned_shard = stencil_iterate(
        u, OFFS, WEIGHTS, 1, vmem_budget=1 << 20, num_shards=2, shard_axis=1,
    )
    assert bool(jnp.allclose(pinned_shard, base, atol=1e-5))
    pinned_sweep = stencil_iterate(
        u, OFFS, WEIGHTS, 1, vmem_budget=1 << 20, num_shards=2, sweep_axis=0,
    )
    assert bool(jnp.allclose(pinned_sweep, base, atol=1e-5))


def test_unshardable_grid_rejected_upfront():
    """A grid with < 2 non-unit dims has no (shard, sweep) axis pair; the
    request must fail with a clear error, not a budget one."""
    planner = Planner(cache=PlanCache(persistent=False))
    with pytest.raises(ValueError, match="cross axis"):
        planner.plan(
            shape=(1024, 1), offsets=np.array([[-1, 0], [0, 0], [1, 0]]),
            num_shards=2,
        )


def test_pinned_shard_axis_plans_its_own_slab():
    """A caller's shard axis reaches the planner: the tile is planned
    for that axis's slab, not for the axis the planner would split (the
    200-lane axis here, in four 50-lane tiles that the chip's DMA cannot
    address)."""
    planner = Planner(cache=PlanCache(persistent=False))
    kw = dict(shape=(16, 20, 200), offsets=star_stencil(3, 2),
              num_shards=4, aligned=True)
    own = planner.plan(**kw)
    assert own.shard_axis == 2 and own.tile[2] == 50
    pinned = planner.plan_along(0, **kw)
    assert pinned.shard_axis == 0 and pinned.request == own.request
    assert pinned.tile[0] == 4 and pinned.tile[2] == 200
    assert pinned.sweep_axis != 0
    assert planner.plan_along(0, **kw) is pinned
    assert planner.plan_along(2, **kw) is own
    assert planner.plan(**kw) is own  # the cache keeps the planner's own


def test_mesh_axis_name_shares_cache_key():
    """mesh_axis is display-only: requests differing only in the axis
    name must share one plan-cache key."""
    from repro.plan import PlanRequest

    offs = np.array([[-1, 0], [0, 0], [0, 1]])
    a = PlanRequest.make(shape=(64, 64), offsets=offs, num_shards=2)
    b = PlanRequest.make(shape=(64, 64), offsets=offs, num_shards=2,
                         mesh_axis="x")
    assert a.cache_key() == b.cache_key()


def test_shard_axis_validation():
    u = _u((16, 24, 130), seed=7)
    with pytest.raises(ValueError, match="sweep axis"):
        stencil_iterate(
            u, OFFS, WEIGHTS, 1, tile=(4, 8, 64), sweep_axis=1, shard_axis=1,
            num_shards=2,
        )
    with pytest.raises(ValueError, match="out of range"):
        stencil_iterate(
            u, OFFS, WEIGHTS, 1, tile=(4, 8, 64), sweep_axis=0, shard_axis=5,
            num_shards=2,
        )


def test_1d_grid_cannot_shard():
    u = jnp.ones(128)
    offs = np.array([[-1], [0], [1]])
    with pytest.raises(ValueError, match="cross axis"):
        stencil_iterate(u, offs, [1.0, 1.0, 1.0], 1, num_shards=2)


def test_pick_shard_axis_prefers_most_columns():
    assert pick_shard_axis((16, 24, 130), (4, 8, 64), 0) == 1  # 3 vs 3...
    assert pick_shard_axis((16, 64, 130), (4, 8, 64), 0) == 1  # 8 vs 3
    assert pick_shard_axis((16, 8, 512), (4, 8, 64), 0) == 2   # 1 vs 8
    with pytest.raises(ValueError, match="cross axis"):
        pick_shard_axis((128,), (4,), 0)


def test_plan_v4_shard_fields():
    planner = Planner(cache=PlanCache(persistent=False))
    kw = dict(shape=(256, 256, 256), offsets=star_stencil(3, 2),
              vmem_budget=16 << 20, aligned=True)
    base = planner.plan(**kw)
    p4 = planner.plan(**kw, num_shards=4)
    assert base.num_shards == 1 and base.shard_axis is None
    assert base.halo_exchange_bytes == 0
    assert base.per_shard_traffic_bytes == base.traffic_bytes
    assert p4.shard_axis is not None
    sweep_eff = 0 if p4.sweep_axis is None else p4.sweep_axis
    assert p4.shard_axis != sweep_eff
    assert p4.halo_exchange_bytes > 0
    # Per-core traffic must be well under the whole-grid figure.
    assert p4.per_shard_traffic_bytes <= base.traffic_bytes / 2
    # Round trip with the shard fields intact.
    again = type(p4).from_json(p4.to_json())
    assert again == p4


def test_modeled_parallel_efficiency_at_eight_shards():
    """The planner's shard scoring for the 13-point star at 256³: the
    modeled parallel efficiency ``traffic₁ / (S · per_shard_traffic)``
    stays >= 0.85 at 8 shards for one application, per-shard traffic
    falls with every doubling of the shards (T ∈ {1, 3}), and a 1-shard
    request plans exactly as an unsharded one."""
    planner = Planner(cache=PlanCache(persistent=False))
    for time_steps in (1, 3):
        kw = dict(shape=(256, 256, 256), offsets=star_stencil(3, 2),
                  vmem_budget=16 << 20, aligned=True, time_steps=time_steps)
        base = planner.plan(**kw)
        plans = {s: planner.plan(**kw, num_shards=s) for s in (1, 2, 4, 8)}
        assert plans[1].to_dict() == base.to_dict()
        per_shard = [plans[s].per_shard_traffic_bytes for s in (1, 2, 4, 8)]
        assert all(a > b for a, b in zip(per_shard, per_shard[1:]))
        if time_steps == 1:
            eff8 = base.traffic_bytes / (8 * plans[8].per_shard_traffic_bytes)
            assert eff8 >= 0.85, eff8
