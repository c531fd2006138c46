"""Stage-chain programs + streaming frontiers (DESIGN.md §9).

Covers: fused stage-chain parity (bit-wise vs. the engine's own iterated
zero-fill launches, allclose vs. the jnp oracle) for T ∈ {1, 2, 3} with
distinct per-stage weights, asymmetric (W−1, 0) halos and non-divisible
shapes; the per-stage halo models and the streaming-vs-recompute flop
model; schema-v3 canonicalization and validation; and planner depth
scoring over heterogeneous chains.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ir
from repro.core.cache_fitting import star_stencil
from repro.core.tiling import (
    chain_flops,
    chain_halo,
    fused_halo,
    fused_stage_bytes,
    select_tile,
    stage_suffix_halos,
    tile_traffic_bytes,
    tile_vmem_bytes,
)
from repro.entry import run, stencil_iterate
from repro.kernels.ref import stencil_ref
from repro.kernels.stencil import _stencil_call
from repro.plan import (
    PlanCache,
    PlanMismatchError,
    Planner,
    PlanRequest,
    StageSpec,
    StencilPlan,
    validate_plan_call,
)

KEY = jax.random.PRNGKey(0)

# Distinct per-stage operators: conv1d-style asymmetric (W-1, 0) halo,
# an r=1 star, an r=2 star — heterogeneous footprints AND weights.
OFFS_CONV = np.array([[-3, 0], [-2, 0], [-1, 0], [0, 0], [0, 1]])
W_CONV = (0.1, 0.2, 0.3, -0.2, 0.25)
OFFS_S1 = star_stencil(2, 1)
W_S1 = tuple(np.linspace(-0.3, 0.4, len(OFFS_S1)).tolist())
OFFS_S2 = star_stencil(2, 2)
W_S2 = tuple(np.linspace(-0.1, 0.12, len(OFFS_S2)).tolist())
CHAIN3 = [(OFFS_CONV, W_CONV), (OFFS_S1, W_S1), (OFFS_S2, W_S2)]


def chain_ref(u, stages):
    for offs, w in stages:
        u = stencil_ref(u, offs, list(w))
    return u


def engine_iter(u, stages, tile, sweep_axis):
    for offs, w in stages:
        u = stencil_iterate(
            u, offs, list(w), 1, tile=tile, sweep_axis=sweep_axis,
        )
    return u


@pytest.fixture
def planner():
    return Planner(cache=PlanCache(persistent=False))


# ---------------------------------------------------------------------------
# Fused stage-chain parity (the acceptance criterion).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T", [1, 2, 3])
def test_stage_chain_bitwise_vs_engine_iter(T):
    """The fused streaming launch must equal the engine's own stage-by-
    stage zero-fill launches *bit-wise*: frontier ring bookkeeping is pure
    data movement, it may not change a single ulp.  Non-divisible shape,
    asymmetric halo in stage 1, distinct weights per stage."""
    u = jax.random.normal(KEY, (50, 45), jnp.float32)
    stages = CHAIN3[:T]
    fused = stencil_iterate(u, stages=stages, tile=(8, 16), sweep_axis=0)
    iterated = engine_iter(u, stages, (8, 16), 0)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(iterated))


@pytest.mark.parametrize("T", [2, 3])
@pytest.mark.parametrize("shape,tile,axis", [
    ((50, 45), (8, 16), 0),      # non-divisible both dims
    ((21, 45), (6, 17), 1),      # sweep along the lane axis
    ((33, 40), (8, 40), 0),      # single cross tile
])
def test_stage_chain_matches_oracle(T, shape, tile, axis):
    u = jax.random.normal(KEY, shape, jnp.float32)
    stages = CHAIN3[:T]
    fused = stencil_iterate(u, stages=stages, tile=tile, sweep_axis=axis)
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(chain_ref(u, stages)),
        atol=2e-5, rtol=2e-5,
    )


def test_stage_chain_3d_distinct_radii():
    u = jax.random.normal(KEY, (14, 22, 70), jnp.float32)
    stages = [
        (star_stencil(3, 1), tuple(np.linspace(0.05, 0.2, 7).tolist())),
        (star_stencil(3, 2), tuple(np.linspace(-0.1, 0.12, 13).tolist())),
    ]
    fused = stencil_iterate(u, stages=stages, tile=(4, 8, 35), sweep_axis=0)
    iterated = engine_iter(u, stages, (4, 8, 35), 0)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(iterated))
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(chain_ref(u, stages)),
        atol=2e-5, rtol=2e-5,
    )


def test_homogeneous_spellings_agree():
    """stencil_iterate(offsets, weights, T) and stages=[op]*T are the same
    program and must produce the same bits."""
    u = jax.random.normal(KEY, (30, 40), jnp.float32)
    a = stencil_iterate(u, OFFS_S1, list(W_S1), 3, tile=(8, 16), sweep_axis=0)
    b = stencil_iterate(
        u, stages=[(OFFS_S1, W_S1)] * 3, tile=(8, 16), sweep_axis=0
    )
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("pipelined", [True, False])
def test_stage_chain_pipelining_invariant(pipelined):
    """The fused chain's launch, with and without the pipelined slab
    fetch (the plan's choice; here the launcher's own argument)."""
    u = jax.random.normal(KEY, (40, 33), jnp.float32)
    specs = tuple(
        (tuple(map(tuple, np.asarray(o).tolist())), tuple(map(float, w)))
        for o, w in CHAIN3
    )
    out = _stencil_call((u,), specs[:1], (8, 16), 0, pipelined, True,
                        stages_w=specs)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(chain_ref(u, CHAIN3)), atol=2e-5)


def test_stage_chain_planned_chunked_launches(planner):
    """A heterogeneous chain whose plan fuses shallower than T must run
    ceil(T/depth) launches over the right stage runs and still match."""
    stages = [(OFFS_S1, W_S1), (OFFS_S1, W_S1), (OFFS_S2, W_S2),
              (OFFS_S1, W_S1), (OFFS_S2, W_S2)]
    u = jax.random.normal(KEY, (48, 64), jnp.float32)
    plan = planner.plan(
        shape=(48, 64), stages=[o for o, _ in stages],
        vmem_budget=64 * 1024, aligned=False,
    )
    assert plan.time_steps == 5
    out = stencil_iterate(u, stages=stages, plan=plan)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(chain_ref(u, stages)),
        atol=2e-5, rtol=2e-5,
    )


def test_stage_api_validation():
    u = jax.random.normal(KEY, (16, 16), jnp.float32)
    with pytest.raises(ValueError, match="not both"):
        stencil_iterate(u, OFFS_S1, list(W_S1), stages=CHAIN3)
    with pytest.raises(ValueError, match="contradicts"):
        stencil_iterate(u, stages=CHAIN3, time_steps=2)
    with pytest.raises(ValueError, match="needs"):
        stencil_iterate(u)
    with pytest.raises(ValueError, match="program inputs"):
        run(ir.chain_program(CHAIN3, 2), [u, u], tile=(8, 8))
    with pytest.raises(ValueError, match="at least one"):
        stencil_iterate(u, stages=[], tile=(8, 8))
    with pytest.raises(ValueError, match="offsets but"):
        stencil_iterate(u, stages=[(OFFS_S1, (0.1, 0.2))], tile=(8, 8))


# ---------------------------------------------------------------------------
# Per-stage halo + flop models.
# ---------------------------------------------------------------------------

def test_chain_halo_sums_and_matches_fused():
    h1 = [(1, 0), (0, 2)]
    h2 = [(2, 1), (1, 0)]
    assert chain_halo([h1, h2]) == [(3, 1), (1, 2)]
    h = [(1, 2), (0, 3)]
    assert chain_halo([h] * 3) == fused_halo(h, 3)


def test_stage_suffix_halos():
    h1, h2, h3 = [(1, 1)], [(2, 0)], [(0, 3)]
    sfx = stage_suffix_halos([h1, h2, h3])
    assert sfx[0] == [(2, 3)]   # stages 2+3 still reach past stage 1
    assert sfx[1] == [(0, 3)]
    assert sfx[2] == [(0, 0)]   # final stage computes the bare tile


def test_stage_models_match_homogeneous():
    """For a repeated chain the stage_halos spelling must price exactly
    like the time_steps spelling — traffic, VMEM, and staged bytes."""
    shape, tile, halo = (256, 256), (16, 64), [(2, 2), (2, 2)]
    launch = [halo] * 3
    assert tile_traffic_bytes(shape, tile, halo, 4, 0, stage_halos=launch) \
        == tile_traffic_bytes(shape, tile, halo, 4, 0, time_steps=3)
    assert tile_vmem_bytes(tile, halo, 4, 0, True, stage_halos=launch) \
        == tile_vmem_bytes(tile, halo, 4, 0, True, time_steps=3)
    assert fused_stage_bytes(tile, halo, 4, 3, stage_halos=launch) \
        == fused_stage_bytes(tile, halo, 4, 3)
    c1 = select_tile(shape, halo, 4, vmem_budget=1 << 20, aligned=False,
                     time_steps=3)
    c2 = select_tile(shape, halo, 4, vmem_budget=1 << 20, aligned=False,
                     stage_halos=launch)
    assert c1 == c2


def test_chain_flops_streaming_below_recompute():
    shape, tile = (128, 128), (4, 64)
    launch = [[(2, 2), (2, 2)]] * 3
    pts = [13, 13, 13]
    stream = chain_flops(shape, tile, pts, launch, 0, streaming=True)
    recomp = chain_flops(shape, tile, pts, launch, 0, streaming=False)
    assert stream < recomp
    # no sweep axis -> nothing to stream, the two coincide
    assert chain_flops(shape, tile, pts, launch, None, True) \
        == chain_flops(shape, tile, pts, launch, None, False)


def test_chain_flops_exact_single_stage():
    """One stage: every output point costs 2*s flops, no overlap anywhere,
    streaming == recompute == 2*s*padded points."""
    shape, tile = (64, 64), (8, 32)
    fl = chain_flops(shape, tile, [5], [[(1, 1), (1, 1)]], 0, True)
    assert fl == 2 * 5 * 64 * 64
    assert fl == chain_flops(shape, tile, [5], [[(1, 1), (1, 1)]], 0, False)


def test_streaming_flops_model_matches_kernel_work():
    """The streaming model counts the §9 kernel's actual work: one full
    trapezoid per column (warm-up) plus t_s rows per stage per later
    step."""
    shape, tile = (64, 32), (8, 32)
    halo = [(2, 2), (0, 0)]
    launch = [halo, halo]
    s = 5
    nswp = 64 // 8
    # stage 1: ext (8+4, 32); stage 2 (final): ext (8, 32)
    warm = 12 * 32 + 8 * 32
    later = (nswp - 1) * (8 * 32 + 8 * 32)
    assert chain_flops(shape, tile, [s, s], launch, 0, True) \
        == 2 * s * (warm + later)


# ---------------------------------------------------------------------------
# Schema v3: canonicalization, keys, validation, round-trip.
# ---------------------------------------------------------------------------

def test_homogeneous_cache_key_stable_across_spellings():
    offs = star_stencil(3, 2)
    k1 = PlanRequest.make(shape=(64, 64, 64), offsets=offs,
                          time_steps=3).cache_key()
    k2 = PlanRequest.make(shape=(64, 64, 64),
                          stages=[offs, offs, offs]).cache_key()
    assert k1 == k2


def test_stage_weights_do_not_leak_into_kernel_driven_keys():
    """The kernel strips weights before planning, so two chains that
    differ only in weights share one plan-cache entry."""
    req = PlanRequest.make(shape=(32, 32), stages=[OFFS_S1, OFFS_S1])
    assert all(st.weights is None for st in req.stages)
    # ... while an explicit weighted request is still representable.
    wreq = PlanRequest.make(
        shape=(32, 32), stages=[(OFFS_S1, W_S1), (OFFS_S1, W_S1)])
    assert wreq.stages[0].weights == tuple(float(w) for w in W_S1)


def test_stage_spec_make_forms():
    d = 2
    a = StageSpec.make(OFFS_S1, d)
    b = StageSpec.make((OFFS_S1, W_S1), d)
    c = StageSpec.make({"offsets": OFFS_S1, "weights": W_S1}, d)
    assert a.offsets == b.offsets == c.offsets
    assert a.weights is None and b.weights == c.weights
    assert StageSpec.make(b, d) == b


def test_multi_rhs_has_empty_stage_chain():
    req = PlanRequest.make(shape=(32, 32), offsets=[OFFS_S1, OFFS_S2])
    assert req.stages == ()
    with pytest.raises(ValueError, match="single RHS"):
        PlanRequest.make(shape=(32, 32), offsets=[OFFS_S1, OFFS_S2],
                         time_steps=2)


def test_request_rejects_offsets_and_stages():
    with pytest.raises(ValueError, match="not both"):
        PlanRequest.make(shape=(32, 32), offsets=OFFS_S1, stages=[OFFS_S1])


def test_heterogeneous_plan_roundtrip(planner):
    plan = planner.plan(shape=(64, 64), stages=[OFFS_S1, OFFS_S2],
                        vmem_budget=1 << 20, aligned=False)
    again = StencilPlan.from_json(plan.to_json())
    assert again == plan
    assert len(again.request.stages) == 2
    assert again.depth_scores == plan.depth_scores
    assert again.modeled_flops == plan.modeled_flops


def test_v2_shaped_plan_dict_still_parses(planner):
    """A v2-era dict (no stages, no flop fields) must parse — the derived
    repeated chain keeps old serialized plans loadable even though their
    cache keys are stale."""
    plan = planner.plan(shape=(32, 64), offsets=OFFS_S1, time_steps=2)
    d = plan.to_dict()
    d["version"] = 2
    d["request"].pop("stages")
    for f in ("modeled_flops", "recompute_flops", "depth_scores"):
        d.pop(f)
    old = StencilPlan.from_dict(d)
    assert len(old.request.stages) == 2
    assert old.request.stages[0].offsets == plan.request.stages[0].offsets


def test_validate_rejects_stage_mismatch(planner):
    plan = planner.plan(shape=(32, 64), stages=[OFFS_S1, OFFS_S2])
    u = jax.random.normal(KEY, (32, 64), jnp.float32)
    with pytest.raises(PlanMismatchError, match="stages"):
        stencil_iterate(u, stages=[(OFFS_S2, W_S2), (OFFS_S1, W_S1)],
                        plan=plan)
    # the matching chain is accepted
    out = stencil_iterate(u, stages=[(OFFS_S1, W_S1), (OFFS_S2, W_S2)],
                          plan=plan)
    assert out.shape == u.shape


def test_validate_stage_weights_not_checked(planner):
    """Weights scale values, never geometry: a plan compiled without them
    serves any weighting of the same offsets."""
    plan = planner.plan(shape=(32, 64), stages=[OFFS_S1, OFFS_S1])
    validate_plan_call(
        plan, (32, 64), [OFFS_S1], 4, time_steps=2,
        stages=[(OFFS_S1, W_S1), (OFFS_S1, tuple(w * 2 for w in W_S1))],
    )


# ---------------------------------------------------------------------------
# Planner depth scoring.
# ---------------------------------------------------------------------------

def test_acceptance_flop_reduction_t3_256(planner):
    """The PR acceptance gate: at T=3, 256³, VMEM scale, the streaming
    path models >= 1.5x fewer flops than the recompute path at equal
    modeled traffic (the traffic model is untouched by streaming)."""
    plan = planner.plan(shape=(256, 256, 256), offsets=star_stencil(3, 2),
                        vmem_budget=16 << 20, aligned=True, time_steps=3)
    assert plan.fused_depth == 3
    assert plan.recompute_flops >= 1.5 * plan.modeled_flops
    assert plan.flops_vs_recompute <= 1 / 1.5
    # the whole-chain traffic gates of PR3 are unchanged
    assert plan.single_pass_traffic_bytes / plan.traffic_bytes >= 1.5


def test_depth_scores_table(planner):
    plan = planner.plan(shape=(256, 256, 256), offsets=star_stencil(3, 2),
                        vmem_budget=16 << 20, aligned=True, time_steps=3)
    depths = [row[0] for row in plan.depth_scores]
    assert depths == sorted(depths) and plan.fused_depth in depths
    chosen = next(r for r in plan.depth_scores if r[0] == plan.fused_depth)
    assert chosen[1] == plan.traffic_bytes
    assert chosen[2] == plan.modeled_flops
    # the chosen depth minimizes chain traffic over the table
    assert all(chosen[1] <= r[1] for r in plan.depth_scores)


@pytest.mark.parametrize("stage_sets", [
    [3, 1, 2],          # big halo in the middle of nowhere
    [1, 2],
    [2, 2, 1, 1],
])
def test_heterogeneous_never_worse(planner, stage_sets):
    stages = [star_stencil(3, r) for r in stage_sets]
    for budget, aligned in [(16 * 1024, False), (16 << 20, True)]:
        plan = planner.plan(shape=(64, 64, 64), stages=stages,
                            vmem_budget=budget, aligned=aligned)
        assert plan.traffic_bytes <= plan.single_pass_traffic_bytes
        assert plan.traffic_bytes <= plan.legacy_traffic_bytes
        assert plan.modeled_flops <= plan.recompute_flops
        assert 1 <= plan.fused_depth <= len(stages)


def test_streaming_flops_shrink_with_depth_at_fixed_traffic(planner):
    """Where PR3's recompute model punished deep fusion with the full
    trapezoid overhead, the streaming model's flops stay near T x the
    single-pass cost — the depth table must show recompute >> streaming
    at the chosen deep-fused tile."""
    plan = planner.plan(shape=(256, 256, 256), offsets=star_stencil(3, 2),
                        vmem_budget=16 << 20, aligned=True, time_steps=3)
    single_flops = plan.depth_scores[0][2]  # depth-1 chain flops
    assert plan.modeled_flops <= 1.25 * single_flops  # near-1x overhead
    assert plan.recompute_flops > 2 * single_flops    # what §8 would pay
