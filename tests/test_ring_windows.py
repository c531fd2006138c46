"""§14 ring frontier windows + dtype-aware tiling (PR 9).

Covers: ring-vs-trapezoid **bit-wise** parity across depth, asymmetric
(W-1, 0) halos, non-divisible extents, and a 4-shard mesh launch; fusion
depths a trapezoid budget cannot reach; the ring/dtype VMEM arithmetic in
``core.tiling``; mixed-precision chains (bf16 frontiers, f32
accumulation) against the f32 oracle; conv1d's bf16 path; schema-v6
dtype/window_kind round-trips; and the planner's window-kind race with
its never-worse gates.

Bit-parity caveat: the CPU backend contracts mul+add into FMAs *per
fusion* and different window kinds fuse differently, so these tests rely
on the ``--xla_cpu_max_isa`` cap ``tests/conftest.py`` pins (TPU runs
are unaffected — no flag needed there).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cache_fitting import star_stencil
from repro.core.tiling import (
    dtype_itemsize,
    fused_stage_bytes,
    sublane_unit,
)
from repro.kernels.ref import stencil_ref
from repro.kernels.stencil import stencil_iterate
from repro.plan import PlanCache, Planner
from repro.plan.schema import PlanRequest, StencilPlan, validate_plan_call

KEY = jax.random.PRNGKey(7)


def iterate_ref(u, offsets, weights, time_steps):
    for _ in range(time_steps):
        u = stencil_ref(u, offsets, weights)
    return u


@pytest.fixture
def planner():
    return Planner(cache=PlanCache(persistent=False))


# ---------------------------------------------------------------------------
# Ring vs trapezoid: bit-wise parity (the §14 gate).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T", [2, 3, 4, 5, 6])
def test_ring_bitwise_equals_trapezoid_and_separate(T):
    """The ring stores a suffix band of exactly the rows the next stage
    streams, so the values every stage reads are identical element-for-
    element to the trapezoid's — equality must be bit-wise, not approx."""
    u = jax.random.normal(KEY, (37, 45), jnp.float32)
    offs = star_stencil(2, 1)
    w = np.linspace(-0.3, 0.4, len(offs)).tolist()
    kw = dict(tile=(8, 16), sweep_axis=0)
    ring = stencil_iterate(u, offs, w, T, window_kind="ring", **kw)
    trap = stencil_iterate(u, offs, w, T, window_kind="trapezoid", **kw)
    sep = u
    for _ in range(T):  # stage-by-stage launches: the PR1-era baseline
        sep = stencil_iterate(sep, offs, w, 1, **kw)
    assert np.array_equal(np.asarray(ring), np.asarray(trap))
    assert np.array_equal(np.asarray(ring), np.asarray(sep))


def test_ring_bitwise_non_divisible_extents():
    """41x53 under a (16, 16) tile: both axes round up, the sweep padding
    runs through the ring rotation, and the trim must agree bit-wise."""
    u = jax.random.normal(KEY, (41, 53), jnp.float32)
    offs = star_stencil(2, 2)
    w = np.linspace(0.05, -0.35, len(offs)).tolist()
    kw = dict(tile=(16, 16), sweep_axis=0)
    ring = stencil_iterate(u, offs, w, 3, window_kind="ring", **kw)
    trap = stencil_iterate(u, offs, w, 3, window_kind="trapezoid", **kw)
    assert np.array_equal(np.asarray(ring), np.asarray(trap))
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(iterate_ref(u, offs, w, 3)),
        atol=3e-5, rtol=3e-5,
    )


@pytest.mark.parametrize("T", [3, 5])
def test_ring_bitwise_asymmetric_halo(T):
    """conv1d-style (W-1, 0) halo on the sweep axis: the ring band depth
    follows the per-side halos, not a symmetric radius."""
    offs = np.array([[-3, 0], [-2, 0], [-1, 0], [0, 0], [0, 1], [0, -1]])
    w = [0.1, 0.2, 0.3, -0.2, 0.25, -0.15]
    u = jax.random.normal(KEY, (50, 40), jnp.float32)
    kw = dict(tile=(8, 16), sweep_axis=0)
    ring = stencil_iterate(u, offs, w, T, window_kind="ring", **kw)
    trap = stencil_iterate(u, offs, w, T, window_kind="trapezoid", **kw)
    assert np.array_equal(np.asarray(ring), np.asarray(trap))
    np.testing.assert_allclose(
        np.asarray(ring), np.asarray(iterate_ref(u, offs, w, T)), atol=3e-5)


@pytest.mark.parametrize("T", [2, 4])
def test_ring_heterogeneous_chain_parity(T):
    """Alternating star(1)/star(2) stages: ring depths vary per frontier
    (each band sized for the *next* stage's read), still bit-wise."""
    o1, o2 = star_stencil(2, 1), star_stencil(2, 2)
    stages = [
        (o1, np.linspace(0.1, -0.2, len(o1)).tolist())
        if j % 2 == 0 else
        (o2, np.linspace(-0.05, 0.15, len(o2)).tolist())
        for j in range(T)
    ]
    u = jax.random.normal(KEY, (44, 52), jnp.float32)
    kw = dict(tile=(8, 16), sweep_axis=0)
    ring = stencil_iterate(u, stages=stages, window_kind="ring", **kw)
    trap = stencil_iterate(u, stages=stages, window_kind="trapezoid", **kw)
    assert np.array_equal(np.asarray(ring), np.asarray(trap))
    ref = u
    for o, ws in stages:
        ref = stencil_ref(ref, o, ws)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(ref), atol=3e-5)


def test_ring_sharded_bitwise_vs_single_device():
    """4-shard column launch of a ring-windowed chain == the single-device
    ring launch bit-wise (§10's promise extended to §14)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    u = jax.random.normal(KEY, (32, 48), jnp.float32)
    offs = star_stencil(2, 1)
    w = np.linspace(-0.25, 0.3, len(offs)).tolist()
    kw = dict(tile=(8, 16), sweep_axis=0, window_kind="ring")
    single = stencil_iterate(u, offs, w, 3, **kw)
    sharded = stencil_iterate(u, offs, w, 3, num_shards=4, shard_axis=1,
                              **kw)
    assert np.array_equal(np.asarray(single), np.asarray(sharded))


def test_ring_depth_beyond_trapezoid_budget(planner):
    """At a budget the same-dtype trapezoid exhausts, the ring's flat
    bands still admit strictly deeper fusion — and the deeper plan must
    execute correctly.  (The full 2 -> 4 uncapping needs bf16 frontiers
    on top; that gate is ``test_mixed_precision_plan_beats_f32_depth``.)"""
    shape = (64, 48, 128)
    offs = star_stencil(3, 1)
    budget = 360_000  # windows charged at their DMA-grain rounded size
    kw = dict(shape=shape, offsets=offs, time_steps=6, vmem_budget=budget,
              n_operands=1, aligned=True)
    trap = planner.plan(window_kind="trapezoid", **kw)
    ring = planner.plan(window_kind="ring", **kw)
    trap_max = max(d for d, _, _ in trap.depth_scores)
    ring_max = max(d for d, _, _ in ring.depth_scores)
    assert ring_max > trap_max, (trap.depth_scores, ring.depth_scores)
    # The extra depth genuinely does not fit a trapezoid at this budget.
    assert ring_max not in {d for d, _, _ in trap.depth_scores}
    # Per-depth never-worse: the freed VMEM can only buy an equal or
    # larger tile, so modeled traffic never regresses at any depth.
    trap_scores = dict((d, tr) for d, tr, _ in trap.depth_scores)
    ring_scores = dict((d, tr) for d, tr, _ in ring.depth_scores)
    for depth in trap_scores:
        assert ring_scores[depth] <= trap_scores[depth]
    # The deep ring plan actually runs, matching the iterated reference.
    u = jax.random.normal(KEY, shape, jnp.float32)
    w = np.linspace(-0.2, 0.3, len(offs)).tolist()
    out = stencil_iterate(u, offs, w, 6, plan=ring)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(iterate_ref(u, offs, w, 6)),
        atol=5e-5, rtol=5e-5,
    )


# ---------------------------------------------------------------------------
# Dtype-aware tiling arithmetic (core.tiling).
# ---------------------------------------------------------------------------

def test_sublane_unit_by_dtype():
    assert sublane_unit(4) == 8     # f32:  (8, 128)
    assert sublane_unit(2) == 16    # bf16: (16, 128)
    assert sublane_unit(1) == 32    # int8: (32, 128)
    assert dtype_itemsize("float32") == 4
    assert dtype_itemsize("bfloat16") == 2
    assert dtype_itemsize("int8") == 1
    with pytest.raises((KeyError, ValueError)):
        dtype_itemsize("float17")


def test_ring_stage_bytes_smaller_and_exact():
    """Ring bands beat trapezoid cones whenever some frontier's suffix
    exceeds its next stage's own sweep halo; equal-depth traffic parity
    is checked in the planner, residency here."""
    tile = (8, 16)
    halo = [(1, 1), (1, 1)]
    stage_halos = [[(1, 1), (1, 1)]] * 4
    trap = fused_stage_bytes(tile, halo, 4, 4, stage_halos=stage_halos,
                             window_kind="trapezoid", sweep_axis=0)
    ring = fused_stage_bytes(tile, halo, 4, 4, stage_halos=stage_halos,
                             window_kind="ring", sweep_axis=0)
    # Trapezoid: sweep extents 8+6, 8+4, 8+2; ring: 8+2 each.
    cross = [16 + 6, 16 + 4, 16 + 2]
    assert trap == 4 * sum(e * c for e, c in zip([14, 12, 10], cross))
    assert ring == 4 * sum(10 * c for c in cross)
    assert ring < trap
    # Depth 2 has a single frontier whose suffix IS the next stage's
    # halo: ring == trapezoid by construction.
    t2 = fused_stage_bytes(tile, halo, 4, 2, stage_halos=stage_halos[:2],
                           window_kind="trapezoid", sweep_axis=0)
    r2 = fused_stage_bytes(tile, halo, 4, 2, stage_halos=stage_halos[:2],
                           window_kind="ring", sweep_axis=0)
    assert t2 == r2


def test_stage_dtype_bytes_price_each_frontier():
    tile = (8, 16)
    halo = [(1, 1), (1, 1)]
    stage_halos = [[(1, 1), (1, 1)]] * 3
    f32 = fused_stage_bytes(tile, halo, 4, 3, stage_halos=stage_halos,
                            window_kind="ring", sweep_axis=0)
    mixed = fused_stage_bytes(tile, halo, 4, 3, stage_halos=stage_halos,
                              window_kind="ring", sweep_axis=0,
                              stage_dtype_bytes=[2, 2, 4])
    # Both frontiers (holding stages 0 and 1) drop to bf16: half the bytes.
    assert mixed == f32 // 2


# ---------------------------------------------------------------------------
# Mixed-precision chains: bf16 frontiers vs the f32 oracle.
# ---------------------------------------------------------------------------

def test_bf16_frontiers_hit_f32_oracle_within_tolerance():
    u = jax.random.normal(KEY, (40, 48), jnp.float32)
    offs = star_stencil(2, 1)
    w = np.linspace(-0.3, 0.4, len(offs)).tolist()
    kw = dict(tile=(8, 16), sweep_axis=0)
    oracle = np.asarray(stencil_iterate(u, offs, w, 3, **kw))
    out = stencil_iterate(
        u, offs, w, 3, dtypes=["bfloat16", "bfloat16", "float32"], **kw
    )
    assert out.dtype == jnp.float32  # last stage dtype wins
    # Two bf16 roundings of O(1) intermediates: ~1e-2 relative scale.
    np.testing.assert_allclose(np.asarray(out), oracle, atol=5e-2, rtol=5e-2)
    # And materially different from f32: the cast really happened.
    assert not np.array_equal(np.asarray(out), oracle)


def test_bf16_input_chain_and_output_dtype():
    """A bf16 input with default stage dtypes stays bf16 end to end; the
    f32 accumulate keeps it within bf16 rounding of the f32 chain."""
    uf = jax.random.normal(KEY, (33, 40), jnp.float32)
    ub = uf.astype(jnp.bfloat16)
    offs = star_stencil(2, 1)
    w = np.linspace(0.05, -0.3, len(offs)).tolist()
    kw = dict(tile=(8, 16), sweep_axis=0)
    out = stencil_iterate(ub, offs, w, 2, **kw)
    assert out.dtype == jnp.bfloat16
    oracle = np.asarray(stencil_iterate(uf, offs, w, 2, **kw))
    np.testing.assert_allclose(
        np.asarray(out, dtype=np.float32), oracle, atol=5e-2, rtol=5e-2)


def test_conv1d_bf16_parity_with_f32():
    """conv1d accepts bf16 without silent upcast: bf16 out/grads, f32
    accumulation, parity with the f32 path at loosened tolerance."""
    from repro.kernels.conv1d import causal_conv1d

    rng = np.random.default_rng(3)
    xf = jnp.asarray(rng.standard_normal((2, 48, 128)), jnp.float32)
    xb = xf.astype(jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((4, 128)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((128,)) * 0.1, jnp.float32)
    outf = causal_conv1d(xf, w, b, tile_s=16, interpret=True)
    outb = causal_conv1d(xb, w, b, tile_s=16, interpret=True)
    assert outb.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(outb, dtype=np.float32), np.asarray(outf),
        atol=5e-2, rtol=5e-2,
    )

    def loss(x):
        return causal_conv1d(x, w, b, tile_s=16, interpret=True).astype(
            jnp.float32).sum()

    gb = jax.grad(loss)(xb)
    gf = jax.grad(loss)(xf)
    assert gb.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(gb, dtype=np.float32), np.asarray(gf),
        atol=5e-2, rtol=5e-2,
    )


def test_conv1d_int8_codes_forward_bitwise():
    """conv1d's §15 int8 path: int8 code input keeps its VMEM window,
    slabs, and output in int8 while every MAC, the bias, and the silu
    run f32 — so the output IS the f32 path's values cast to int8,
    bit-wise (the int8→f32 load cast is exact)."""
    from repro.kernels.conv1d import causal_conv1d

    rng = np.random.default_rng(5)
    x8 = jnp.asarray(rng.integers(-127, 128, (2, 48, 128)), jnp.int8)
    xf = x8.astype(jnp.float32)
    # Small weights keep silu outputs inside int8 range post-cast.
    w = jnp.asarray(rng.standard_normal((4, 128)) * 0.02, jnp.float32)
    b = jnp.asarray(rng.standard_normal((128,)) * 0.1, jnp.float32)
    out8 = causal_conv1d(x8, w, b, tile_s=16, interpret=True)
    outf = causal_conv1d(xf, w, b, tile_s=16, interpret=True)
    assert out8.dtype == jnp.int8
    assert np.array_equal(
        np.asarray(out8), np.asarray(outf.astype(jnp.int8))
    )


def test_conv1d_int8_fake_quant_grad_parity():
    """int8 codes are not differentiable, so the training-side spelling
    is fake-quant: f32 values snapped to the int8 grid (scale 0.05).
    The kernel's forward and custom-VJP gradients at that point must
    match the reference model's within the f32 pair's tolerance."""
    from repro.kernels.conv1d import causal_conv1d
    from repro.models.ssm import _causal_conv

    rng = np.random.default_rng(7)
    xf = jnp.asarray(rng.standard_normal((2, 40, 128)), jnp.float32)
    scale = 0.05
    xq = jnp.round(xf / scale).clip(-127, 127) * scale
    w = jnp.asarray(rng.standard_normal((4, 128)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((128,)) * 0.1, jnp.float32)
    g = jnp.asarray(rng.standard_normal((2, 40, 128)), jnp.float32)

    def loss_kernel(x):
        return (causal_conv1d(x, w, b, tile_s=16, interpret=True) * g).sum()

    def loss_ref(x):
        ref, _ = _causal_conv(x, w, b, None)
        return (ref * g).sum()

    np.testing.assert_allclose(
        float(loss_kernel(xq)), float(loss_ref(xq)), rtol=1e-4)
    gk = jax.grad(loss_kernel)(xq)
    gr = jax.grad(loss_ref)(xq)
    assert gk.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(gk), np.asarray(gr), atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# Schema v6: dtype + window_kind round-trips and call validation.
# ---------------------------------------------------------------------------

def test_schema_v6_round_trip():
    offs = star_stencil(2, 1)
    req = PlanRequest.make(
        shape=(32, 48), offsets=offs, time_steps=3,
        dtypes=["bfloat16", None, "float32"], window_kind="ring",
    )
    assert req.window_kind == "ring"
    # "float32" restates the f32 input dtype — None-normalized (v7), so
    # spelling the input dtype out keys identically to omitting it.
    assert [st.dtype for st in req.stages] == ["bfloat16", None, None]
    back = PlanRequest.from_dict(req.canonical())
    assert back == req
    assert back.cache_key() == req.cache_key()
    # Normalization: jnp dtypes and names collapse to the same key.
    req2 = PlanRequest.make(
        shape=(32, 48), offsets=offs, time_steps=3,
        dtypes=[jnp.bfloat16, None, jnp.float32], window_kind="ring",
    )
    assert req2.cache_key() == req.cache_key()


def test_schema_rejects_bad_window_kind_and_dtype():
    offs = star_stencil(2, 1)
    with pytest.raises(ValueError):
        PlanRequest.make(shape=(32, 48), offsets=offs,
                         window_kind="doughnut")
    with pytest.raises((KeyError, ValueError, TypeError)):
        PlanRequest.make(shape=(32, 48), offsets=offs, time_steps=2,
                         dtypes=["float17", None])


def test_old_plan_dict_defaults_to_trapezoid(planner):
    """Pre-v6 dicts carry no window_kind: their frontiers were cones."""
    plan = planner.plan(shape=(64, 64), offsets=star_stencil(2, 1),
                        time_steps=2)
    d = plan.to_dict()
    d.pop("window_kind")
    d["request"].pop("window_kind")
    old = StencilPlan.from_dict(d)
    assert old.window_kind == "trapezoid"
    assert old.request.window_kind == "auto"


def test_validate_plan_call_checks_dtypes(planner):
    from repro.plan import PlanMismatchError

    offs = star_stencil(2, 1)
    plan = planner.plan(shape=(32, 48), offsets=offs, time_steps=2,
                        dtypes=["bfloat16", "float32"])
    validate_plan_call(
        plan, shape=(32, 48), offsets=[offs], dtype_bytes=4, time_steps=2,
        dtypes=["bfloat16", "float32"],
    )
    with pytest.raises(PlanMismatchError):
        validate_plan_call(
            plan, shape=(32, 48), offsets=[offs], dtype_bytes=4,
            time_steps=2, dtypes=["float32", "float32"],
        )
    with pytest.raises(PlanMismatchError):
        validate_plan_call(
            plan, shape=(32, 48), offsets=[offs], dtype_bytes=4,
            time_steps=2,
        )


def test_explain_json_round_trips_dtyped_plan(monkeypatch, tmp_path,
                                              capsys):
    """--json with --window-kind/--dtypes: the emitted plan dict round-
    trips through StencilPlan.from_dict and the report carries the §14
    fields."""
    import json

    from repro.plan.explain import main as explain_main

    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path / "plans"))
    rc = explain_main([
        "64x64x128", "--stencil", "star:1", "--geom", "none",
        "--time-steps", "3", "--window-kind", "ring",
        "--dtypes", "bfloat16,bfloat16,float32", "--json",
    ])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    plan = StencilPlan.from_dict(doc["plan"])
    # round trip (JSON turns tuples into lists; normalize first)
    assert json.loads(json.dumps(plan.to_dict())) == doc["plan"]
    assert plan.window_kind == "ring"
    assert doc["report"]["window_kind"] == "ring"
    assert doc["report"]["stage_dtypes"] == [
        "bfloat16", "bfloat16", None
    ]
    assert [st.dtype for st in plan.request.stages] == [
        "bfloat16", "bfloat16", None
    ]


# ---------------------------------------------------------------------------
# Planner: the window-kind race and its never-worse gates.
# ---------------------------------------------------------------------------

def test_auto_resolves_to_ring_never_worse(planner):
    offs = star_stencil(3, 2)
    kw = dict(shape=(128, 128, 128), offsets=offs, time_steps=4,
              vmem_budget=1 << 20)
    auto = planner.plan(**kw)
    trap = planner.plan(window_kind="trapezoid", **kw)
    assert auto.window_kind == "ring"
    assert auto.traffic_bytes <= trap.traffic_bytes
    assert max(d for d, _, _ in auto.depth_scores) >= max(
        d for d, _, _ in trap.depth_scores
    )
    # Distinct cache keys: a forced kind is a different request.
    assert auto.request.cache_key() != trap.request.cache_key()


def test_single_step_plans_have_no_frontier(planner):
    """T=1 has no staged frontiers: auto prices as a trapezoid and both
    forced kinds produce identical cost fields."""
    offs = star_stencil(2, 1)
    auto = planner.plan(shape=(64, 64), offsets=offs)
    ring = planner.plan(shape=(64, 64), offsets=offs, window_kind="ring")
    assert auto.window_kind == "trapezoid"
    assert ring.tile == auto.tile
    assert ring.traffic_bytes == auto.traffic_bytes


def test_mixed_precision_plan_beats_f32_depth(planner):
    """bf16 windows double the legal lane grain: at a budget that caps
    the f32 trapezoid at depth 2, the bf16 ring chain reaches depth 4
    (the BENCH_PR9 headline, pinned as a test).  The budget sits inside
    the 525,000-548,000 B window where that holds with every buffer
    charged at its DMA-grain rounded size."""
    offs = star_stencil(3, 2)
    kw = dict(shape=(256, 256, 256), offsets=offs, time_steps=4,
              vmem_budget=536_000, n_operands=1, pipelined=False,
              aligned=True)
    trap = planner.plan(window_kind="trapezoid", **kw)
    ring = planner.plan(
        window_kind="ring", dtype_bytes=2,
        dtypes=["bfloat16", "bfloat16", "bfloat16", "float32"], **kw,
    )
    assert max(d for d, _, _ in trap.depth_scores) == 2
    assert max(d for d, _, _ in ring.depth_scores) >= 4
    assert ring.fused_depth >= 4
