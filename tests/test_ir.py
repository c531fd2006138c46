"""Stencil-program IR (DESIGN.md §13).

Covers: serialization round-trips and the canonical plan-key normal form
(every spelling of one computation — ``time_steps=``, ``stages=``, an
explicit program — shares a single serialized key); the shape-inference
pass pinned against the legacy §9 halo arithmetic for T ∈ {1, 2, 3};
verify/lowering legality errors; bit-wise parity of ``stencil_iterate``
and of the engine's multi-RHS launch with their program spellings run
through ``repro.entry.run``; boundary-op lowering to in-kernel correction taps (dirichlet
/ neumann / reflect vs the :func:`repro.kernels.ref.stencil_ref`
oracle, single-stage and fused, single-device and on the 4-device mesh
with zero host-side ``jnp.pad`` on the hot path); and the
``plan.explain --json`` program/bounds document.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ir
from repro.core.cache_fitting import star_stencil
from repro.core.tiling import halo_from_offsets, stage_suffix_halos
from repro.ir import (
    Apply,
    Bounds,
    IRLowerError,
    IRVerifyError,
    Load,
    Program,
    Store,
    chain_program,
    infer_bounds,
    infer_halos,
    plan_program_key,
    rhs_program,
    stencil_program,
    summarize_program,
)
from repro.entry import run, stencil_iterate
from repro.kernels.ref import stencil_ref
from repro.kernels.stencil import _stencil_call
from repro.plan import PlanCache, Planner, PlanRequest

KEY = jax.random.PRNGKey(7)

OFFS_CONV = np.array([[-3, 0], [-2, 0], [-1, 0], [0, 0], [0, 1]])
W_CONV = (0.1, 0.2, 0.3, -0.2, 0.25)
OFFS_S1 = star_stencil(2, 1)
W_S1 = tuple(np.linspace(-0.3, 0.4, len(OFFS_S1)).tolist())
OFFS_S2 = star_stencil(2, 2)
W_S2 = tuple(np.linspace(-0.1, 0.12, len(OFFS_S2)).tolist())
CHAIN3 = [(OFFS_CONV, W_CONV), (OFFS_S1, W_S1), (OFFS_S2, W_S2)]


def bc_ref(u, stages, kind, value=0.0):
    """Stage-by-stage oracle: each stage reads its input under ``kind``."""
    for offs, w in stages:
        u = stencil_ref(u, offs, list(w), boundary=kind, value=value)
    return u


# ---------------------------------------------------------------------------
# Serialization + the canonical plan key.
# ---------------------------------------------------------------------------

def test_serialize_roundtrip():
    prog = chain_program(CHAIN3, d=2, boundary="neumann")
    again = Program.from_json(prog.serialize())
    assert again == prog
    assert again.serialize() == prog.serialize()


def test_spellings_share_one_plan_key():
    """time_steps=, stages=, and the explicit program serialize to one
    canonical key (weightless, values renamed)."""
    a = stencil_program(OFFS_S1, W_S1, time_steps=3, d=2)
    b = chain_program([(OFFS_S1, W_S1)] * 3, d=2)
    c = chain_program([(OFFS_S1, None)] * 3, d=2)
    key = plan_program_key(
        2, stage_offsets=[tuple(map(tuple, OFFS_S1.tolist()))] * 3
    )
    assert a.canonical().serialize() == key
    assert b.canonical().serialize() == key
    assert c.canonical().serialize() == key
    # A boundary op is part of the computation: it splits the plan key.
    plain = PlanRequest.make(shape=(32, 32), stages=[OFFS_S1] * 3)
    neu = PlanRequest.make(shape=(32, 32), stages=[OFFS_S1] * 3,
                           bcs=["neumann"] * 3)
    assert neu.cache_key() != plain.cache_key()


def test_zero_boundary_drops_from_plan_key():
    """zero / dirichlet(0) boundary ops are bit-identical to the native
    fill and must not split the cache key."""
    plain = chain_program([(OFFS_S1, W_S1)], d=2)
    zero = chain_program([(OFFS_S1, W_S1)], d=2, boundary="zero")
    dir0 = chain_program([(OFFS_S1, W_S1)], d=2, boundary="dirichlet")
    neu = chain_program([(OFFS_S1, W_S1)], d=2, boundary="neumann")
    key = plain.canonical().serialize()
    assert zero.canonical().serialize() == key
    assert dir0.canonical().serialize() == key
    assert neu.canonical().serialize() != key


def test_plan_request_carries_program():
    """PlanRequest derives the canonical program (schema v5) and re-derives
    it on deserialization — the dict is never trusted."""
    req = PlanRequest.make(shape=(48, 64), offsets=OFFS_S1, time_steps=3)
    req2 = PlanRequest.make(
        shape=(48, 64), stages=[OFFS_S1, OFFS_S1, OFFS_S1]
    )
    assert req.program and req.program == req2.program
    assert req.cache_key() == req2.cache_key()
    rt = PlanRequest.from_dict(req.canonical())
    assert rt.program == req.program and rt.cache_key() == req.cache_key()
    # A non-zero boundary is a different computation: different key.
    bc = PlanRequest.make(
        shape=(48, 64), stages=[OFFS_S1] * 3, bcs=["neumann"] * 3
    )
    assert bc.program != req.program
    assert bc.cache_key() != req.cache_key()


def test_plan_request_zero_bcs_normalize_away():
    a = PlanRequest.make(shape=(48, 64), stages=[OFFS_S1] * 2)
    b = PlanRequest.make(
        shape=(48, 64), stages=[OFFS_S1] * 2,
        bcs=["zero", ("dirichlet", 0.0)],
    )
    assert a == b and a.cache_key() == b.cache_key()


# ---------------------------------------------------------------------------
# Shape inference, pinned to the legacy §9 halo arithmetic.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T", [1, 2, 3])
def test_suffix_halos_match_legacy(T):
    stages = CHAIN3[:T]
    prog = chain_program(stages, d=2)
    legacy = stage_suffix_halos(
        [halo_from_offsets([offs], 2) for offs, _ in stages]
    )
    got = ir.suffix_halos(prog)
    assert [list(map(tuple, h)) for h in got] == [
        list(map(tuple, h)) for h in legacy
    ]
    assert all(lo == 0 and hi == 0 for lo, hi in got[-1])


@pytest.mark.parametrize("T", [1, 2, 3])
def test_stage_halos_match_legacy(T):
    prog = chain_program(CHAIN3[:T], d=2)
    got = ir.stage_halos(prog)
    legacy = [halo_from_offsets([offs], 2) for offs, _ in CHAIN3[:T]]
    assert [list(map(tuple, h)) for h in got] == [
        list(map(tuple, h)) for h in legacy
    ]


def test_infer_bounds_backward_growth():
    """The stored value covers [0, N); each upstream value grows by the
    accessed-offset footprint (xdsl-style boxes)."""
    prog = chain_program([(OFFS_S1, W_S1), (OFFS_S2, W_S2)], d=2)
    bounds = infer_bounds(prog, (20, 30))
    stored = bounds[prog.stored()]
    assert stored == Bounds(lb=(0, 0), ub=(20, 30))
    # Stage 2 (r=2 star) grows its operand by 2 per side; the load then
    # grows by stage 1's r=1 on top of that.
    assert bounds["v1"] == Bounds(lb=(-2, -2), ub=(22, 32))
    assert bounds["u0"] == Bounds(lb=(-3, -3), ub=(23, 33))
    halos = infer_halos(prog)
    assert halos["u0"] == ((3, 3), (3, 3))
    assert halos["v1"] == ((2, 2), (2, 2))


def test_boundary_op_passes_bounds_through():
    plain = chain_program([(OFFS_S2, W_S2)], d=2)
    withbc = chain_program([(OFFS_S2, W_S2)], d=2, boundary="neumann")
    assert infer_halos(plain)["u0"] == ((2, 2), (2, 2))
    h = infer_halos(withbc)
    assert h["u0"] == h["b0"] == ((2, 2), (2, 2))


# ---------------------------------------------------------------------------
# Verify / lowering legality.
# ---------------------------------------------------------------------------

def test_verify_rejects_double_store():
    ops = (
        Load(result="u", input="u"),
        Apply(result="v", operand="u",
              offsets=((0, 0),), weights=(1.0,)),
        Store(operand="v"),
        Store(operand="v"),
    )
    with pytest.raises(IRVerifyError, match="exactly one store"):
        ir.verify(Program(d=2, ops=ops))


def test_verify_rejects_undefined_operand():
    ops = (
        Apply(result="v", operand="ghost",
              offsets=((0, 0),), weights=(1.0,)),
        Store(operand="v"),
    )
    with pytest.raises(IRVerifyError, match="undefined value"):
        ir.verify(Program(d=2, ops=ops))


def test_verify_rejects_reflect_on_asymmetric_halo():
    prog = chain_program([(OFFS_CONV, W_CONV)], d=2, boundary="reflect")
    with pytest.raises(IRVerifyError, match="asymmetric"):
        ir.verify(prog, shape=(50, 45))


def test_verify_rejects_tiny_domain_under_bc():
    prog = chain_program([(OFFS_S2, W_S2)], d=2, boundary="neumann")
    with pytest.raises(IRVerifyError, match="both edges"):
        ir.verify(prog, shape=(4, 45))


def test_shape_only_program_plans_but_does_not_lower():
    prog = chain_program([OFFS_S1, OFFS_S2], d=2)
    assert ir.stage_halos(prog)  # planning-side passes work...
    with pytest.raises(IRLowerError, match="shape-only"):
        ir.lower(prog)  # ...but there is no executable launch


def test_lower_folds_damped_jacobi_combine():
    """(1-ω)·u + ω·K·u folds into one widened stage — exact, same sum."""
    omega = 0.8
    ops = (
        Load(result="u", input="u"),
        Apply(result="Ku", operand="u",
              offsets=tuple(map(tuple, OFFS_S1.tolist())), weights=W_S1),
        ir.Combine(result="v", operands=("u", "Ku"),
                   coeffs=(1.0 - omega, omega)),
        Store(operand="v"),
    )
    low = ir.lower(Program(d=2, ops=ops))
    assert low.kind == "chain" and len(low.stages) == 1
    offs, wts = low.stages[0]
    table = dict(zip(offs, wts))
    w_center = dict(zip(map(tuple, OFFS_S1.tolist()), W_S1))[(0, 0)]
    assert table[(0, 0)] == pytest.approx((1.0 - omega) + omega * w_center)


def test_lower_multi_rhs_folds_coeffs():
    ops = (
        Load(result="a", input="a"),
        Load(result="b", input="b"),
        Apply(result="Ka", operand="a",
              offsets=tuple(map(tuple, OFFS_S1.tolist())), weights=W_S1),
        Apply(result="Kb", operand="b",
              offsets=tuple(map(tuple, OFFS_S2.tolist())), weights=W_S2),
        ir.Combine(result="q", operands=("Ka", "Kb"), coeffs=(1.0, -1.0)),
        Store(operand="q"),
    )
    low = ir.lower(Program(d=2, ops=ops))
    assert low.kind == "multi_rhs" and low.inputs == ("a", "b")
    assert low.stages[1][1] == tuple(-w for w in W_S2)


# ---------------------------------------------------------------------------
# Bit-parity: stencil_iterate's spellings vs their program form.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("T", [1, 2, 3])
def test_program_spelling_bitwise_equals_stages(T):
    u = jax.random.normal(KEY, (50, 45), jnp.float32)
    stages = CHAIN3[:T]
    legacy = stencil_iterate(u, stages=stages, tile=(8, 16), sweep_axis=0)
    prog = run(
        chain_program(stages, d=2), u, tile=(8, 16), sweep_axis=0
    )
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(prog))


def test_program_spelling_bitwise_equals_time_steps():
    u = jax.random.normal(KEY, (30, 40), jnp.float32)
    legacy = stencil_iterate(
        u, OFFS_S1, list(W_S1), 3, tile=(8, 16), sweep_axis=0,
    )
    prog = run(
        stencil_program(OFFS_S1, W_S1, time_steps=3, d=2),
        u, tile=(8, 16), sweep_axis=0,
    )
    np.testing.assert_array_equal(np.asarray(legacy), np.asarray(prog))


def test_program_spelling_bitwise_equals_multi_rhs():
    ua = jax.random.normal(KEY, (30, 40), jnp.float32)
    """The §5 sum as a program equals the engine's multi-RHS launch bit
    for bit, and the oracle's sum of the two applications."""
    ub = jax.random.normal(jax.random.PRNGKey(8), (30, 40), jnp.float32)
    specs = tuple(
        (tuple(map(tuple, np.asarray(o).tolist())), tuple(map(float, w)))
        for o, w in ((OFFS_S1, W_S1), (OFFS_S2, W_S2))
    )
    launch = _stencil_call((ua, ub), specs, (8, 16), 0, True, True)
    prog = run(
        rhs_program([OFFS_S1, OFFS_S2], [W_S1, W_S2], d=2),
        {"u0": ua, "u1": ub}, tile=(8, 16), sweep_axis=0,
    )
    np.testing.assert_array_equal(np.asarray(launch), np.asarray(prog))
    ref = stencil_ref(ua, OFFS_S1, list(W_S1)) + stencil_ref(
        ub, OFFS_S2, list(W_S2)
    )
    np.testing.assert_allclose(np.asarray(prog), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_explicit_zero_boundary_bitwise_equals_plain():
    """A zero boundary op lowers to the engine-native fill: same bits,
    same cache key, no correction taps."""
    u = jax.random.normal(KEY, (40, 33), jnp.float32)
    plain = run(
        chain_program(CHAIN3[:2], d=2), u, tile=(8, 16), sweep_axis=0
    )
    zero = run(
        chain_program(CHAIN3[:2], d=2, boundary="zero"),
        u, tile=(8, 16), sweep_axis=0,
    )
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(zero))


# ---------------------------------------------------------------------------
# Boundary ops: in-kernel correction taps vs the padded oracle.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,value", [
    ("dirichlet", 1.7),
    ("neumann", 0.0),
    ("reflect", 0.0),
])
@pytest.mark.parametrize("offs,wts", [(OFFS_S1, W_S1), (OFFS_S2, W_S2)])
def test_boundary_single_stage_matches_oracle(kind, value, offs, wts):
    u = jax.random.normal(KEY, (40, 33), jnp.float32)
    prog = chain_program([(offs, wts)], d=2, boundary=kind, value=value)
    out = run(prog, u, tile=(8, 16), sweep_axis=0)
    ref = stencil_ref(u, offs, list(wts), boundary=kind, value=value)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("kind", ["dirichlet", "neumann"])
def test_boundary_fused_chain_matches_stagewise_oracle(kind):
    """A fused T=2 heterogeneous chain under a non-zero boundary equals
    applying the boundary oracle stage by stage (the §9 streaming window
    corrects intermediate-stage reads too)."""
    u = jax.random.normal(KEY, (40, 33), jnp.float32)
    stages = [(OFFS_S1, W_S1), (OFFS_S2, W_S2)]
    value = 0.4
    prog = chain_program(stages, d=2, boundary=kind, value=value)
    out = run(prog, u, tile=(8, 16), sweep_axis=0)
    ref = bc_ref(u, stages, kind, value)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_boundary_conv_asymmetric_halo_neumann():
    """Asymmetric (3, 0)/(0, 1) halo: edge-replication corrections on one
    side only per axis."""
    u = jax.random.normal(KEY, (50, 45), jnp.float32)
    prog = chain_program([(OFFS_CONV, W_CONV)], d=2, boundary="neumann")
    out = run(prog, u, tile=(8, 16), sweep_axis=0)
    ref = stencil_ref(u, OFFS_CONV, list(W_CONV), boundary="neumann")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_boundary_3d_reflect_matches_oracle():
    u = jax.random.normal(KEY, (14, 22, 40), jnp.float32)
    offs = star_stencil(3, 1)
    wts = tuple(np.linspace(0.05, 0.2, len(offs)).tolist())
    prog = chain_program([(offs, wts)], d=3, boundary="reflect")
    out = run(prog, u, tile=(4, 8, 20), sweep_axis=0)
    ref = stencil_ref(u, offs, list(wts), boundary="reflect")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def test_boundary_per_stage_mix():
    """Per-stage boundary kinds: neumann into stage 1, zero into stage 2."""
    u = jax.random.normal(KEY, (40, 33), jnp.float32)
    stages = [(OFFS_S1, W_S1), (OFFS_S2, W_S2)]
    prog = chain_program(stages, d=2, boundary=["neumann", None])
    out = run(prog, u, tile=(8, 16), sweep_axis=0)
    ref = stencil_ref(u, OFFS_S1, list(W_S1), boundary="neumann")
    ref = stencil_ref(ref, OFFS_S2, list(W_S2))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


# ---------------------------------------------------------------------------
# The 4-device mesh: boundary programs shard, with no host-side pad.
# ---------------------------------------------------------------------------

@pytest.mark.skipif(
    jax.device_count() < 4, reason="needs 4 devices (conftest forces them)"
)
def test_neumann_program_on_mesh_no_host_pad(monkeypatch):
    """Acceptance: a neumann-boundary program runs column-sharded over 4
    devices, equals the single-device launch bit-wise and the oracle
    numerically — and the hot path never calls ``jnp.pad`` (the §13
    boundary lowering replaces the host pad with in-kernel correction
    taps over a pad-free embed)."""
    u = jax.random.normal(KEY, (41, 52), jnp.float32)
    prog = chain_program(
        [(OFFS_S1, W_S1), (OFFS_S1, W_S1)], d=2, boundary="neumann"
    )
    ref = bc_ref(u, [(OFFS_S1, W_S1)] * 2, "neumann")
    single = run(prog, u, tile=(8, 16), sweep_axis=0)

    calls = []
    real_pad = jnp.pad

    def counting_pad(*args, **kwargs):
        calls.append(1)
        return real_pad(*args, **kwargs)

    monkeypatch.setattr(jnp, "pad", counting_pad)
    sharded = run(
        prog, u, tile=(8, 16), sweep_axis=0, num_shards=4
    )
    monkeypatch.undo()
    assert not calls, f"host-side jnp.pad ran {len(calls)}x on the hot path"
    np.testing.assert_array_equal(np.asarray(single), np.asarray(sharded))
    np.testing.assert_allclose(
        np.asarray(sharded), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


# ---------------------------------------------------------------------------
# Planner + explain integration.
# ---------------------------------------------------------------------------

def test_explain_json_program_roundtrip():
    from repro.plan.explain import plan_json_doc

    planner = Planner(cache=PlanCache(persistent=False))
    plan = planner.plan(
        shape=(64, 64, 64),
        stages=[star_stencil(3, 1), star_stencil(3, 2)],
        vmem_budget=16 << 20, aligned=True,
    )
    doc = plan_json_doc(plan)
    assert doc["program"] is not None
    # The document's program round-trips to the request's cache-key form.
    assert Program.from_dict(doc["program"]).serialize() == \
        plan.request.program
    # Every program value carries inferred bounds; the stored value is
    # exactly the domain box.
    prog = Program.from_dict(doc["program"])
    vb = doc["value_bounds"]
    assert set(vb) == {op.result for op in prog.ops
                       if not isinstance(op, Store)}
    assert vb[prog.stored()] == {"lb": [0, 0, 0], "ub": [64, 64, 64]}


def test_planner_plans_boundary_request():
    """A bc-annotated request plans (same survey machinery), is cached
    under its own key, and prices like the bc-free chain (corrections are
    O(surface), not modeled)."""
    planner = Planner(cache=PlanCache(persistent=False))
    kw = dict(shape=(96, 96), stages=[OFFS_S1, OFFS_S2],
              vmem_budget=1 << 20)
    plain = planner.plan(**kw)
    bc = planner.plan(**kw, bcs=["neumann", "neumann"])
    assert bc.request.cache_key() != plain.request.cache_key()
    assert bc.tile == plain.tile and bc.fused_depth == plain.fused_depth


def test_summarize_program_renders_pipeline():
    prog = chain_program([(OFFS_S1, W_S1)], d=2, boundary="neumann")
    s = summarize_program(prog)
    assert s == "load(u) |> boundary[neumann] |> apply[5pt r(1,1)(1,1)] |> store"
