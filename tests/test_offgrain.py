"""Grids off the (sublane, lane) grain (DESIGN.md §16): extents on the
last two axes that are not whole grains — PolyBench's 2800² and 1300²,
511³, anything not a power of two — go through the normal path.

Each parity case runs a public entry in the Pallas interpreter on a
seeded random grid, compares it with ``kernels/ref.py``, and asserts the
launch kind each launch takes (``core.tiling.direct_input``: such a grid
reads a launch buffer) and the ``offgrain_launches`` count.  The result
must equal the reference bit for bit wherever the same call on the grid
rounded up to whole grains does.  The last cases run the
``jacobi2d_2800.step1`` benchmark cell at an off-grain tiny grid.
"""

import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import ir, obs
from repro.core.cache_fitting import star_stencil
from repro.core.tiling import axis_grain, direct_input, grid_slack
from repro.kernels.ref import star_weights_2nd_order, stencil_ref
from repro.kernels.stencil import launch_pads, stencil_iterate, stencil_pallas

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
# The planted faults of the benchmark's own fault tests.
from bench.tests.test_bench_faults import (  # noqa: E402
    _altered, _half, _unchanged,
)

STAR, STAR_W = star_weights_2nd_order(3, 2)
JACOBI = star_stencil(2, 1)
JACOBI_W = [0.2] * 5


def _ref(u, offs, wts, steps=1, boundary="zero"):
    for _ in range(steps):
        u = stencil_ref(u, offs, wts, boundary=boundary)
    return u


def _jacobi(**kw):
    return (lambda u: stencil_pallas(u, JACOBI, JACOBI_W, **kw),
            lambda u: _ref(u, JACOBI, JACOBI_W))


def _star(steps=1, **kw):
    return (lambda u: stencil_iterate(u, STAR, STAR_W, steps, **kw),
            lambda u: _ref(u, STAR, STAR_W, steps))


def _program(boundary):
    prog = ir.stencil_program(JACOBI, JACOBI_W, 1, d=2, boundary=boundary)
    return (lambda u: ir.run_program(prog, u),
            lambda u: _ref(u, JACOBI, JACOBI_W, boundary=boundary))


# name -> (shape, (entry, reference), launch kinds)
CASES = {
    "jacobi_64x200": ((64, 200), _jacobi(), ["pad"]),
    "jacobi_60x200": ((60, 200), _jacobi(), ["pad"]),
    "jacobi_52x300": ((52, 300), _jacobi(), ["pad"]),
    # Several tiles on both axes, each axis's last one ragged (the
    # planner's tile (8, 128) at this budget).
    "jacobi_60x300_ragged": (
        (60, 300), _jacobi(vmem_budget=32 << 10), ["pad"],
    ),
    "star_12x13x250": ((12, 13, 250), _star(), ["pad"]),
    "star_9x11x131": ((9, 11, 131), _star(), ["pad"]),
    # Two sublane tiles (8 + a ragged 5) beside one off-grain lane tile.
    "star_12x13x250_split": (
        (12, 13, 250), _star(vmem_budget=256 << 10), ["pad"],
    ),
    # The planner fuses both steps into one launch.
    "star_T2_fused": ((9, 11, 131), _star(2), ["pad"]),
    "star_T4_ring": (
        (9, 11, 131),
        _star(4, tile=(1, 11, 131), sweep_axis=0, window_kind="ring"),
        ["pad"],
    ),
    "program_zero": ((60, 200), _program("zero"), ["pad"]),
    "program_periodic": ((60, 200), _program("periodic"), ["wrap"]),
    # Four shards along axis 0, both grain axes off the grain; the
    # planner would split the 200-lane axis itself.
    "star_sharded_4": (
        (16, 20, 200), _star(num_shards=4, shard_axis=0), ["pad"],
    ),
}


def _on_grain(shape):
    """The shape rounded up to whole (8, 128) grains on its last two
    axes."""
    d = len(shape)
    return tuple(
        -(-n // g) * g
        for n, g in zip(shape, [1] * (d - 2) + [8, 128])
    )


def _normal(shape, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


@pytest.mark.parametrize("case", sorted(CASES))
def test_offgrain_matches_reference(case):
    shape, (entry, ref), kinds = CASES[case]
    assert any(grid_slack(shape, 4))
    u = _normal(shape, seed=len(case))
    with obs.recording() as rec:
        out = entry(u)
    launches = [s.args for s in rec.spans if s.name == "kernel_launch"]
    assert [a["input_buffer"] for a in launches] == kinds
    assert all(a["grid_slack"] == list(grid_slack(shape, 4))
               for a in launches)
    assert rec.counters["offgrain_launches"] == len(launches)
    assert rec.counters.get("direct_input_launches", 0) == 0
    want = ref(u)
    assert out.shape == shape and out.dtype == u.dtype
    # Bit for bit where the same call on whole grains is.
    v = _normal(_on_grain(shape), seed=len(case))
    with obs.recording() as rec_v:
        on_grain = entry(v)
    assert rec_v.counters.get("offgrain_launches", 0) == 0
    if np.array_equal(np.asarray(on_grain), np.asarray(ref(v))):
        assert np.array_equal(np.asarray(out), np.asarray(want))
    else:
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,itemsize,slack", [
    ((2800, 2800), 4, (0, 16)),
    ((1300, 1300), 4, (4, 108)),
    ((511, 511, 511), 4, (1, 1)),
    ((512, 510, 510), 4, (2, 2)),
    ((512, 512, 512), 4, (0, 0)),
    ((40, 256), 2, (8, 0)),      # bf16: 16-row sublane grain
    ((70,), 4, (0, 58)),
])
def test_grid_slack(shape, itemsize, slack):
    """The slack, and what it decides: with the grid in one tile, a grid
    with slack reads a launch buffer, one without reads itself; the
    buffer is whole grains on every axis."""
    assert grid_slack(shape, itemsize) == slack
    d = len(shape)
    halo = [(1, 1)] * d
    assert direct_input(shape, shape, halo, itemsize) is not any(slack)
    pads = launch_pads(shape, shape, [1] * d, [1] * d, itemsize)
    for i, (n, (lo, hi)) in enumerate(zip(shape, pads)):
        assert (lo + n + hi) % axis_grain(i, d, itemsize) == 0


def test_offgrain_counter_reconciles():
    """One count per launch on an off-grain grid; ``repro.obs.report``
    reconciles the counter against the spans' ``grid_slack``."""
    from repro.obs.report import reconcile, render, summarize

    with obs.recording() as rec:
        stencil_pallas(_normal((60, 200)), JACOBI, JACOBI_W)
        stencil_pallas(_normal((64, 256)), JACOBI, JACOBI_W)
        stencil_iterate(_normal((9, 11, 131)), STAR, STAR_W, 4)
    slacks = [s.args["grid_slack"] for s in rec.spans
              if s.name == "kernel_launch"]
    assert slacks[:2] == [[4, 56], [0, 0]]
    assert rec.counters["offgrain_launches"] == len(slacks) - 1
    summary = summarize(rec.to_trace_events())
    assert reconcile(summary) == []
    assert "4x56" in render(summary)
    summary["counters"]["offgrain_launches"] += 1
    assert any("offgrain_launches" in p for p in reconcile(summary))


@pytest.mark.parametrize("shape,offs,tile", [
    ((2800, 2800), JACOBI, (8, 2800)),
    ((1300, 1300), JACOBI, None),
    ((511, 511, 511), STAR, None),
])
def test_offgrain_plan_report_and_charge(shape, offs, tile):
    """The plan of an off-grain grid: the report's ``input:`` and ``grid
    slack:`` lines and JSON fields answer with the launcher's functions,
    and the planner charges the windows and slabs the kernel allocates
    (``window_extents``: the edge handling allocates nothing more)."""
    from repro.core.tiling import halo_from_offsets, window_extents
    from repro.plan import PlanCache, Planner
    from repro.plan.explain import format_plan, launch_input, plan_json_doc

    plan = Planner(cache=PlanCache(persistent=False)).plan(
        shape=shape, offsets=offs,
    )
    if tile is not None:
        assert plan.tile == tile
    slack = grid_slack(shape, 4)
    assert launch_input(plan) == "buffer"
    assert plan_json_doc(plan)["report"]["grid_slack"] == list(slack)
    assert (f"grid slack: {slack[0]} sublanes x {slack[1]} lanes"
            in format_plan(plan))
    ext = window_extents(plan.tile, halo_from_offsets([offs], len(shape)), 4)
    s = plan.sweep_axis
    cross = int(np.prod([e for i, e in enumerate(ext) if i != s]))
    slabs = 2 * plan.tile[s] * cross if plan.pipelined else 0
    assert plan.vmem_bytes == 4 * (int(np.prod(ext)) + slabs)


# -- the benchmark cell at an off-grain tiny grid ---------------------------

CELL = "jacobi2d_2800.step1"
SEED = 2**32 + 2801


@pytest.fixture
def offgrain_root(tmp_path):
    """A checkout with ``BENCHMARK.json`` and ``bench/`` as committed,
    ``jacobi2d_2800`` cut to an off-grain 60x200 grid, and a ``cpu`` row
    in the peaks table so that a run can go on without a chip (as
    ``bench/tests/conftest.py`` builds its tiny checkout)."""
    bench = tmp_path / "bench"
    shutil.copytree(
        os.path.join(ROOT, "bench"), bench,
        ignore=shutil.ignore_patterns("tests", "__pycache__"),
    )
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    path = bench / "configs" / "jacobi2d_2800.json"
    config = json.loads(path.read_text())
    config["grid"] = [60, 200]
    path.write_text(json.dumps(config))
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["cpu"] = peaks["TPU v5 lite"]
    (bench / "peaks.json").write_text(json.dumps(peaks))
    return tmp_path


def _run(root, **kw):
    return harness.run(CELL, SEED, 0.3, False, time.perf_counter(),
                       root=root, bench_dir=root / "bench",
                       require_chip=False, **kw)


def test_cell_runs_correct(offgrain_root):
    r = _run(offgrain_root)
    assert r["correct"], r["check"]
    assert r["check"]["max_rel_err"]["value"] == 0.0
    assert set(r["metrics"]) == {"gpts_per_s", "call_p95_ms", "setup_s"}


def test_cell_bf16_control_is_not_correct(offgrain_root):
    r = _run(offgrain_root, input_dtype="bfloat16")
    assert not r["correct"], r["check"]


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered])
def test_cell_fault_is_not_correct(offgrain_root, fault):
    r = _run(offgrain_root, wrap_entry=fault)
    assert not r["correct"], (fault.__name__, r["check"])
