"""Compiles of the main path for a described TPU v5e, with no chip attached.

Each case lowers a public entry point with ``interpret=False`` for one
chip (or all four) of a described ``v5e:2x2`` topology and compiles it
with the chip's own compiler, which refuses what interpret mode lets
through: DMAs off the (sublane, lane) grain, more VMEM than the core has,
a program that does not fit the device.  Each case asserts that the
compiled program holds the Mosaic kernel (``tpu_custom_call``).  The
sizes are those of ``chip_smoke.py``'s phases.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and the test workers must
all collect the same tests.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import ir, obs
from repro.core.cache_fitting import star_stencil
from repro.kernels.ref import star_weights_2nd_order
from repro.kernels.stencil import stencil_iterate, stencil_pallas
from repro.launch.mesh import make_column_mesh

STAR, STAR_W = star_weights_2nd_order(3, 2)
JACOBI_2D = star_stencil(2, 1)
JACOBI_2D_W = [0.0, 0.25, 0.25, 0.25, 0.25]
BF16_CHAIN = ["bfloat16", "bfloat16", "bfloat16", "float32"]

# name -> (shape, dtype, entry point with interpret=False)
ONE_CHIP = {
    "star_512_single": (
        (512, 512, 512), jnp.float32,
        lambda u: stencil_pallas(u, STAR, STAR_W, interpret=False),
    ),
    "star_256_fused_T3": (
        (256, 256, 256), jnp.float32,
        lambda u: stencil_iterate(u, STAR, STAR_W, 3, interpret=False),
    ),
    "star_256_bf16_ring_T4": (
        (256, 256, 256), jnp.bfloat16,
        lambda u: stencil_iterate(
            u, STAR, STAR_W, 4, interpret=False, dtypes=BF16_CHAIN
        ),
    ),
    "jacobi_2d_16384": (
        (16384, 16384), jnp.float32,
        lambda u: stencil_pallas(u, JACOBI_2D, JACOBI_2D_W, interpret=False),
    ),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def chip(topo, monkeypatch):
    """The described chip, with the launcher's VMEM-capacity lookup
    steered to its device kind (``jax.devices()`` is the CPU here)."""
    from repro.kernels import _backend

    monkeypatch.setattr(
        _backend, "device_kind", lambda: topo.devices[0].device_kind
    )
    return topo


def _scopes(text):
    """The ``stencil_*`` scopes named in the compiled ops' metadata, and
    the HLO names of the Mosaic calls."""
    scopes = set(re.findall(r'op_name="[^"]*?/(stencil_[a-z]+)/', text))
    kernels = set(re.findall(
        r"%([\w.-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text
    ))
    return scopes, kernels


def _pad_ops(text):
    return re.findall(r"= \S+ pad\(", text)


def _compile(fn, arg):
    with obs.recording() as rec:
        compiled = jax.jit(fn).lower(arg).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    launches = [s for s in rec.spans if s.name == "kernel_launch"]
    assert launches and not any(s.args["interpret"] for s in launches)
    # The kernel is named, and a launch buffer's ops carry their scope
    # into the compiled program (a profiler trace reads both); a program
    # whose launches all read the caller's array builds no buffer.
    scopes, kernels = _scopes(text)
    assert kernels and all(k.startswith("stencil_sweep") for k in kernels)
    assert "stencil_sweep" in scopes
    if all(s.args["input_buffer"] == "direct" for s in launches):
        assert "stencil_embed" not in scopes and not _pad_ops(text)
    else:
        assert "stencil_embed" in scopes
    return text, launches


@pytest.mark.parametrize("case", sorted(ONE_CHIP))
def test_one_chip_phase_compiles(chip, case):
    shape, dtype, fn = ONE_CHIP[case]
    arg = jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(chip.devices[0])
    )
    _, launches = _compile(fn, arg)
    if case.startswith("star_256"):
        # The fused stage-chain kernel really runs: one launch, depth >= 2.
        assert len(launches) == 1
        assert launches[0].args["fused_depth"] >= 2


# The star13_512 cells' launches, straight from the caller's array: the
# single application at tile (1, 512, 512) and the fused depth-4 f32 ring
# at (1, 256, 512) (~3 min to compile).
DIRECT = {
    "star_512_single": (
        (1, 512, 512),
        lambda u: stencil_pallas(
            u, STAR, STAR_W, tile=(1, 512, 512), sweep_axis=0,
            interpret=False,
        ),
    ),
    "star_512_ring_T4": (
        (1, 256, 512),
        lambda u: stencil_iterate(
            u, STAR, STAR_W, 4, tile=(1, 256, 512), sweep_axis=0,
            window_kind="ring", interpret=False,
        ),
    ),
}


@pytest.mark.parametrize("case", sorted(DIRECT))
def test_direct_launch_compiles(chip, case):
    tile, fn = DIRECT[case]
    arg = jax.ShapeDtypeStruct(
        (512, 512, 512), jnp.float32,
        sharding=SingleDeviceSharding(chip.devices[0]),
    )
    text, launches = _compile(fn, arg)
    assert [s.args["input_buffer"] for s in launches] == ["direct"]
    assert launches[0].args["tile"] == list(tile)
    assert not _pad_ops(text) and "dynamic-update-slice" not in text


# The fused T=3 sharded launch compiles in ~2 min at 512^3 (its per-step
# tile is 8x the single-device one), so the test takes it at 256^3;
# chip_smoke.py --chips 4 runs both at 512^3.
@pytest.mark.parametrize("time_steps,n", [(1, 512), (3, 256)])
def test_four_chip_column_sharded_compiles(chip, time_steps, n):
    mesh = make_column_mesh(4, devices=chip.devices)
    arg = jax.ShapeDtypeStruct(
        (n, n, n), jnp.float32,
        sharding=NamedSharding(mesh, P("columns")),
    )
    text, launches = _compile(
        lambda u: stencil_iterate(
            u, STAR, STAR_W, time_steps, mesh=mesh, interpret=False
        ),
        arg,
    )
    assert all(s.args["num_shards"] == 4 for s in launches)
    assert "collective-permute" in text  # the shard-boundary halo exchange


def test_four_chip_periodic_program_scopes(chip):
    """A periodic 27-point program (NPB MG's operator shape) over the four
    chips: the compiled ops carry the wrap fill's and the halo exchange's
    scopes beside the embed's, and the exchange is a collective."""
    from repro import ir

    offs = np.array([(i, j, k) for i in (-1, 0, 1) for j in (-1, 0, 1)
                     for k in (-1, 0, 1)])
    w = [1.0 / 27] * 27
    prog = ir.stencil_program(offs, w, 1, d=3, boundary="periodic")
    mesh = make_column_mesh(4, devices=chip.devices)
    arg = jax.ShapeDtypeStruct(
        (128, 128, 128), jnp.float32,
        sharding=NamedSharding(mesh, P("columns")),
    )
    text, launches = _compile(
        lambda u: ir.run_program(prog, u, mesh=mesh, shard_axis=0,
                                 interpret=False),
        arg,
    )
    assert all(s.args["num_shards"] == 4 for s in launches)
    scopes, _ = _scopes(text)
    assert {"stencil_embed", "stencil_wrap", "stencil_halo"} <= scopes
    permutes = re.findall(r'collective-permute-start[^\n]*op_name="([^"]*)"',
                          text)
    assert permutes and all("/stencil_halo/" in p for p in permutes)


# Grids off the (sublane, lane) grain: PolyBench jacobi-2d's one sweep
# at EXTRALARGE (2800^2, the jacobi2d_2800.step1 cell's run_program) and
# LARGE (1300^2), the paper's 511^3 star, and 512 x 510 x 510 and
# 16 x 20 x 200 stars split four ways along axis 0.  Each reads a
# launch buffer (DESIGN.md §16): the chip slices an array only in whole
# grains.
POLYBENCH_W = [0.2] * 5
OFF_GRAIN = {
    "jacobi_2d_2800": (
        (2800, 2800), 1,
        lambda u, mesh: ir.run_program(
            ir.stencil_program(JACOBI_2D, POLYBENCH_W, 1, d=2), u,
            interpret=False,
        ),
    ),
    "jacobi_2d_1300": (
        (1300, 1300), 1,
        lambda u, mesh: stencil_pallas(
            u, JACOBI_2D, POLYBENCH_W, interpret=False
        ),
    ),
    "star_511": (
        (511, 511, 511), 1,
        lambda u, mesh: stencil_pallas(u, STAR, STAR_W, interpret=False),
    ),
    "star_512x510x510_4chip": (
        (512, 510, 510), 4,
        lambda u, mesh: stencil_pallas(
            u, STAR, STAR_W, mesh=mesh, shard_axis=0, interpret=False
        ),
    ),
    # The planner alone would split the 200 lanes into 50-lane slabs.
    "star_16x20x200_4chip": (
        (16, 20, 200), 4,
        lambda u, mesh: stencil_pallas(
            u, STAR, STAR_W, mesh=mesh, shard_axis=0, interpret=False
        ),
    ),
}


@pytest.mark.parametrize("case", sorted(OFF_GRAIN))
def test_off_grain_grid_compiles(chip, case):
    from repro.core.tiling import grid_slack

    shape, chips, fn = OFF_GRAIN[case]
    if chips > 1:
        mesh = make_column_mesh(chips, devices=chip.devices)
        sharding = NamedSharding(mesh, P("columns"))
    else:
        mesh = None
        sharding = SingleDeviceSharding(chip.devices[0])
    arg = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
    text, launches = _compile(lambda u: fn(u, mesh), arg)
    slack = list(grid_slack(shape, 4))
    assert any(slack)
    for s in launches:
        assert s.args["grid_slack"] == slack
        assert s.args["input_buffer"] == "pad"
        assert s.args["num_shards"] == chips


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_conv1d_compiles(chip, dtype):
    """The causal conv's sweep shares the grain-rounded window: a
    Mamba2-width conv (5120 channels, W=4) compiles at a 256-token tile."""
    from repro.kernels.conv1d import causal_conv1d

    one = SingleDeviceSharding(chip.devices[0])
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one)
        for shape in ((2, 4096, 5120), (4, 5120), (5120,))
    ]
    compiled = jax.jit(
        lambda x, w, b: causal_conv1d(x, w, b, tile_s=256, interpret=False)
    ).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "%conv1d_sweep" in text  # the kernel's name= reaches the HLO


def test_unaligned_explicit_tile_refused_before_compile(chip):
    """A tile off the lane grain with several tiles along that axis
    cannot be DMA'd on the chip: the launcher says so, not Mosaic."""
    arg = jax.ShapeDtypeStruct(
        (64, 256), jnp.float32, sharding=SingleDeviceSharding(chip.devices[0])
    )
    offs = star_stencil(2, 1)
    w = np.full(len(offs), 0.2).tolist()
    with pytest.raises(ValueError, match="grain on axis 1"):
        jax.jit(
            lambda u: stencil_pallas(
                u, offs, w, tile=(8, 64), sweep_axis=0, interpret=False
            )
        ).lower(arg)
