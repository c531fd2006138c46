"""Pallas stencil kernels vs the pure-jnp oracle (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cache_fitting import box_stencil, star_stencil
from repro import ir
from repro.entry import run, stencil_iterate
from repro.kernels.ops import plan_tiles
from repro.kernels.ref import star_weights_2nd_order, stencil_ref

KEY = jax.random.PRNGKey(0)

SHAPES_1D = [(65,), (256,)]
SHAPES_2D = [(17, 130), (40, 256), (33, 129)]
SHAPES_3D = [(9, 20, 140), (24, 40, 128)]


@pytest.mark.parametrize("shape", SHAPES_1D + SHAPES_2D + SHAPES_3D)
@pytest.mark.parametrize("r", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_star_stencil_matches_ref(shape, r, dtype):
    d = len(shape)
    u = jax.random.normal(KEY, shape, dtype)
    offs = star_stencil(d, r)
    w = np.linspace(-1, 1, len(offs)).tolist()
    out = stencil_iterate(u, offs, w, 1)
    ref = stencil_ref(u, offs, w)
    tol = 2e-5 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )


@pytest.mark.parametrize("shape", [(16, 140), (30, 70)])
def test_box_stencil_matches_ref(shape):
    u = jax.random.normal(KEY, shape, jnp.float32)
    offs = box_stencil(2, 1)
    w = np.arange(len(offs), dtype=float).tolist()
    np.testing.assert_allclose(
        stencil_iterate(u, offs, w, 1), stencil_ref(u, offs, w),
        atol=1e-4, rtol=1e-4,
    )


def test_paper_13pt_operator():
    u = jax.random.normal(KEY, (20, 30, 130), jnp.float32)
    offs, w = star_weights_2nd_order(3, 2)
    out = stencil_iterate(u, offs, w, 1)
    ref = stencil_ref(u, offs, w)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_multi_rhs_budget_split():
    """§5: p RHS arrays, one VMEM budget."""
    u1 = jax.random.normal(KEY, (24, 140), jnp.float32)
    u2 = jax.random.normal(jax.random.PRNGKey(1), (24, 140), jnp.float32)
    o1, o2 = star_stencil(2, 1), star_stencil(2, 2)
    w1, w2 = [0.3] * len(o1), [0.1] * len(o2)
    out = run(ir.rhs_program([o1, o2], [w1, w2], d=2), [u1, u2])
    ref = stencil_ref(u1, o1, w1) + stencil_ref(u2, o2, w2)
    np.testing.assert_allclose(out, ref, atol=1e-5)


def test_explicit_tile_override():
    u = jax.random.normal(KEY, (32, 256), jnp.float32)
    offs = star_stencil(2, 1)
    w = [1.0, 0.25, 0.25, 0.25, 0.25]
    out = stencil_iterate(u, offs, w, 1, tile=(8, 128))
    np.testing.assert_allclose(out, stencil_ref(u, offs, w), atol=1e-5)


def test_plan_reports_efficiency():
    c = plan_tiles((128, 128, 512), r=2)
    assert 0.5 < c.efficiency <= 1.0
