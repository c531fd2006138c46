"""Hypothesis sweep: random stencils through the Pallas kernel vs oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.entry import stencil_iterate
from repro.kernels.ref import stencil_ref


@settings(deadline=None, max_examples=10)
@given(
    st.tuples(st.integers(6, 24), st.integers(100, 200)),
    st.integers(1, 2),
    st.integers(0, 2 ** 31 - 1),
)
def test_random_2d_stencils(shape, r, seed):
    rng = np.random.default_rng(seed)
    n_pts = rng.integers(2, 6)
    offs = rng.integers(-r, r + 1, size=(n_pts, 2))
    w = rng.normal(size=n_pts).tolist()
    u = jax.random.normal(jax.random.PRNGKey(seed % 997), shape, jnp.float32)
    out = stencil_iterate(u, offs, w, 1)
    ref = stencil_ref(u, offs, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


@settings(deadline=None, max_examples=6)
@given(st.integers(0, 2 ** 31 - 1))
def test_random_3d_stencils(seed):
    rng = np.random.default_rng(seed)
    offs = rng.integers(-1, 2, size=(4, 3))
    w = rng.normal(size=4).tolist()
    u = jax.random.normal(jax.random.PRNGKey(seed % 991), (6, 10, 136),
                          jnp.float32)
    np.testing.assert_allclose(
        np.asarray(stencil_iterate(u, offs, w, 1)),
        np.asarray(stencil_ref(u, offs, w)), atol=1e-4, rtol=1e-4)
