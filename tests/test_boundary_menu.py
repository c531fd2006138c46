"""Boundary-tap regressions for the §15 menu completions (PR 10).

Periodic wrap and robin (``u_ghost = α·u_edge + β``) joined the in-kernel
boundary menu; these tests pin their semantics against the numpy oracles
of :mod:`repro.kernels.ref` — including the corner composition (box
stencils read diagonal ghosts), fused T≥2 chains whose *intermediate*
values also need conditioning, fully one-sided ``(W-1, 0)`` halos, the
equivalence degeneracies (robin α=0 is dirichlet(β); α=1, β=0 is
neumann), and the 4-device sharded launch (bit-wise equal to the
single-device one, wrap links closing the ring over domain-owning
shards, including a ragged last shard)."""

from __future__ import annotations

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import ir  # noqa: E402
from repro.entry import run  # noqa: E402
from repro.ir.verify import IRVerifyError  # noqa: E402
from repro.kernels.ref import stencil_ref  # noqa: E402

N_DEV = len(jax.devices())

# A box(2,1) operator: 9 taps, so corner ghosts are actually read.
BOX = np.array([(i, j) for i in (-1, 0, 1) for j in (-1, 0, 1)])
BOX_W = [0.02 * k - 0.07 for k in range(9)]
# A star operator for the chains.
STAR = np.array([(0, 0), (-1, 0), (1, 0), (0, -1), (0, 2)])
STAR_W = [0.3, 0.2, 0.15, 0.1, 0.05]
# Fully one-sided (W-1, 0) halo: every tap trails the point.
TRAIL = np.array([(0, 0), (-1, 0), (-2, 0), (0, -1), (-1, -2)])
TRAIL_W = [0.4, 0.25, 0.1, 0.15, 0.05]


def _u(shape=(24, 32), seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


def _chain(u, offs, w, steps, kind, value, **kw):
    prog = ir.chain_program([(offs, w)] * steps, u.ndim, boundary=kind,
                            value=value)
    return run(prog, u, interpret=True, **kw)


def _ref_chain(u, offs, w, steps, kind, value):
    ref = u
    for _ in range(steps):
        ref = stencil_ref(ref, offs, w, boundary=kind, value=value)
    return ref


@pytest.mark.parametrize("offs,w", [(BOX, BOX_W), (STAR, STAR_W),
                                    (TRAIL, TRAIL_W)])
@pytest.mark.parametrize("kind,value", [("periodic", 0.0),
                                        ("robin", (0.7, 0.3))])
def test_single_application_matches_oracle(offs, w, kind, value):
    """T=1, corner-reading box / asymmetric star / one-sided trail taps."""
    u = _u()
    got = _chain(u, offs, w, 1, kind, value, tile=(8, 16))
    ref = _ref_chain(u, offs, w, 1, kind, value)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=0)


@pytest.mark.parametrize("kind,value", [("periodic", 0.0),
                                        ("robin", (-0.6, 0.25))])
@pytest.mark.parametrize("steps", [2, 3])
def test_fused_chain_matches_oracle(kind, value, steps):
    """Fused T≥2: intermediate values are conditioned in-kernel too."""
    u = _u((16, 32), seed=3)
    got = _chain(u, STAR, STAR_W, steps, kind, value, tile=(16, 32))
    ref = _ref_chain(u, STAR, STAR_W, steps, kind, value)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=5e-6, rtol=0)


def test_fused_one_sided_periodic():
    """(W-1, 0) halos under wrap, fused two stages deep."""
    u = _u((24, 32), seed=5)
    got = _chain(u, TRAIL, TRAIL_W, 2, "periodic", 0.0, tile=(12, 16))
    ref = _ref_chain(u, TRAIL, TRAIL_W, 2, "periodic", 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=0)


def test_robin_corner_single_application():
    """The corner contract: the affine ghost mix is applied ONCE even
    where two faces meet (the oracle pads edge-first, then mixes)."""
    u = _u((8, 16), seed=9)
    got = _chain(u, BOX, BOX_W, 1, "robin", (0.5, -1.25), tile=(8, 16))
    ref = _ref_chain(u, BOX, BOX_W, 1, "robin", (0.5, -1.25))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-6, rtol=0)


def test_robin_degenerates_to_dirichlet_and_neumann():
    u = _u((16, 16), seed=11)
    beta = 0.75
    rob0 = _chain(u, STAR, STAR_W, 2, "robin", (0.0, beta), tile=(16, 16))
    dir_ = _chain(u, STAR, STAR_W, 2, "dirichlet", beta, tile=(16, 16))
    np.testing.assert_allclose(np.asarray(rob0), np.asarray(dir_),
                               atol=2e-6, rtol=0)
    rob1 = _chain(u, STAR, STAR_W, 2, "robin", (1.0, 0.0), tile=(16, 16))
    neu = _chain(u, STAR, STAR_W, 2, "neumann", 0.0, tile=(16, 16))
    np.testing.assert_allclose(np.asarray(rob1), np.asarray(neu),
                               atol=2e-6, rtol=0)


def test_mixed_bc_chain_matches_oracle():
    """Per-stage mixed menu: robin input stage, neumann intermediate."""
    u = _u((16, 32), seed=13)
    prog = ir.chain_program(
        [(STAR, STAR_W), (BOX, BOX_W)], 2,
        boundary=[("robin", (0.4, 0.6)), ("neumann", 0.0)],
    )
    got = run(prog, u, tile=(16, 32), interpret=True)
    ref = stencil_ref(u, STAR, STAR_W, boundary="robin", value=(0.4, 0.6))
    ref = stencil_ref(ref, BOX, BOX_W, boundary="neumann")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=5e-6, rtol=0)


def test_periodic_is_all_or_nothing():
    """Mixing wrap with any other kind has no single-domain embedding —
    verify rejects it up front."""
    with pytest.raises(IRVerifyError):
        ir.lower(ir.chain_program(
            [(STAR, STAR_W), (STAR, STAR_W)], 2,
            boundary=["periodic", "neumann"],
        ), shape=(16, 32))


def test_periodic_reach_exceeding_domain_rejected():
    """A wrap halo deeper than the axis (reach 5 > extent 4) has no
    single-copy ghost source."""
    prog = ir.chain_program([(STAR, STAR_W)] * 5, 2, boundary="periodic")
    with pytest.raises(IRVerifyError, match="exceeds the domain extent"):
        ir.lower(prog, shape=(4, 32))


@pytest.mark.parametrize("kind,value", [("periodic", 0.0),
                                        ("robin", (0.8, -0.2))])
@pytest.mark.parametrize("shape", [(64, 256), (64, 192)])
def test_sharded_bitwise_parity(kind, value, shape):
    """4-device sharded launch is bit-wise equal to single-device — wrap
    links close the ring over the domain-owning shards, and (64, 192)
    makes the last shard ragged (192 = 3×64, round-up slack)."""
    if N_DEV < 4:
        pytest.skip("needs 4 devices")
    u = _u(shape, seed=17)
    kw = dict(tile=(64, 64), sweep_axis=0)
    base = _chain(u, STAR, STAR_W, 2, kind, value, **kw)
    shard = _chain(u, STAR, STAR_W, 2, kind, value, num_shards=4, **kw)
    assert np.array_equal(np.asarray(base), np.asarray(shard))


def test_sharded_periodic_matches_oracle():
    if N_DEV < 4:
        pytest.skip("needs 4 devices")
    u = _u((64, 256), seed=19)
    got = _chain(u, STAR, STAR_W, 2, "periodic", 0.0, tile=(64, 64),
                 sweep_axis=0, num_shards=4)
    ref = _ref_chain(u, STAR, STAR_W, 2, "periodic", 0.0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=5e-6, rtol=0)
