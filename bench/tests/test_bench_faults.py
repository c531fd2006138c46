"""The check that decides ``correct`` fails the control and each fault a
cell can have, planted underneath a run that otherwise goes as on the
chip (the look for a TPU skipped, Pallas interpreted, tiny grids)."""

import time

import pytest

from bench import harness

SEED = 2**32 + 777


def _run(root, cell, **kw):
    return harness.run(cell, SEED, 0.3, False, time.perf_counter(),
                       root=root, bench_dir=root / "bench",
                       require_chip=False, **kw)


def _unchanged(entry):
    """A call that returns its input: the state never advances."""
    return lambda u: u


def _half(entry):
    """Half of the grid left out of the result."""
    def call(u):
        out = entry(u)
        return out.at[out.shape[0] // 2:].set(0)
    return call


def _altered(entry):
    """One answer altered where it is produced."""
    def call(u):
        out = entry(u)
        return out.at[(0,) * out.ndim].add(1.0)
    return call


CELLS = ["star13_512.step1", "star13_512.smooth4", "mg27_512.apply_4chip"]


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_control_is_not_correct(tiny_root, cell):
    """The program's own bfloat16 path (grid and every stage stored in
    bfloat16) in place of the configured f32."""
    r = _run(tiny_root, cell, input_dtype="bfloat16")
    assert not r["correct"], r["check"]


@pytest.mark.parametrize("cell", CELLS)
def test_bf16_reference_control_is_not_correct(tiny_root, cell):
    """The plain reference in the program's place, every value and
    product in bfloat16: the control where the program's bfloat16 path
    does not compile at the cell's size."""
    from bench import reference

    c = harness.find_cell(harness.load_benchmark(tiny_root), cell)
    config = harness.load_json(tiny_root / "bench", "configs", c["config"])
    traffic = harness.load_json(tiny_root / "bench", "traffic", c["traffic"])
    op = config["operator"]

    def in_place(entry):
        return lambda u: reference.apply(
            u, op["offsets"], op["weights"], traffic["applications"],
            config["boundary"], "bfloat16",
        )

    r = _run(tiny_root, cell, wrap_entry=in_place)
    assert not r["correct"], r["check"]
    # In f32 the same reference passes: the check separates precisions,
    # not implementations.
    def in_place_f32(entry):
        return lambda u: reference.apply(
            u, op["offsets"], op["weights"], traffic["applications"],
            config["boundary"], "float32",
        )

    assert _run(tiny_root, cell, wrap_entry=in_place_f32)["correct"]


@pytest.mark.parametrize("fault", [_unchanged, _half, _altered])
@pytest.mark.parametrize("cell", ["star13_512.step1", "mg27_512.apply_4chip"])
def test_fault_is_not_correct(tiny_root, cell, fault):
    r = _run(tiny_root, cell, wrap_entry=fault)
    assert not r["correct"], (fault.__name__, r["check"])


def test_exchange_left_out_is_not_correct(tiny_root, monkeypatch):
    """The sharded cell with its halo exchange between chips left out:
    every ``ppermute`` of the column-sharded launch delivers zeros."""
    import jax
    import jax.numpy as jnp

    from repro.parallel import shard_columns

    shard_columns._build_sharded.cache_clear()
    monkeypatch.setattr(jax.lax, "ppermute",
                        lambda x, *a, **k: jnp.zeros_like(x))
    try:
        r = _run(tiny_root, "mg27_512.apply_4chip")
    finally:
        shard_columns._build_sharded.cache_clear()
    assert not r["correct"], r["check"]
