"""Differential stencil-program fuzzer (PR 10).

Random *legal* stencil programs — 1–3 dims, asymmetric (including fully
one-sided) halos, mixed dirichlet/neumann/reflect/robin or all-periodic
boundaries, mixed f32/bf16/int8-quantized stage storage, fusion depths
1–4, ring and trapezoid frontier windows — executed on the sweep engine
and checked against the :mod:`repro.kernels.ref` oracle within a
per-dtype tolerance band derived from the §15 documentation:

* f32-only chains: summation-order noise only (tiny absolute band);
* each bf16 stage contributes one bf16 ulp of its stage maximum,
  amplified by the downstream stages' L1 weight norms (× robin gain);
* each int8-quantized stage contributes one code (``scale`` — ½ code
  half-even rounding + ½ code for compile-order .5-boundary flips),
  amplified the same way.

Ring and trapezoid launches of the same program must additionally be
**bit-wise identical** (the §14 contract), so every fuzz case doubles as
a window-parity case.

When ``hypothesis`` is installed the generator runs under ``@given``;
this container does not ship it, so the committed seed corpus under
``tests/corpus/`` replays the same generator deterministically — the
corpus is the CI floor, hypothesis the opportunistic explorer.
Regenerate the corpus with ``python tests/test_program_fuzz.py``.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as hyp_st
    HAVE_HYPOTHESIS = True
except ImportError:  # the container ships no hypothesis — corpus only
    HAVE_HYPOTHESIS = False

CORPUS_DIR = os.path.join(os.path.dirname(__file__), "corpus")

if __name__ == "__main__":
    # Direct execution (corpus regeneration): the ISA pin must land
    # before the first jax import, exactly as conftest does for pytest.
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), os.pardir, "src"))
    from repro.runtime import isa

    isa.pin_xla_flags()

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro import ir  # noqa: E402
from repro.entry import run  # noqa: E402
from repro.kernels.ref import (  # noqa: E402
    dequantize_ref,
    quantize_ref,
    stencil_ref,
)


# -- the generator ---------------------------------------------------------


def gen_spec(seed: int) -> dict:
    """One random legal program spec, fully determined by ``seed``."""
    rng = np.random.default_rng(int(seed))
    d = int(rng.integers(1, 4))
    shape = tuple(int(rng.integers(2, 5)) * 8 for _ in range(d))
    T = int(rng.integers(1, 5))
    stages = []
    for _ in range(T):
        n_taps = int(rng.integers(2, 6))
        offs = {(0,) * d}
        while len(offs) < n_taps:
            offs.add(tuple(int(o) for o in rng.integers(-2, 3, size=d)))
        if rng.random() < 0.25:
            # Fully one-sided (W-1, 0) halo: every tap trails the point.
            offs = {tuple(-abs(o) for o in off) for off in offs}
        offs = sorted(offs)
        wts = [round(float(w), 3)
               for w in rng.uniform(-0.5, 0.5, len(offs))]
        stages.append({"offsets": [list(o) for o in offs], "weights": wts})
    r = rng.random()
    if r < 0.25:
        # Periodic is all-or-nothing per program (torus semantics).
        bcs: list = [["periodic", 0.0]] * T
    elif r < 0.6:
        menu = ("zero", "dirichlet", "neumann", "reflect", "robin")
        bcs = []
        for _ in range(T):
            kind = menu[int(rng.integers(0, len(menu)))]
            if kind == "zero":
                bcs.append(None)
            elif kind == "dirichlet":
                bcs.append(["dirichlet",
                            round(float(rng.uniform(-1, 1)), 3)])
            elif kind == "robin":
                bcs.append(["robin",
                            [round(float(rng.uniform(-1, 1)), 3),
                             round(float(rng.uniform(-1, 1)), 3)]])
            else:
                bcs.append([kind, 0.0])
    else:
        bcs = [None] * T
    for j, bc in enumerate(bcs):
        if bc is not None and bc[0] == "reflect" and not _symmetric(
            stages[j]["offsets"]
        ):
            # ir.verify refuses reflect on an asymmetric stage halo (the
            # reflected taps would leave the window): not a legal program.
            bcs[j] = ["neumann", 0.0]
    dtypes: list = []
    quants: list = []
    for j in range(T):
        q = rng.random()
        if j < T - 1 and q < 0.2:
            dtypes.append("int8")
            quants.append([float(rng.choice([0.02, 0.05, 0.1])),
                           int(rng.integers(-8, 9))])
        elif j < T - 1 and q < 0.4:
            dtypes.append("bfloat16")
            quants.append(None)
        else:
            dtypes.append(None)
            quants.append(None)
    tile = list(shape)
    a = int(rng.integers(0, d))
    if rng.random() < 0.5:
        tile[a] = shape[a] // 2
    return {
        "seed": int(seed),
        "d": d,
        "shape": list(shape),
        "stages": stages,
        "bcs": bcs,
        "dtypes": dtypes,
        "quants": quants,
        "window_kind": "ring" if rng.random() < 0.5 else "trapezoid",
        "tile": tile,
    }


def _symmetric(offsets) -> bool:
    """Whether a stage's halo reaches equally far to both sides on every
    axis — what a reflect boundary needs."""
    offs = np.asarray(offsets)
    return bool(np.array_equal(np.maximum(-offs.min(axis=0), 0),
                               np.maximum(offs.max(axis=0), 0)))


def spec_classes(spec: dict) -> set[str]:
    """Coverage labels of one spec — what the corpus must jointly span."""
    out = {f"{spec['d']}d", f"T{len(spec['stages'])}",
           spec["window_kind"]}
    for bc in spec["bcs"]:
        out.add(bc[0] if bc else "zero")
    for dt in spec["dtypes"]:
        if dt:
            out.add(dt)
    for st in spec["stages"]:
        offs = np.asarray(st["offsets"])
        if offs.size and offs.max() <= 0 and offs.min() < 0:
            out.add("one_sided")
    return out


# -- the differential check ------------------------------------------------


def _build_program(spec: dict):
    return ir.chain_program(
        [(np.asarray(st["offsets"], dtype=np.int64), st["weights"])
         for st in spec["stages"]],
        spec["d"],
        boundary=[
            None if bc is None else (bc[0], bc[1] if not
                                     isinstance(bc[1], list)
                                     else tuple(bc[1]))
            for bc in spec["bcs"]
        ],
        dtypes=spec["dtypes"],
        quants=[None if q is None else (q[0], q[1])
                for q in spec["quants"]],
    )


def _oracle(u, spec):
    """Stage-stacked :func:`stencil_ref` with the §15 storage round-trips
    spelled host-side; returns the reference and per-stage |max| values
    (the band's amplitude inputs)."""
    ref = jnp.asarray(u, jnp.float32)
    maxima = []
    for st, bc, dt, qn in zip(spec["stages"], spec["bcs"],
                              spec["dtypes"], spec["quants"]):
        kind, val = ("zero", 0.0) if bc is None else (bc[0], bc[1])
        ref = stencil_ref(ref, np.asarray(st["offsets"], dtype=np.int64),
                          st["weights"], boundary=kind, value=val)
        if qn is not None:
            ref = dequantize_ref(quantize_ref(ref, qn[0], qn[1]),
                                 qn[0], qn[1])
        elif dt == "bfloat16":
            ref = ref.astype(jnp.bfloat16).astype(jnp.float32)
        maxima.append(float(jnp.max(jnp.abs(ref))))
    return ref, maxima


def _band(spec: dict, maxima: list[float]) -> float:
    """The documented §15 tolerance band for this chain (see module doc)."""
    T = len(spec["stages"])
    amps = []
    for st, bc in zip(spec["stages"], spec["bcs"]):
        l1 = float(np.sum(np.abs(st["weights"])))
        if bc is not None and bc[0] == "robin":
            l1 *= max(1.0, abs(float(bc[1][0])))
        amps.append(l1)
    tol = 1e-4 * (1.0 + max(maxima, default=1.0))
    for j in range(T):
        amp = math.prod(amps[j + 1:])
        if spec["quants"][j] is not None:
            tol += float(spec["quants"][j][0]) * 1.0 * amp
        elif spec["dtypes"][j] == "bfloat16":
            tol += maxima[j] * 2.0 ** -7 * amp
    return tol


def run_case(spec: dict) -> None:
    prog = _build_program(spec)
    key = jax.random.PRNGKey(spec["seed"])
    u = jax.random.normal(key, tuple(spec["shape"]), jnp.float32)
    got = run(prog, u, tile=tuple(spec["tile"]),
              window_kind=spec["window_kind"], interpret=True)
    ref, maxima = _oracle(u, spec)
    tol = _band(spec, maxima)
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref)))
    assert err <= tol, (
        f"seed {spec['seed']}: engine deviates {err:.3e} > band {tol:.3e} "
        f"(classes {sorted(spec_classes(spec))})"
    )
    # §14 window parity: the other frontier layout is bit-wise identical.
    other = ("trapezoid" if spec["window_kind"] == "ring" else "ring")
    flip = run(prog, u, tile=tuple(spec["tile"]), window_kind=other,
               interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(flip)), (
        f"seed {spec['seed']}: ring/trapezoid launches differ bit-wise"
    )


# -- corpus replay (always on) --------------------------------------------


def _corpus_seeds() -> list[int]:
    if not os.path.isdir(CORPUS_DIR):
        return []
    seeds = []
    for name in sorted(os.listdir(CORPUS_DIR)):
        if name.endswith(".json"):
            with open(os.path.join(CORPUS_DIR, name)) as f:
                seeds.append(int(json.load(f)["seed"]))
    return seeds


_SEEDS = _corpus_seeds()


@pytest.mark.parametrize("seed", _SEEDS)
def test_corpus_replay(seed):
    run_case(gen_spec(seed))


def test_corpus_present_and_covering():
    """The committed corpus exists and jointly spans every class the
    fuzzer generates — dims, depths, window kinds, the §13/§15 boundary
    menu, the storage dtypes, and one-sided halos."""
    assert len(_SEEDS) >= 16, "seed corpus missing or too small"
    covered: set[str] = set()
    for seed in _SEEDS:
        covered |= spec_classes(gen_spec(seed))
    need = {
        "1d", "2d", "3d", "T1", "T2", "T3", "T4", "ring", "trapezoid",
        "zero", "dirichlet", "neumann", "reflect", "periodic", "robin",
        "bfloat16", "int8", "one_sided",
    }
    assert need <= covered, f"corpus misses classes: {sorted(need-covered)}"


# -- hypothesis exploration (opportunistic) -------------------------------


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(hyp_st.integers(min_value=0, max_value=2 ** 31 - 1))
    def test_fuzz_hypothesis(seed):
        run_case(gen_spec(seed))


# -- corpus regeneration ---------------------------------------------------


def regenerate_corpus(target: int = 24, scan: int = 4000) -> list[dict]:
    """Greedy cover: scan seeds until every class is covered, then pad to
    ``target`` cases.  Writes one JSON per kept seed under tests/corpus/."""
    need = {
        "1d", "2d", "3d", "T1", "T2", "T3", "T4", "ring", "trapezoid",
        "zero", "dirichlet", "neumann", "reflect", "periodic", "robin",
        "bfloat16", "int8", "one_sided",
    }
    kept: list[dict] = []
    covered: set[str] = set()
    for seed in range(scan):
        spec = gen_spec(seed)
        cls = spec_classes(spec)
        if not (cls - covered) and len(kept) >= target:
            continue
        if not (cls - covered) and covered >= need:
            continue
        run_case(spec)  # gen_spec emits only legal programs: never skip
        kept.append(spec)
        covered |= cls
        if covered >= need and len(kept) >= target:
            break
    assert covered >= need, f"scan too small; missing {need - covered}"
    os.makedirs(CORPUS_DIR, exist_ok=True)
    for name in os.listdir(CORPUS_DIR):
        if name.endswith(".json"):
            os.remove(os.path.join(CORPUS_DIR, name))
    for spec in kept:
        path = os.path.join(CORPUS_DIR, f"seed_{spec['seed']:05d}.json")
        with open(path, "w") as f:
            json.dump(
                {"seed": spec["seed"],
                 "classes": sorted(spec_classes(spec))},
                f, indent=2,
            )
            f.write("\n")
    return kept


if __name__ == "__main__":
    cases = regenerate_corpus()
    print(f"wrote {len(cases)} corpus cases to {CORPUS_DIR}")
