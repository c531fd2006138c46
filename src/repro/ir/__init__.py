"""Stencil-program IR (DESIGN.md §13): bounds-inferred programs with
boundary ops, lowered onto the sweep engine.

The package is numpy-only (``ops`` + ``infer`` + ``verify`` + ``lower``)
and safe to import from the plan compiler; programs run through
:func:`repro.entry.run`.
"""

from .infer import infer_bounds, infer_halos, stage_halos, suffix_halos
from .lower import IRLowerError, Lowered, lower
from .ops import (
    BC_KINDS,
    Apply,
    Boundary,
    Bounds,
    Combine,
    Dequantize,
    Load,
    Program,
    Quantize,
    Store,
    chain_program,
    normalize_bc,
    plan_program_key,
    rhs_program,
    stencil_program,
    summarize_program,
)
from .verify import IRVerifyError, verify

__all__ = [
    "BC_KINDS",
    "Apply",
    "Boundary",
    "Bounds",
    "Combine",
    "Dequantize",
    "IRLowerError",
    "IRVerifyError",
    "Load",
    "Lowered",
    "Program",
    "Quantize",
    "Store",
    "chain_program",
    "infer_bounds",
    "infer_halos",
    "lower",
    "normalize_bc",
    "plan_program_key",
    "rhs_program",
    "stage_halos",
    "stencil_program",
    "suffix_halos",
    "summarize_program",
    "verify",
]


def __getattr__(name):
    # Forwarder for bench/harness.py's make_entry, which calls
    # ir.run_program: resolved once to repro.entry.run, then a global.
    if name == "run_program":
        from repro.entry import run

        globals()[name] = run
        return run
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
