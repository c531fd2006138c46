"""Shared helpers for the benchmark harness.  Keep this module jax-free:
:func:`force_cpu_devices` must run before the first jax import."""
from __future__ import annotations

import os
import sys
import time


def force_cpu_devices(n: int = 4) -> None:
    """The §10 sharding parity gates need a multi-device CPU mesh, and the
    §14 bit-parity gates need the CPU ISA capped below FMA3; both pins
    are fixed at first jax import — call this before any benchmark
    module pulls jax in (harmless on real TPUs; both are host-platform
    flags).  The guards and rationale live in repro.runtime.isa, the
    single home of the pins (tests/test_isa_pin.py gates against
    drifting back to an inline copy); repro.runtime is jax-free."""
    src = os.path.abspath(
        os.path.join(os.path.dirname(__file__), os.pardir, "src")
    )
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.runtime import isa

    isa.pin_xla_flags(n_devices=n)


def timed(fn, *args, repeats: int = 1, **kw):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeats):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeats
    return out, dt * 1e6  # us


def emit(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")
