"""Measurement harness: wall-clock timing of jax callables, done right.

Every host-timed number in this repo — the ``repro.plan.tune`` autotune
loop first of all — flows through :func:`measure`, so the methodology is
defined once:

* **warm-up excluded**: the first ``warmup`` calls run (and block) before
  the clock starts, so jit tracing/compilation and first-touch allocation
  never pollute the sample;
* **block-until-ready**: each timed call is wrapped in
  ``jax.block_until_ready`` on its result, so asynchronous dispatch cannot
  under-report;
* **median-of-n with IQR**: the reported statistic is the median of
  ``reps`` timed calls with the interquartile range as the noise bar —
  robust against the one GC pause / SMT neighbor that ruins a mean.

:func:`device_fingerprint` is the identity of the thing being measured:
backend + device kind + device count + jax version.  The
``TunedPlanDB`` keys measurements by it so numbers taken on one backend
are never served to another.

jax is imported lazily so that importing this module (e.g. via
``repro.plan``) never fixes the process's device topology before a
caller has set ``XLA_FLAGS``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Sequence

from .. import obs

__all__ = ["TimingResult", "measure", "device_fingerprint"]


@dataclass(frozen=True)
class TimingResult:
    """Median-of-n wall-clock sample of one callable (seconds)."""

    median_s: float
    iqr_s: float                      # q75 - q25 of the timed reps
    times_s: tuple[float, ...]        # every timed rep, in call order
    reps: int
    warmup: int

    @property
    def median_us(self) -> float:
        return self.median_s * 1e6

    @property
    def median_ms(self) -> float:
        return self.median_s * 1e3

    def to_dict(self) -> dict:
        return {
            "median_s": self.median_s,
            "iqr_s": self.iqr_s,
            "times_s": list(self.times_s),
            "reps": self.reps,
            "warmup": self.warmup,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "TimingResult":
        return cls(
            median_s=float(d["median_s"]),
            iqr_s=float(d["iqr_s"]),
            times_s=tuple(float(t) for t in d["times_s"]),
            reps=int(d["reps"]),
            warmup=int(d["warmup"]),
        )


def _median_iqr(times: Sequence[float]) -> tuple[float, float]:
    xs = sorted(times)
    n = len(xs)
    mid = n // 2
    median = xs[mid] if n % 2 else 0.5 * (xs[mid - 1] + xs[mid])

    def quantile(q: float) -> float:
        # Linear interpolation between closest ranks (numpy's default).
        pos = q * (n - 1)
        lo = int(pos)
        hi = min(lo + 1, n - 1)
        return xs[lo] + (pos - lo) * (xs[hi] - xs[lo])

    return median, quantile(0.75) - quantile(0.25)


def measure(
    fn: Callable[[], object],
    reps: int = 5,
    warmup: int = 1,
) -> TimingResult:
    """Time ``fn()`` properly: ``warmup`` un-timed calls (jit compile,
    allocator warm-up), then ``reps`` timed calls, each blocked on its
    result, reported as median + IQR.

    ``fn`` returns whatever it computes (an array, a pytree, or plain
    Python data — ``jax.block_until_ready`` passes non-array leaves
    through), so callers time exactly the expression they care about.
    """
    import time

    import jax

    if reps < 1:
        raise ValueError(f"reps must be >= 1, got {reps}")
    if warmup < 0:
        raise ValueError(f"warmup must be >= 0, got {warmup}")
    times: list[float] = []
    sp = obs.span("measure") if obs.enabled() else None
    if sp is not None:
        sp.__enter__()
    try:
        for _ in range(warmup):
            jax.block_until_ready(fn())
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            times.append(time.perf_counter() - t0)
    finally:
        if sp is not None:
            measured_ns = int(sum(times) * 1e9) if times else 0
            sp.set(reps=reps, warmup=warmup, measured_ns=measured_ns)
            sp.__exit__(None, None, None)
            obs.add("measured_ns", measured_ns)
    median, iqr = _median_iqr(times)
    return TimingResult(
        median_s=median,
        iqr_s=iqr,
        times_s=tuple(times),
        reps=int(reps),
        warmup=int(warmup),
    )


def device_fingerprint() -> str:
    """Stable identity of the local accelerator configuration:
    ``backend:device_kind:xN:jax-VERSION``.  Two processes with the same
    fingerprint are measuring the same hardware through the same stack —
    the precondition for sharing tuned-plan measurements."""
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind.replace(" ", "_") if devs else "none"
    return (
        f"{jax.default_backend()}:{kind}:x{len(devs)}:jax-{jax.__version__}"
    )
