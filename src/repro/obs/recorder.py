"""Process-local telemetry recorder: structured spans, counters, events.

The paper's whole argument is that cache behavior is *predictable* —
lattice geometry prices the traffic before the run — and §11 closed the
loop by measuring.  This module makes the evidence trail *visible at
runtime* (DESIGN.md §12): every layer of the plan→tune→launch pipeline
records **spans** (plan, cache lookup, tune race, kernel launch, halo
exchange, timing harness) and **counters** (plan_cache_hit/miss,
tunedb_hit/miss/degrade, interpret_fallback, launches, modeled_bytes,
modeled_flops, measured_ns, ...) into one :class:`Recorder`, exported as
Chrome/Perfetto ``trace_event`` JSON by :mod:`repro.obs.trace_event` and
reconciled by ``python -m repro.obs.report``.

**Disabled is the default and costs one predicate check.**  The
module-level :func:`span` / :func:`add` / :func:`event` helpers read one
module global; when no recorder is installed they return a shared
singleton null span (or ``None``) without allocating anything, so
instrumented hot paths — the sub-ms warm plan-cache hit, the kernel
launch wrapper — pay a pointer compare.  Hot callers that would build a
kwargs dict for span arguments guard with ``if obs.enabled():`` first,
keeping even that allocation off the disabled path.

**A profiler session sees the spans too.**  While a ``jax.profiler``
session is collecting and no recorder is installed, :func:`span` returns
a bare ``jax.profiler.TraceAnnotation``: the span reaches the profiler's
own trace, on the caller's thread, and nothing is stored or counted in
Python.  The session is detected with one call of the profiler's own
``TraceAnnotation.is_enabled`` (:func:`tracing`); :func:`add` and
:func:`event` stay recorder-only, and sites guarded by :func:`enabled`
(per-launch pricing, cache lookups) stay off the profiled path.

Enabling, in precedence order (innermost wins; recorders nest):

* ``REPRO_TRACE=path.json`` in the environment — a process-wide recorder
  installed at first ``repro.obs`` import, flushed to ``path.json`` at
  interpreter exit (:func:`_activate_from_env`);
* ``with obs.recording("path.json") as rec:`` — scoped recorder, trace
  written on exit (around one call, it records that call).

This module is dependency-free (stdlib only) and never imports jax; the
optional ``jax.profiler`` bridge in :mod:`repro.obs.trace_event` only
activates when jax is *already* imported by someone else.
"""

from __future__ import annotations

import atexit
import itertools
import os
import sys
import threading
import time
from contextlib import contextmanager

__all__ = [
    "Recorder",
    "Span",
    "active",
    "add",
    "call_span",
    "enabled",
    "event",
    "recording",
    "span",
    "tracing",
]

_ENV = "REPRO_TRACE"

# The single module global every disabled-path check reads.  ``None``
# means recording is off and all helpers are no-ops.
_active: "Recorder | None" = None

# The per-process number of each traced call's root span (call_span).
_calls = itertools.count(1)


def _now_us() -> float:
    return time.perf_counter_ns() / 1e3


class _NullSpan:
    """The shared no-op span: entering, exiting, and ``set`` do nothing.
    A single module-level instance is returned by every disabled-path
    :func:`span` call, so the no-op path allocates nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()


def _resolve_profiling() -> bool:
    """Whether a ``jax.profiler`` session is collecting.  Until jax is
    imported by someone else there is none; from then on the check is the
    profiler's own ``TraceAnnotation.is_enabled``, installed here as
    :data:`_profiling` so that later calls go to it directly."""
    global _profiling
    if "jax" not in sys.modules:
        return False
    from jax.profiler import TraceAnnotation

    _profiling = TraceAnnotation.is_enabled
    return _profiling()


# ``() -> bool``: is a profiler session collecting?  Starts as the
# resolver, which replaces itself once jax is importable.
_profiling = _resolve_profiling


class _Annotation:
    """A span with no recorder while a profiler session collects: a bare
    ``jax.profiler.TraceAnnotation`` whose arguments become the event's
    metadata.  Nothing is kept in Python."""

    __slots__ = ("_me",)

    def __init__(self, name: str, args: dict):
        from jax.profiler import TraceAnnotation

        self._me = TraceAnnotation(name, **args)

    def __enter__(self) -> "_Annotation":
        self._me.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._me.__exit__(*exc)
        return False

    def set(self, **args) -> "_Annotation":
        self._me.set_metadata(**args)
        return self


class Span:
    """One recorded span: a named, timed region with key/value args.

    Use as a context manager (``with rec.span("plan", key=...) as sp:``);
    :meth:`set` attaches outcome args discovered mid-span (the tune
    winner, the chosen fusion depth).  Finished spans append to the
    recorder; the Chrome exporter turns them into ``ph: "X"`` complete
    events.
    """

    __slots__ = ("name", "cat", "args", "ts_us", "dur_us", "tid", "_rec",
                 "_jax_ctx")

    def __init__(self, rec: "Recorder", name: str, cat: str, args: dict):
        self.name = name
        self.cat = cat
        self.args = args
        self.ts_us = 0.0
        self.dur_us = 0.0
        self.tid = 0
        self._rec = rec
        self._jax_ctx = None

    def set(self, **args) -> "Span":
        self.args.update(args)
        return self

    def __enter__(self) -> "Span":
        self.tid = threading.get_ident()
        if self._rec.jax_bridge and "jax" in sys.modules:
            # Bridge into the XLA profiler timeline so repro spans line
            # up with jax's own trace when both are captured.  Only when
            # jax is already imported — observability must never pull in
            # (and topology-fix) the accelerator stack.
            try:
                import jax.profiler

                ctx = jax.profiler.TraceAnnotation(self.name)
                ctx.__enter__()
                self._jax_ctx = ctx
            except Exception:
                self._jax_ctx = None
        self.ts_us = _now_us()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_us = _now_us() - self.ts_us
        if self._jax_ctx is not None:
            try:
                self._jax_ctx.__exit__(*exc)
            except Exception:
                pass
            self._jax_ctx = None
        self._rec._finish(self)
        return False


class Recorder:
    """Process-local span/counter/event store for one recording session.

    Thread-safe (appends under one lock).  ``counters`` are monotone
    totals; every update is also sampled with a timestamp so the Chrome
    exporter can emit ``ph: "C"`` counter tracks.  ``path`` is where
    :meth:`write` puts the trace by default (also used by the
    ``REPRO_TRACE`` atexit flush).
    """

    def __init__(self, path: str | None = None, jax_bridge: bool = True):
        self.path = path
        self.jax_bridge = bool(jax_bridge)
        self.pid = os.getpid()
        self.spans: list[Span] = []
        self.counters: dict[str, int] = {}
        self.counter_samples: list[tuple[float, str, int]] = []
        self.events: list[dict] = []
        self.t0_us = _now_us()
        self._lock = threading.Lock()

    # -- recording ---------------------------------------------------------

    def span(self, name: str, cat: str = "repro", **args) -> Span:
        return Span(self, name, cat, args)

    def _finish(self, sp: Span) -> None:
        with self._lock:
            self.spans.append(sp)

    def add(self, name: str, value: int = 1) -> int:
        with self._lock:
            total = self.counters.get(name, 0) + int(value)
            self.counters[name] = total
            self.counter_samples.append((_now_us(), name, total))
        return total

    def event(self, name: str, cat: str = "repro", **args) -> None:
        with self._lock:
            self.events.append({
                "name": name,
                "cat": cat,
                "ts_us": _now_us(),
                "tid": threading.get_ident(),
                "args": args,
            })

    # -- export ------------------------------------------------------------

    def to_trace_events(self) -> dict:
        from .trace_event import to_trace_events

        return to_trace_events(self)

    def write(self, path: str | None = None) -> str:
        from .trace_event import write_trace

        return write_trace(self, path or self.path)


# -- module-level no-op-able helpers ----------------------------------------


def active() -> Recorder | None:
    """The currently installed recorder, or ``None`` when disabled."""
    return _active


def enabled() -> bool:
    """One predicate check — the guard hot paths use before building
    span kwargs."""
    return _active is not None


def tracing() -> bool:
    """Whether a span would reach a trace: a recorder is installed, or a
    ``jax.profiler`` session is collecting.  The guard for span arguments
    that are cheap enough to send to the profiler."""
    return _active is not None or _profiling()


def span(name: str, cat: str = "repro", **args):
    """A span on the active recorder; with none, a bare profiler
    annotation while a ``jax.profiler`` session collects, else the shared
    null span (no allocation on that path when called with no keyword
    args — hot callers guard kwargs with :func:`enabled` or
    :func:`tracing`)."""
    rec = _active
    if rec is not None:
        return rec.span(name, cat, **args)
    if _profiling():
        return _Annotation(name, args)
    return NULL_SPAN


def call_span(name: str):
    """:func:`span` for the root of one call, carrying ``call=<n>``: a
    per-process number, drawn only while tracing, that joins all spans of
    one call.  The shared null span when nothing is tracing."""
    if not tracing():
        return NULL_SPAN
    return span(name, call=next(_calls))


def add(name: str, value: int = 1) -> None:
    """Bump a counter on the active recorder; no-op when disabled."""
    rec = _active
    if rec is None:
        return
    rec.add(name, value)


def event(name: str, cat: str = "repro", **args) -> None:
    """Record an instant event on the active recorder; no-op when
    disabled (guard kwargs with :func:`enabled` on hot paths)."""
    rec = _active
    if rec is None:
        return
    rec.event(name, cat, **args)


def _install(rec: Recorder | None) -> Recorder | None:
    """Swap the active recorder, returning the previous one."""
    global _active
    prev = _active
    _active = rec
    return prev


@contextmanager
def recording(path: str | None = None, jax_bridge: bool = True):
    """Scoped recording: install a fresh :class:`Recorder`, yield it, and
    on exit write the trace to ``path`` (when given) and restore whatever
    recorder — possibly none — was active before.  Nests: an inner
    ``recording`` shadows an outer one (spans go to the innermost)."""
    rec = Recorder(path=path, jax_bridge=jax_bridge)
    prev = _install(rec)
    try:
        yield rec
    finally:
        _install(prev)
        if path is not None:
            rec.write(path)


# -- REPRO_TRACE env activation ---------------------------------------------

_env_recorder: Recorder | None = None


def _flush_env_recorder() -> None:
    """atexit hook for the ``REPRO_TRACE`` recorder: write the trace once
    at interpreter exit (idempotent; safe to call early in tests)."""
    global _env_recorder
    rec, _env_recorder = _env_recorder, None
    if rec is not None:
        if _active is rec:
            _install(None)
        rec.write()


def _activate_from_env() -> Recorder | None:
    """Install a process-wide recorder when ``REPRO_TRACE=path.json`` is
    set (called at first ``repro.obs`` import).  Returns the recorder, or
    ``None`` when the env var is unset or a recorder is already active."""
    global _env_recorder
    path = os.environ.get(_ENV)
    if not path or _active is not None:
        return None
    _env_recorder = Recorder(path=path)
    _install(_env_recorder)
    atexit.register(_flush_env_recorder)
    return _env_recorder
