"""Zero-dependency telemetry for the plan→tune→launch pipeline (DESIGN.md §12).

Public API (all safe to call with recording disabled — one predicate
check, no allocation):

* ``obs.enabled()`` / ``obs.active()`` — is a recorder installed?
* ``obs.tracing()`` — is a recorder installed or a ``jax.profiler``
  session collecting?
* ``obs.span(name, **args)`` — context-managed timed region (a bare
  ``jax.profiler.TraceAnnotation`` while only a profiler collects),
* ``obs.call_span(name)`` — the root span of one call, numbered,
* ``obs.add(name, value)`` — bump a counter,
* ``obs.event(name, **args)`` — instant event,
* ``obs.recording(path)`` — scoped recorder, trace written on exit,
* ``Recorder`` — the span/counter/event store itself.

Setting ``REPRO_TRACE=path.json`` before this package is first imported
installs a process-wide recorder flushed at interpreter exit.  Traces
are Chrome/Perfetto ``trace_event`` JSON (:mod:`repro.obs.trace_event`)
and reconcile with ``python -m repro.obs.report``.
"""

from .recorder import (  # noqa: F401
    NULL_SPAN,
    Recorder,
    Span,
    _activate_from_env,
    active,
    add,
    call_span,
    enabled,
    event,
    recording,
    span,
    tracing,
)
from .trace_event import (  # noqa: F401
    load_trace,
    to_trace_events,
    validate_trace,
    write_trace,
)

__all__ = [
    "NULL_SPAN",
    "Recorder",
    "Span",
    "active",
    "add",
    "call_span",
    "enabled",
    "event",
    "load_trace",
    "recording",
    "span",
    "to_trace_events",
    "tracing",
    "validate_trace",
    "write_trace",
]

_activate_from_env()
