"""From a ``jax.profiler`` trace to the numbers the per-layer metrics read.

A TPU trace (``.xplane.pb``) holds one plane per chip, ``/device:TPU:<id>``,
whose line ``XLA Ops`` lists every operation the chip ran, named by its
HLO text (``%x = f32[...] custom-call(...), custom_call_target=...``), and
one host plane, ``/host:CPU``, whose lines hold the host's spans: the
benchmark's own ``bench.dispatch`` and ``bench.block`` annotations and
the runtime's (``PjitFunction(...)``, ``PJRT_LoadedExecutable_Execute``).

Each device operation falls in one of three kinds:

* ``kernel``: a Mosaic kernel, ``custom_call_target="tpu_custom_call"``
  (matched by the kind of op, not by its name);
* ``collective``: a collective-permute, all-reduce, all-gather,
  all-to-all, reduce-scatter, send or recv (or their start/done halves);
* ``xla``: every other operation (pads, slices, fusions, copies).

Per device the reduction sums each kind's durations, and takes the busy
time as the union of the intervals of all operations.  The window is the
host's, from the first ``bench.dispatch`` to the end of the last
``bench.block``.  Idle gaps on the busiest device are put on the host's
clock (the device's first operation is taken to start when the host's
first ``PJRT_LoadedExecutable_Execute`` does) and labelled by the
innermost host span on the annotated thread at the gap's middle.
"""

from __future__ import annotations

import bisect
import re

__all__ = [
    "device_events",
    "hlo_opcode",
    "op_kind",
    "reduce",
    "reduce_file",
    "union_length",
]

_COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|all-to-all|reduce-scatter"
    r"|collective-broadcast|send|recv)(-start|-done)?$"
)
DEVICE_PREFIX = "/device:TPU:"
# The line of ops the core runs one at a time.  ``Async XLA Ops`` also
# holds each async collective's time in flight, which overlaps other
# work and is not time the core is busy.
OPS_LINE = "XLA Ops"
ANNOTATIONS = ("bench.dispatch", "bench.block")
EXECUTE = "PJRT_LoadedExecutable_Execute"


def hlo_opcode(name: str) -> str:
    """The opcode of an op named by its HLO text, ``%x = <type>
    <opcode>(...)``; a name that is not HLO text is its own opcode."""
    _, eq, rest = name.partition(" = ")
    if not eq:
        return name.split("(", 1)[0].strip()
    rest = rest.lstrip()
    if rest.startswith("("):  # a tuple type: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return rest.lstrip().split("(", 1)[0].strip()


def op_kind(name: str) -> str:
    """``kernel``, ``collective`` or ``xla``."""
    opcode = hlo_opcode(name)
    if opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' in name:
        return "kernel"
    if _COLLECTIVE.match(opcode):
        return "collective"
    return "xla"


def union_length(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total = 0.0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def device_events(profile):
    """``({device_id: [(name, start_ns, dur_ns)]}, [(name, start_ns,
    dur_ns, thread)])`` from a ``jax.profiler.ProfileData``."""
    devices, host = {}, []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = int(plane.name[len(DEVICE_PREFIX):])
            ops = devices.setdefault(dev, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events
                    )
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(
                    (e.name, e.start_ns, e.duration_ns, line.name)
                    for e in line.events
                )
    return devices, host


def _label(t, spans, starts):
    """The innermost span of ``spans`` (``(start, end, name)`` of one
    thread, sorted by start, so nested) that holds time ``t``: the latest
    starting one that has not ended; "host loop" where none does."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        s, e, name = spans[i]
        if e >= t:
            return name
    return "host loop"


def reduce(devices: dict, host: list, use=None, top=10) -> dict:
    """The numbers the metric readers take, from :func:`device_events`'
    output.  ``use`` lists the device ids of the run (default: all).

    Returns, in seconds: per device its busy time and each kind's sum
    (``per_device``), the mean busy time over the devices (``busy_s``),
    the window (``window_s``), the id of the busiest device
    (``busiest``), the ``top`` operations by total time on it
    (``top_ops``) and its idle time by host label (``idle_gaps``)."""
    ids = sorted(devices if use is None else use)
    marks = [(s, s + d) for name, s, d, _ in host if name in ANNOTATIONS]
    if not marks:
        raise ValueError("the trace holds no bench.dispatch/bench.block span")
    w0 = min(s for s, _ in marks)
    w1 = max(e for _, e in marks)
    per_device = {}
    for dev in ids:
        ops = devices.get(dev, [])
        sums = {"kernel": 0.0, "collective": 0.0, "xla": 0.0}
        for name, _, dur in ops:
            sums[op_kind(name)] += dur
        busy = union_length([(s, s + d) for _, s, d in ops])
        per_device[dev] = {
            "busy_s": busy * 1e-9,
            "kernel_s": sums["kernel"] * 1e-9,
            "collective_s": sums["collective"] * 1e-9,
            "xla_s": sums["xla"] * 1e-9,
            "ops": len(ops),
        }
    busiest = max(ids, key=lambda d: per_device[d]["busy_s"])
    totals = {}
    for name, _, dur in devices.get(busiest, []):
        key = hlo_opcode(name) if op_kind(name) == "xla" else op_kind(name)
        key = _short(name, key)
        totals[key] = totals.get(key, 0.0) + dur * 1e-9
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return {
        "per_device": per_device,
        "busiest": busiest,
        "busy_s": sum(p["busy_s"] for p in per_device.values()) / len(ids),
        "window_s": (w1 - w0) * 1e-9,
        "top_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": _idle_by_label(devices.get(busiest, []), host, w0, w1,
                                    top),
    }


def _short(name: str, key: str) -> str:
    """A readable op name for the breakdown: the HLO result name and the
    kind (``%pad.0 pad``, ``%_stencil_call.1 kernel``)."""
    lhs = name.partition(" = ")[0].strip()
    return f"{lhs} {key}" if lhs and lhs != name else key


def _idle_by_label(ops, host, w0, w1, top):
    if not ops:
        return []
    busy = _merged([(s, s + d) for _, s, d in ops])
    execs = sorted(s for name, s, _, _ in host if name == EXECUTE and s >= w0)
    # Device clock to host clock: the device sits idle before the first
    # call, so its first op starts when the host first enqueues one.
    shift = (execs[0] - busy[0][0]) if execs else 0.0
    threads = {line for name, _, _, line in host if name in ANNOTATIONS}
    spans = sorted(
        (s, s + d, name) for name, s, d, line in host if line in threads
    )
    starts = [s for s, _, _ in spans]
    gaps = []
    prev = w0 - shift
    for s, e in busy + [[w1 - shift, w1 - shift]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    by_label = {}
    for s, e in gaps:
        lo, hi = max(s + shift, w0), min(e + shift, w1)
        if hi <= lo:
            continue
        label = _label((lo + hi) / 2, spans, starts)
        by_label[label] = by_label.get(label, 0.0) + (hi - lo) * 1e-9
    return [[k, v] for k, v in
            sorted(by_label.items(), key=lambda kv: -kv[1])[:top]]


def reduce_file(path: str, devices=None) -> dict:
    """:func:`reduce` of the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData

    dev_ops, host = device_events(ProfileData.from_file(path))
    return reduce(dev_ops, host, use=devices)
