"""From a ``jax.profiler`` trace to the program's own layers: its host
spans, the scopes of its device ops, and the idle gap between calls.

Reads the same ``.xplane.pb`` as :mod:`bench.trace_reduce`, for three
things that reduction cannot see:

1. **Host spans.**  The engine's ``repro.obs`` spans reach the trace as
   profiler annotations on the calling thread: ``stencil_call`` (one per
   call, ``call=<n>``) over ``ir_lower``, ``plan`` (``cache=hit|miss|
   tuned``) and one ``kernel_launch`` per launch.  Each is read as a mean
   per traced call, with the root's self time (its duration less its
   children's).
2. **Device scopes.**  Each device op's ``tf_op`` metadata holds the
   ``jax.named_scope`` path it was traced under: ``stencil_embed`` (the
   launch buffer), ``stencil_wrap`` (the periodic ghost fill),
   ``stencil_halo`` (the shard exchange), ``stencil_trim`` (the output
   slice) and ``stencil_sweep`` (the Mosaic kernel's ``name=``).  A
   fusion carries its root's metadata, so it counts under its root's
   scope.  ``jax.profiler.ProfileData`` exposes event stats but not the
   stats of event metadata, so the metadata is read here from the
   XSpace's protobuf wire format (stdlib only).
3. **The idle gap, split without a clock offset.**  Each device ``XLA
   Modules`` event has a ``run_id``; the host's ``DoEnqueueProgram`` and
   ``CompleteCallbacks`` carry the same ``run_id`` and ``device_ordinal``.
   On the busiest device, between consecutive modules *n* and *n*+1, the
   host part is completion *n* (end of ``CompleteCallbacks``) to enqueue
   *n*+1 (start of ``DoEnqueueProgram``), read on the host clock alone;
   the round trip is the device's gap between the two modules less that
   host part.  The same links bound the device-to-host clock offset.

Each part is ``None`` where the trace holds nothing for it (a trace of a
program without spans or scopes, or without the runtime's links).

Run by hand::

    python3 bench/trace_program.py TRACE.xplane.pb
    python3 bench/trace_program.py --record <cell> --seed <n> --seconds <s> --out DIR

The second form runs the cell's closed loop under ``jax.profiler`` on the
chip(s) of this machine, as the benchmark's ``--trace 1`` run does, keeps
the trace under ``DIR`` and prints both reductions as one JSON line.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

if __package__ in (None, ""):  # run as a script from the checkout's root
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace_reduce  # noqa: E402

__all__ = [
    "LAUNCH_SCOPES",
    "SCOPES",
    "SPANS",
    "idle_split",
    "metrics",
    "op_scopes",
    "read_file",
    "scope_of",
    "scope_times",
    "span_means",
]

ROOT_SPAN = "stencil_call"
SPANS = ("ir_lower", "plan", "kernel_launch")
LAUNCH_SCOPES = ("stencil_embed", "stencil_wrap", "stencil_halo",
                 "stencil_trim")
SCOPES = LAUNCH_SCOPES + ("stencil_sweep",)
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"


# -- the protobuf wire format, as far as the XSpace's metadata needs ---------

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of each field of one message: an int for
    a varint, a memoryview for a length-delimited or fixed-width one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif wire == 1:
            value = buf[i:i + 8]
            i += 8
        elif wire == 5:
            value = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(view):
    key = value = None
    for number, v in _fields(view):
        if number == 1:
            key = v
        elif number == 2:
            value = v
    return key, value


def _stat(view):
    """``(metadata id, value)`` of one ``XStat`` whose value is text: a
    ``str_value`` (5), or a ``ref_value`` (7) to the stat metadata that
    holds the text, as ``("ref", id)``; None for other values."""
    mid, value = None, None
    for number, v in _fields(view):
        if number == 1:
            mid = v
        elif number == 5:
            value = _text(v)
        elif number == 7:
            value = ("ref", v)
    return mid, value


def op_scopes(path, prefix=trace_reduce.DEVICE_PREFIX) -> dict:
    """``{plane name: {op name: tf_op}}`` for every plane whose name
    starts with ``prefix``: each event metadata's name (an op's HLO text,
    as ``ProfileData`` names its events) and its ``tf_op`` stat, the
    op's scope path, without the trailing ``:<type>``.  Ops without the
    stat are left out."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    # XSpace.planes = 1; XPlane.name = 2, event_metadata = 4,
    # stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name =
    # 2, stats = 5; XStatMetadata.name = 2.
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stat_names = None, [], {}
        for pn, pv in _fields(plane):
            if pn == 2:
                name = _text(pv)
            elif pn == 4:
                events.append(_map_entry(pv)[1])
            elif pn == 5:
                key, value = _map_entry(pv)
                for sn, sv in _fields(value or b""):
                    if sn == 2:
                        stat_names[key] = _text(sv)
        if name is None or not name.startswith(prefix):
            continue
        tf_ids = {k for k, v in stat_names.items() if v == "tf_op"}
        ops = {}
        for meta in events:
            op, tf_op = None, None
            for en, ev in _fields(meta or b""):
                if en == 2:
                    op = _text(ev)
                elif en == 5:
                    mid, value = _stat(ev)
                    if mid in tf_ids:
                        if isinstance(value, tuple):  # a ref to the text
                            value = stat_names.get(value[1])
                        tf_op = value
            if op and tf_op:
                ops[op] = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
        out[name] = ops
    return out


def scope_of(tf_op: str | None) -> str | None:
    """The outermost of :data:`SCOPES` on an op's scope path, or None."""
    if not tf_op:
        return None
    for part in tf_op.split("/"):
        if part in SCOPES:
            return part
    return None


# -- the three readings --------------------------------------------------------

def span_means(host) -> dict | None:
    """Mean per traced call of the program's spans, from host events
    ``(name, start_ns, dur_ns, line, stats)``: on the thread that holds
    the ``stencil_call`` roots, each child counted inside its root.

    Returns ``calls``, ``us`` (mean µs per call of the root and of each
    of :data:`SPANS`), ``self_us`` (the root less its children), the
    roots' ``call`` numbers in order, and the ``plan`` spans' cache
    outcomes.  None where the trace holds no root."""
    roots = sorted((s, s + d, line, st) for name, s, d, line, st in host
                   if name == ROOT_SPAN)
    if not roots:
        return None
    threads = {line for _, _, line, _ in roots}
    children = sorted(
        (s, s + d, name, st) for name, s, d, line, st in host
        if name in SPANS and line in threads
    )
    totals = {name: 0.0 for name in SPANS}
    self_ns = 0.0
    cache = {}
    j = 0
    for s, e, _, _ in roots:
        inner = 0.0
        while j < len(children) and children[j][0] < s:
            j += 1
        k = j
        while k < len(children) and children[k][0] < e:
            cs, ce, name, st = children[k]
            if ce <= e:
                totals[name] += ce - cs
                inner += ce - cs
                if name == "plan" and "cache" in st:
                    cache[st["cache"]] = cache.get(st["cache"], 0) + 1
            k += 1
        self_ns += (e - s) - inner
    n = len(roots)
    us = {ROOT_SPAN: sum(e - s for s, e, _, _ in roots) / n / 1e3}
    us.update({name: t / n / 1e3 for name, t in totals.items()})
    return {
        "calls": n,
        "us": us,
        "self_us": self_ns / n / 1e3,
        "call_numbers": [st.get("call") for _, _, _, st in roots],
        "plan_cache": cache,
    }


def scope_times(ops, tf_ops) -> dict | None:
    """Device seconds of ``ops`` (``(name, start_ns, dur_ns)`` of one
    device) by scope and kind (``kernel``, ``collective``, ``xla``, as
    :func:`bench.trace_reduce.op_kind` has them), with the ops under no
    scope as ``None``.  None where no op carries a scope."""
    times = {}
    for name, _, dur in ops:
        scope = scope_of(tf_ops.get(name))
        kinds = times.setdefault(scope, {})
        kind = trace_reduce.op_kind(name)
        kinds[kind] = kinds.get(kind, 0.0) + dur * 1e-9
    if not any(scope is not None for scope in times):
        return None
    return times


def idle_split(modules, links) -> dict | None:
    """The idle gap between consecutive modules of one device, split by
    the runtime's ``run_id`` links.

    ``modules`` are ``(start_ns, end_ns, run_id)`` on the device clock;
    ``links`` maps ``(event name, run_id)`` to that device's host
    ``(start_ns, end_ns)``.  Means over the pairs whose both links are in
    the trace, in µs: the device's gap (``module_gap_us``), its host part
    (``host_gap_us``: completion of *n* to enqueue of *n*+1) and the rest
    (``roundtrip_us``); and the device-to-host clock offset's bounds in
    ms (``offset_ms``): module *n*+1 starts after its enqueue returned,
    and module *n* ends before its completion starts."""
    modules = sorted(modules)
    gaps, host, lo, hi = [], [], [], []
    for (s0, e0, r0), (s1, _, r1) in zip(modules, modules[1:]):
        done = links.get((COMPLETE, r0))
        enq = links.get((ENQUEUE, r1))
        if done is None or enq is None:
            continue
        gaps.append(s1 - e0)
        host.append(enq[0] - done[1])
        lo.append(enq[1] - s1)
        hi.append(done[0] - e0)
    if not gaps:
        return None
    n = len(gaps)
    return {
        "pairs": n,
        "module_gap_us": sum(gaps) / n / 1e3,
        "host_gap_us": sum(host) / n / 1e3,
        "roundtrip_us": (sum(gaps) - sum(host)) / n / 1e3,
        "offset_ms": [max(lo) / 1e6, min(hi) / 1e6],
    }


def _events(profile):
    """Device ops and modules by device id, and the host's events with
    their stats: ``({id: [(name, start, dur)]}, {id: [(start, end,
    run_id)]}, [(name, start, dur, line, stats)])``."""
    ops, modules, host = {}, {}, []
    for plane in profile.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            dev = int(plane.name[len(trace_reduce.DEVICE_PREFIX):])
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops.setdefault(dev, []).extend(
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events
                    )
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        run_id = dict(e.stats).get("run_id")
                        if run_id is not None:
                            modules.setdefault(dev, []).append(
                                (e.start_ns, e.start_ns + e.duration_ns,
                                 run_id)
                            )
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    host.append((e.name, e.start_ns, e.duration_ns,
                                 line.name, dict(e.stats)))
    return ops, modules, host


def read_file(path, devices=None) -> dict:
    """The three readings of the ``.xplane.pb`` at ``path`` (see the
    module's docstring); ``devices`` lists the device ids of the run
    (default: all).  The busiest device is :mod:`bench.trace_reduce`'s:
    the one with the longest union of op intervals."""
    from jax.profiler import ProfileData

    ops, modules, host = _events(ProfileData.from_file(str(path)))
    ids = sorted(ops if devices is None else devices)
    busiest = max(ids, key=lambda d: trace_reduce.union_length(
        [(s, s + dur) for _, s, dur in ops.get(d, [])]
    )) if ids else None
    spans = span_means(host)
    scopes = None
    if busiest is not None:
        tf_ops = op_scopes(path).get(
            f"{trace_reduce.DEVICE_PREFIX}{busiest}", {}
        )
        scopes = scope_times(ops.get(busiest, []), tf_ops)
    split = None
    if busiest is not None and modules.get(busiest):
        links = {}
        for name, s, dur, _, st in host:
            if (name in (ENQUEUE, COMPLETE)
                    and st.get("device_ordinal", busiest) == busiest
                    and "run_id" in st):
                links[(name, st["run_id"])] = (s, s + dur)
        split = idle_split(modules[busiest], links)
    return {
        "busiest": busiest,
        "modules": len(modules.get(busiest, [])) if busiest is not None
        else 0,
        "spans": spans,
        "scopes": scopes,
        "idle_split": split,
    }


def metrics(reading: dict, calls: int | None = None) -> dict:
    """The per-layer numbers a reading gives, by metric name; a value is
    None where the trace holds nothing for it.  ``calls`` (default: the
    number of ``stencil_call`` roots) divides the device times.

    * ``entry.lower_us``, ``plan.resolve_us``, ``launch.enqueue_us``:
      mean µs per call of the spans ``ir_lower``, ``plan``,
      ``kernel_launch``;
    * ``host.gap_us``, ``device.roundtrip_us``: the idle split;
    * ``launch.embed_ms``, ``launch.wrap_ms``: device ms per call of the
      ops under ``stencil_embed`` and ``stencil_wrap`` (the latter only
      where the call runs such ops);
    * ``shard.halo_ms``: device ms per call of the non-collective ops
      under ``stencil_halo``, where the call runs such ops."""
    spans, scopes, split = (reading["spans"], reading["scopes"],
                            reading["idle_split"])
    if calls is None and spans is not None:
        calls = spans["calls"]
    out = dict.fromkeys((
        "entry.lower_us", "plan.resolve_us", "launch.enqueue_us",
        "host.gap_us", "device.roundtrip_us", "launch.embed_ms",
        "launch.wrap_ms", "shard.halo_ms",
    ))
    if spans is not None:
        out["entry.lower_us"] = spans["us"]["ir_lower"]
        out["plan.resolve_us"] = spans["us"]["plan"]
        out["launch.enqueue_us"] = spans["us"]["kernel_launch"]
    if split is not None:
        out["host.gap_us"] = split["host_gap_us"]
        out["device.roundtrip_us"] = split["roundtrip_us"]
    if scopes is not None and calls:
        def ms(scope, skip=()):
            kinds = scopes.get(scope)
            if not kinds:
                return None
            return sum(t for k, t in kinds.items() if k not in skip) \
                / calls * 1e3
        out["launch.embed_ms"] = ms("stencil_embed")
        out["launch.wrap_ms"] = ms("stencil_wrap")
        out["shard.halo_ms"] = ms("stencil_halo", skip=("collective",))
    return out


# -- by hand -------------------------------------------------------------------

def _summary(reading, calls=None) -> dict:
    """:func:`metrics` and the reading, with device ms per call by scope,
    the share of the launch's XLA ops (``launch.xla_ms``) that the four
    launch scopes name, and the root span's self share."""
    out = {"metrics": metrics(reading, calls), **reading}
    spans, scopes = reading["spans"], reading["scopes"]
    if spans is not None:
        out["self_pct"] = spans["self_us"] / spans["us"][ROOT_SPAN] * 100
    if scopes is not None:
        n = calls or (spans or {}).get("calls") or reading["modules"]
        out["scopes_ms"] = {
            str(scope): {k: t / n * 1e3 for k, t in kinds.items()}
            for scope, kinds in scopes.items()
        }
        xla = sum(kinds.get("xla", 0.0) for kinds in scopes.values())
        named = sum(scopes.get(scope, {}).get("xla", 0.0)
                    for scope in LAUNCH_SCOPES)
        out["launch_scoped_pct"] = named / xla * 100 if xla else None
    return out


def record(cell_name, seed, seconds, out_dir) -> dict:
    """Run ``cell_name``'s closed loop under ``jax.profiler`` on this
    machine's chip(s) for ``seconds`` and keep the trace under
    ``out_dir``; returns the path, the loop's host numbers and both
    reductions."""
    import glob
    import random
    import time

    import jax

    from bench import harness

    benchmark = harness.load_benchmark()
    cell = harness.find_cell(benchmark, cell_name)
    config = harness.load_json(harness.BENCH_DIR, "configs", cell["config"])
    traffic = harness.load_json(harness.BENCH_DIR, "traffic",
                                cell["traffic"])
    devices = harness.chip_devices(int(cell["chips"]))
    if str(harness.ROOT / "src") not in sys.path:
        sys.path.insert(0, str(harness.ROOT / "src"))
    harness.enable_compile_cache(harness.ROOT)
    u = harness.make_grid(config, seed, harness.input_sharding(config,
                                                               devices))
    entry = harness.make_entry(config, traffic)
    feed_back = bool(traffic["feed_back"])
    for _ in range(2):  # compile (or load) and plan, then one warm call
        entry(u).block_until_ready()
    keep_at = random.Random(seed).randrange(harness.KEEP_SPAN)
    os.makedirs(out_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    t0 = time.perf_counter()
    with jax.profiler.trace(out_dir, profiler_options=options):
        lat, dispatch, window, _ = harness.closed_loop(
            entry, u, feed_back, seconds, keep_at, annotate=True
        )
    paths = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    path = paths[-1]
    ids = [d.id for d in devices]
    reading = read_file(path, devices=ids)
    return {
        "cell": cell_name,
        "seed": seed,
        "trace": path,
        "trace_bytes": os.path.getsize(path),
        "traced_s": time.perf_counter() - t0,
        "calls": len(lat),
        "host_call_us": sum(dispatch) / len(dispatch) * 1e6,
        "latency_mean_ms": sum(lat) / len(lat) * 1e3,
        "reduce": trace_reduce.reduce_file(path, devices=ids),
        "program": _summary(reading, len(lat)),
    }


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", nargs="?", help="an .xplane.pb to read")
    ap.add_argument("--devices", help="comma-separated device ids")
    ap.add_argument("--record", metavar="CELL",
                    help="trace CELL's closed loop on this machine's chips")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--out", help="with --record: where the trace goes")
    args = ap.parse_args(argv)
    if args.record:
        if not args.out:
            ap.error("--record needs --out DIR")
        print(json.dumps(record(args.record, args.seed, args.seconds,
                                os.path.join(args.out, args.record))),
              flush=True)
        return 0
    if not args.trace:
        ap.error("give a trace, or --record CELL")
    devices = ([int(d) for d in args.devices.split(",")]
               if args.devices else None)
    print(json.dumps(_summary(read_file(args.trace, devices))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
