"""``python -m repro.obs.report trace.json`` — reconcile a recorded trace.

Reads a ``trace_event`` JSON file written by :mod:`repro.obs` and prints
the evidence trail the paper's model promises (DESIGN.md §12):

* a per-launch reconciliation table — plan key, fused depth, shard
  count, tile, window kind, input buffer (``direct`` or the launch
  buffer's kind), grid slack (sublanes x lanes past the grid's end in
  its last grain), modeled bytes — one row per
  ``kernel_launch`` span.  That span times the host's enqueue, not the
  device's run, so the table shows no wall time or bandwidth: device
  time comes from a ``jax.profiler`` trace, read by the benchmark's
  ``bench/trace_program.py``;
* the tune-race outcome (candidate ranks, measured medians, winner);
* the counter totals (cache hits/misses, fallbacks, modeled totals).

``--check`` additionally asserts the internal bookkeeping reconciles —
the ``launches`` counter matches the number of launch spans, the summed
per-span ``modeled_bytes`` match the ``modeled_bytes`` counter, the
summed per-span ``ring_vmem_bytes`` (§14 staged-frontier VMEM at each
stage's own dtype; 0 on pre-v6 traces) match the ``ring_vmem_bytes``
counter, the ``direct_input_launches`` counter matches the launch
spans with ``input_buffer=direct``, the ``offgrain_launches`` counter
matches the launch spans whose ``grid_slack`` is not ``(0, 0)`` (a
grid off the (sublane, lane) grain), the ``entry_memo_hit`` and
``entry_memo_miss`` counters match the ``stencil_call`` roots carrying
``memo=hit`` and ``memo=miss`` (the call entry's memo, DESIGN.md
§17), and the summed ``measure`` span nanoseconds match
``measured_ns`` — exiting non-zero on any mismatch.  This is what the
CI obs smoke runs.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any

from .trace_event import load_trace

__all__ = ["main", "reconcile", "summarize"]


def _spans(doc: dict, name: str) -> list[dict]:
    return [
        ev for ev in doc["traceEvents"]
        if ev.get("ph") == "X" and ev.get("name") == name
    ]


def _counters(doc: dict) -> dict[str, int]:
    # Prefer the final totals stashed by the exporter; fall back to the
    # last ph:"C" sample per counter for traces from other producers.
    other = doc.get("otherData") or {}
    if isinstance(other.get("counters"), dict):
        return dict(other["counters"])
    totals: dict[str, int] = {}
    for ev in doc["traceEvents"]:
        if ev.get("ph") == "C":
            for k, v in (ev.get("args") or {}).items():
                totals[k] = v
    return totals


def summarize(doc: dict) -> dict[str, Any]:
    """Digest a trace into the report's row data (pure, testable)."""
    counters = _counters(doc)
    launches = []
    for ev in _spans(doc, "kernel_launch"):
        args = ev.get("args") or {}
        modeled = int(args.get("modeled_bytes", 0))
        launches.append({
            "plan_key": str(args.get("plan_key", "?")),
            "fused_depth": args.get("fused_depth"),
            "num_shards": args.get("num_shards"),
            "tile": args.get("tile"),
            "steps": args.get("steps"),
            "modeled_bytes": modeled,
            "modeled_flops": int(args.get("modeled_flops", 0)),
            # §14 accounting; absent in pre-v6 traces (trapezoid era).
            "window_kind": args.get("window_kind"),
            "stage_dtypes": args.get("stage_dtypes"),
            "ring_vmem_bytes": int(args.get("ring_vmem_bytes", 0)),
            # What the launch read its input from (DESIGN.md §16);
            # absent in traces that predate the direct launch.
            "input_buffer": args.get("input_buffer"),
            # Cells past the grid's end in its last (sublane, lane)
            # grain; absent in traces that predate off-grain launches.
            "grid_slack": args.get("grid_slack"),
        })
    races = []
    for ev in _spans(doc, "tune_race"):
        args = ev.get("args") or {}
        races.append({
            "key": str(args.get("plan_key", "?")),
            "candidates": args.get("candidates"),
            "winner_rank": args.get("winner_rank"),
            "winner_source": args.get("source"),
            "dur_us": float(ev.get("dur", 0.0)),
        })
    candidates = []
    for ev in _spans(doc, "tune_candidate"):
        args = ev.get("args") or {}
        candidates.append({
            "rank": args.get("rank"),
            "tile": args.get("tile"),
            "fused_depth": args.get("fused_depth"),
            "median_ms": args.get("median_ms"),
            "dur_us": float(ev.get("dur", 0.0)),
        })
    measures = _spans(doc, "measure")
    memo = {"hit": 0, "miss": 0}
    for ev in _spans(doc, "stencil_call"):
        outcome = (ev.get("args") or {}).get("memo")
        if outcome in memo:
            memo[outcome] += 1
    return {
        "counters": counters,
        "launches": launches,
        "races": races,
        "candidates": candidates,
        "n_plan_spans": len(_spans(doc, "plan")),
        # The call entry's memo outcome per ``stencil_call`` root (§17).
        "memo": memo,
        "n_measure_spans": len(measures),
        "measure_ns_total": int(
            sum((m.get("args") or {}).get("measured_ns", 0) for m in measures)
        ),
    }


def reconcile(summary: dict[str, Any]) -> list[str]:
    """Cross-check counters against spans; returns mismatch messages."""
    problems: list[str] = []
    c = summary["counters"]
    launches = summary["launches"]
    n_counter = int(c.get("launches", 0))
    if n_counter != len(launches):
        problems.append(
            f"launches counter={n_counter} but {len(launches)} "
            f"kernel_launch spans recorded"
        )
    span_bytes = sum(l["modeled_bytes"] for l in launches)
    if span_bytes != int(c.get("modeled_bytes", 0)):
        problems.append(
            f"modeled_bytes counter={c.get('modeled_bytes', 0)} but launch "
            f"spans sum to {span_bytes}"
        )
    span_flops = sum(l["modeled_flops"] for l in launches)
    if span_flops != int(c.get("modeled_flops", 0)):
        problems.append(
            f"modeled_flops counter={c.get('modeled_flops', 0)} but launch "
            f"spans sum to {span_flops}"
        )
    span_ring = sum(l["ring_vmem_bytes"] for l in launches)
    if span_ring != int(c.get("ring_vmem_bytes", 0)):
        problems.append(
            f"ring_vmem_bytes counter={c.get('ring_vmem_bytes', 0)} but "
            f"launch spans sum to {span_ring}"
        )
    n_direct = sum(1 for l in launches if l["input_buffer"] == "direct")
    if n_direct != int(c.get("direct_input_launches", 0)):
        problems.append(
            f"direct_input_launches counter="
            f"{c.get('direct_input_launches', 0)} but {n_direct} launch "
            f"spans read their input directly"
        )
    n_off = sum(1 for l in launches if any(l["grid_slack"] or ()))
    if n_off != int(c.get("offgrain_launches", 0)):
        problems.append(
            f"offgrain_launches counter={c.get('offgrain_launches', 0)} "
            f"but {n_off} launch spans have grid slack"
        )
    for outcome, n in summary["memo"].items():
        name = f"entry_memo_{outcome}"
        if n != int(c.get(name, 0)):
            problems.append(
                f"{name} counter={c.get(name, 0)} but {n} stencil_call "
                f"spans carry memo={outcome}"
            )
    if summary["measure_ns_total"] != int(c.get("measured_ns", 0)):
        problems.append(
            f"measured_ns counter={c.get('measured_ns', 0)} but measure "
            f"spans sum to {summary['measure_ns_total']}"
        )
    return problems


def _fmt_bytes(n: int) -> str:
    for unit, div in (("GiB", 2**30), ("MiB", 2**20), ("KiB", 2**10)):
        if n >= div:
            return f"{n / div:.2f} {unit}"
    return f"{n} B"


def render(summary: dict[str, Any]) -> str:
    lines: list[str] = []
    launches = summary["launches"]
    lines.append(f"launches: {len(launches)}")
    if launches:
        hdr = (
            f"{'#':>3}  {'plan key':<14} {'T':>3} {'shards':>6} "
            f"{'tile':<14} {'win':<5} {'input':<6} {'slack':<7} "
            f"{'ring vmem':>10} {'modeled':>12}"
        )
        lines += [hdr, "-" * len(hdr)]
        for i, l in enumerate(launches):
            tile = "x".join(map(str, l["tile"])) if l["tile"] else "-"
            wk = (l.get("window_kind") or "-")[:5]
            buf = (l.get("input_buffer") or "-")[:6]
            slack = l.get("grid_slack")
            slack = "x".join(map(str, slack)) if slack else "-"
            lines.append(
                f"{i:>3}  {l['plan_key'][:14]:<14} "
                f"{l['fused_depth'] or 1:>3} {l['num_shards'] or 1:>6} "
                f"{tile:<14} {wk:<5} {buf:<6} {slack:<7} "
                f"{_fmt_bytes(l['ring_vmem_bytes']):>10} "
                f"{_fmt_bytes(l['modeled_bytes']):>12}"
            )
            dts = l.get("stage_dtypes")
            if dts and any(dt is not None for dt in dts):
                lines.append(
                    "     stage dtypes: "
                    + " -> ".join(dt or "<input>" for dt in dts)
                )
    for race in summary["races"]:
        lines.append(
            f"tune race: key={race['key'][:14]} "
            f"candidates={race['candidates']} "
            f"winner_rank={race['winner_rank']} "
            f"source={race['winner_source']} "
            f"({race['dur_us'] / 1e3:.1f} ms)"
        )
    for cand in summary["candidates"]:
        tile = "x".join(map(str, cand["tile"])) if cand["tile"] else "-"
        med = cand["median_ms"]
        lines.append(
            f"  candidate rank={cand['rank']} tile={tile} "
            f"T={cand['fused_depth']} "
            f"median={med:.3f} ms" if isinstance(med, (int, float))
            else f"  candidate rank={cand['rank']} tile={tile}"
        )
    lines.append(
        f"spans: plan={summary['n_plan_spans']} "
        f"measure={summary['n_measure_spans']} "
        f"calls: memo hit={summary['memo']['hit']} "
        f"miss={summary['memo']['miss']}"
    )
    counters = summary["counters"]
    if counters:
        lines.append("counters:")
        for name in sorted(counters):
            lines.append(f"  {name:<24} {counters[name]}")
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs.report",
        description="Reconcile a repro.obs trace_event JSON file.",
    )
    ap.add_argument("trace", help="path to a REPRO_TRACE/recording() output")
    ap.add_argument(
        "--check",
        action="store_true",
        help="exit non-zero unless counters reconcile against spans",
    )
    ap.add_argument(
        "--json",
        action="store_true",
        help="emit the summary as JSON instead of a table",
    )
    ns = ap.parse_args(argv)
    try:
        doc = load_trace(ns.trace)
    except (OSError, ValueError, json.JSONDecodeError) as exc:
        print(f"repro.obs.report: invalid trace {ns.trace!r}: {exc}",
              file=sys.stderr)
        return 2
    summary = summarize(doc)
    problems = reconcile(summary)
    if ns.json:
        print(json.dumps(
            {"summary": summary, "reconciled": not problems,
             "problems": problems},
            indent=2, default=str,
        ))
    else:
        print(render(summary))
        if problems:
            print("RECONCILIATION MISMATCH:")
            for p in problems:
                print(f"  {p}")
        else:
            print("reconciled: counters match spans")
    if ns.check and problems:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
