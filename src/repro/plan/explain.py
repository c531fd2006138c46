"""Human-readable plan reports:  python -m repro.plan.explain 45x91x24

Prints the full pipeline for one grid — interference-lattice basis, LLL
reduction, shortest vector, why a pad was (not) chosen, the winning tile
(with its §8 fusion depth under ``--time-steps``), the per-depth score
table (modeled chain traffic + streaming flops per candidate fusion
depth), and the predicted traffic against the legacy heuristic, the
planner's own single-pass choice, and the isoperimetric lower bound.
``--num-shards N`` plans the §10 column-sharded launch (per-shard
figures + halo-exchange bytes).  ``--tuned`` additionally looks the
request up in the §11 TunedPlanDB for this backend fingerprint and, on a
hit, prints the stored measured-candidate table (``python -m
repro.autotune`` is the tool that writes it).  ``--smoke`` runs the CI gate: seven
shapes (one unfavorable, one ``time_steps=3`` fused, one two-stage
heterogeneous chain, one 4-way sharded, one §14 mixed-precision ring
chain), asserting the pad triggers, the
planner never predicts more traffic than the legacy heuristic, a fused
plan never predicts more traffic than its own single-pass choice, the
streaming-frontier path never models more flops than the recompute
trapezoid, and a shard's slab moves well under the whole-grid bytes.

The full CLI reference (flags, the per-depth score table, a captured
transcript) lives in ``docs/plan_explain.md``.
"""

from __future__ import annotations

import argparse
import sys

import numpy as np

from repro.core.cache_fitting import box_stencil, star_stencil
from repro.core.tiling import grid_slack

from .cache import PlanCache
from .planner import Planner
from .schema import StencilPlan

__all__ = ["format_plan", "main", "plan_json_doc", "smoke"]


def _parse_shape(s: str) -> tuple[int, ...]:
    for sep in ("x", ","):
        if sep in s:
            return tuple(int(p) for p in s.split(sep) if p)
    return (int(s),)


def _parse_stencil(spec: str, d: int) -> np.ndarray:
    kind, _, r = spec.partition(":")
    r = int(r or 2)
    if kind == "star":
        return star_stencil(d, r)
    if kind == "box":
        return box_stencil(d, r)
    raise SystemExit(f"unknown stencil spec {spec!r} (use star:R or box:R)")


def _fmt_bytes(b: float) -> str:
    if b >= 1 << 20:
        return f"{b / (1 << 20):.2f} MiB"
    if b >= 1 << 10:
        return f"{b / (1 << 10):.2f} KiB"
    return f"{b:.0f} B"


def launch_input(plan: StencilPlan) -> str:
    """``"direct"`` when the plan's first launch reads the caller's array
    as it is (``core.tiling.direct_input``, DESIGN.md §16), else
    ``"buffer"``: it reads a zero-filled launch buffer."""
    from repro.core.tiling import (
        chain_halo, direct_input, halo_from_offsets,
    )

    req = plan.request
    d = len(req.shape)
    if req.stages:
        halo = chain_halo([
            halo_from_offsets([st.offsets], d)
            for st in req.stages[: plan.fused_depth]
        ])
    else:
        halo = halo_from_offsets(req.offsets, d)
    direct = direct_input(
        req.shape, plan.tile, halo, req.dtype_bytes, bcs=req.bcs,
        num_shards=plan.num_shards,
    )
    return "direct" if direct else "buffer"


def format_plan(plan: StencilPlan, validation: dict | None = None) -> str:
    req = plan.request
    slack = grid_slack(req.shape, req.dtype_bytes)
    lines = [
        f"plan for grid {req.shape}  (dtype {req.dtype_bytes} B, "
        f"{len(req.offsets)} RHS, budget {_fmt_bytes(req.vmem_budget)}, "
        f"strategy {req.strategy})",
    ]
    lat = plan.lattice
    if lat is not None:
        lines += [
            f"  cache model: S = {lat.S} words "
            f"(geometry a,z,w = {req.geometry})",
            "  interference lattice (Eq. 9 basis rows):",
        ]
        lines += [f"    {row}" for row in lat.basis]
        lines.append("  LLL-reduced basis:")
        lines += [f"    {row}" for row in lat.reduced]
        lines += [
            f"  shortest vector: {lat.shortest}  |v|_1 = {lat.shortest_l1:.0f}"
            f"  |v|_2 = {lat.shortest_l2:.2f}  eccentricity {lat.eccentricity:.2f}",
            f"  unfavorable: {lat.unfavorable}  "
            f"(threshold |v|_1 < {lat.threshold:.3g}; Fig. 5 hyperbola "
            f"k = {lat.hyperbola_k}, rel. dist {lat.hyperbola_dist:.3f})",
        ]
    else:
        lines.append("  cache model: none (explicitly managed memory)")
    lines += [
        f"  pad: {plan.pad.pad} -> {plan.pad.padded_shape} "
        f"(+{plan.pad.extra_words} words)",
        f"    why: {plan.pad.reason}",
        f"  tile: {plan.tile}  sweep axis {plan.sweep_axis}  "
        f"grid {plan.grid}  pipelined {plan.pipelined}",
        "  input: " + (
            "direct (the kernel reads the caller's array, §16)"
            if launch_input(plan) == "direct"
            else "launch buffer (zero-filled copy of the grid)"
        ),
        f"  grid slack: {slack[0]} sublanes x {slack[1]} lanes past the "
        "grid's end in its last grain" + (
            " (off the grain: the chip slices the array only in whole "
            "grains, §16)" if any(slack) else ""
        ),
    ]
    if plan.time_steps > 1:
        n_launch = -(-plan.time_steps // plan.fused_depth)
        distinct = len({st.offsets for st in req.stages})
        lines.append(
            f"  stage chain: {plan.time_steps} applications "
            f"({distinct} distinct operator(s)), fused depth "
            f"{plan.fused_depth} ({n_launch} launch(es); §14 "
            f"{plan.window_kind} frontier windows)"
        )
        dts = [st.dtype for st in req.stages]
        if any(dt is not None for dt in dts):
            lines.append(
                "  stage dtypes: "
                + " -> ".join(dt or "<input>" for dt in dts)
                + "  (frontiers sized at each stage's own width; "
                "accumulation stays f32)"
            )
    if plan.num_shards > 1:
        lines.append(
            f"  sharding: {plan.num_shards} shards over axis "
            f"{plan.shard_axis} (mesh axis {req.mesh_axis!r}); per-shard "
            f"traffic {_fmt_bytes(plan.per_shard_traffic_bytes)}, halo "
            f"exchange {_fmt_bytes(plan.halo_exchange_bytes)} "
            "(§10 column sharding — all figures below are per shard)"
        )
    if len(plan.depth_scores) > 1:
        lines.append("  fused-depth scores (whole chain, modeled):")
        lines.append("    depth        traffic     flops(streaming)  chosen")
        for depth, tr, fl in plan.depth_scores:
            mark = "   <--" if depth == plan.fused_depth else ""
            lines.append(
                f"    {depth:>5}  {_fmt_bytes(tr):>13}  {fl:>17,}{mark}"
            )
    if plan.recompute_flops > plan.modeled_flops:
        lines.append(
            f"  modeled flops: streaming {plan.modeled_flops:,} vs "
            f"recompute trapezoid {plan.recompute_flops:,} -> "
            f"{plan.recompute_flops / max(plan.modeled_flops, 1):.2f}x "
            f"saved at unchanged traffic"
        )
    if req.program:
        from repro.ir import summarize_program

        lines.append(f"  program: {summarize_program(req.program)}")
    lines += [
        f"  vmem/operand window: {_fmt_bytes(plan.vmem_bytes)}  "
        f"surface/volume {plan.surface_to_volume:.3f}",
        f"  predicted traffic: {_fmt_bytes(plan.traffic_bytes)} "
        f"({plan.traffic_bytes // max(req.dtype_bytes, 1)} loads)",
        f"    vs legacy heuristic: {_fmt_bytes(plan.legacy_traffic_bytes)} "
        f"(tile {plan.legacy_tile}) -> planned/legacy = "
        f"{plan.traffic_vs_legacy:.3f}",
        f"    vs isoperimetric lower bound: "
        f"{_fmt_bytes(plan.lower_bound_bytes)} -> efficiency = "
        f"{plan.efficiency:.3f}",
    ]
    if plan.time_steps > 1:
        lines.append(
            f"    vs own single-pass plan: "
            f"{_fmt_bytes(plan.single_pass_traffic_bytes)} -> fused/single = "
            f"{plan.traffic_vs_single_pass:.3f}"
        )
    if validation and validation.get("validated"):
        o = validation["original"]
        p = validation["padded"]
        lines.append(
            f"  cache-sim check: original {o['dims']} "
            f"{o['miss_per_point']:.3f} miss/pt, padded {p['dims']} "
            f"{p['miss_per_point']:.3f} miss/pt"
            + (
                f" ({validation['miss_reduction_x']:.2f}x fewer)"
                if "miss_reduction_x" in validation
                else ""
            )
        )
    return "\n".join(lines)


def plan_json_doc(plan: StencilPlan) -> dict:
    """The ``--json`` document: the full frozen plan (round-trips through
    ``StencilPlan.from_dict``), the per-depth score table, the request's
    canonical §13 stencil program with its inferred per-value bounds
    (``repro.ir.Program.from_dict(doc["program"])`` round-trips to the
    request's cache-key form), and a ``report`` block carrying the same
    fields ``repro.obs.report`` prints per launch — so a trace row and an
    explain dump reconcile key-for-key.
    """
    program = None
    value_bounds = None
    if plan.request.program:
        from repro.ir import Program, infer_bounds

        prog = Program.from_json(plan.request.program)
        program = prog.to_dict()
        value_bounds = {
            name: b.to_dict()
            for name, b in infer_bounds(prog, plan.request.shape).items()
        }
    return {
        "plan": plan.to_dict(),
        "program": program,
        "value_bounds": value_bounds,
        "depth_scores": [
            {
                "depth": d,
                "traffic_bytes": tr,
                "streaming_flops": fl,
                "chosen": d == plan.fused_depth,
            }
            for d, tr, fl in plan.depth_scores
        ],
        "report": {
            "plan_key": plan.request.cache_key(),
            "tile": list(plan.tile),
            "sweep_axis": plan.sweep_axis,
            "fused_depth": plan.fused_depth,
            "time_steps": plan.time_steps,
            "num_shards": plan.num_shards,
            "shard_axis": plan.shard_axis,
            "modeled_bytes": (
                plan.per_shard_traffic_bytes * plan.num_shards
                + plan.halo_exchange_bytes
            ),
            "modeled_flops": plan.modeled_flops,
            "traffic_vs_legacy": plan.traffic_vs_legacy,
            "efficiency": plan.efficiency,
            "window_kind": plan.window_kind,
            "stage_dtypes": [st.dtype for st in plan.request.stages] or None,
            "input": launch_input(plan),
            "grid_slack": list(grid_slack(plan.request.shape,
                                          plan.request.dtype_bytes)),
        },
    }


def smoke() -> int:
    """CI gate: plan 7 shapes (one unfavorable, one T=3 fused, one
    two-stage heterogeneous chain, one 4-way sharded, one §14
    mixed-precision ring chain), assert the pipeline's promises — pad triggers and clears the threshold, planned
    traffic never exceeds the legacy heuristic, a fused plan never
    exceeds the planner's own single-pass choice, the streaming path
    never models more flops than the recompute trapezoid, a sharded
    plan's per-shard slab beats the whole grid (and 1 shard == unsharded
    exactly), warm cache hits are O(1)."""
    import time

    from repro.core.padding import is_unfavorable

    planner = Planner(cache=PlanCache(persistent=False))
    offs = star_stencil(3, 2)
    geom = (2, 512, 4)
    S = geom[0] * geom[1] * geom[2]
    cases = [
        # (name, shape, geometry, vmem_budget, aligned, time_steps|stages)
        ("favorable", (64, 91, 60), geom, 16 * 1024, False, 1),
        # n1*n2 ~ 2*(S/2), Fig. 5
        ("unfavorable", (45, 91, 24), geom, 16 * 1024, False, 1),
        ("tpu", (256, 256, 256), None, 16 * 1024, False, 1),
        # §8 temporal blocking: at VMEM scale the T=3 trapezoid must fuse
        # and cut modeled traffic vs the single-pass chain.
        ("fused_t3", (256, 256, 256), None, 16 << 20, True, 3),
        # §9 stage chain: two distinct operators (r=1 then r=2 star) —
        # heterogeneous per-stage halos through planning and pricing.
        ("stage_chain_2", (128, 128, 128), None, 16 << 20, True,
         [star_stencil(3, 1), star_stencil(3, 2)]),
        # §10 column sharding: the planner tiles the worst shard's slab
        # and must beat the unsharded whole-grid traffic per core.
        ("sharded_4", (256, 256, 256), None, 16 << 20, True, 1),
        # §14 mixed-precision ring: bf16 frontiers under window_kind
        # "auto" must resolve to the ring and never lose to a forced
        # trapezoid of the same request.
        ("ring_bf16", (256, 256, 256), None, 16 << 20, True, 4),
    ]
    for name, shape, g, budget, aligned, t_steps in cases:
        kw = dict(shape=shape, geometry=g, vmem_budget=budget, aligned=aligned)
        if isinstance(t_steps, list):
            kw["stages"] = t_steps
        else:
            kw.update(offsets=offs, time_steps=t_steps)
        if name == "sharded_4":
            kw["num_shards"] = 4
        if name == "ring_bf16":
            kw["dtypes"] = ["bfloat16"] * 3 + ["float32"]
        plan = planner.plan(**kw)
        assert plan.traffic_bytes <= plan.legacy_traffic_bytes, (
            name, plan.traffic_bytes, plan.legacy_traffic_bytes)
        assert plan.traffic_bytes <= plan.single_pass_traffic_bytes, (
            name, plan.traffic_bytes, plan.single_pass_traffic_bytes)
        assert plan.modeled_flops <= plan.recompute_flops, (
            name, plan.modeled_flops, plan.recompute_flops)
        if name == "unfavorable":
            assert plan.pad.nonzero, "pad did not trigger on unfavorable grid"
            assert not is_unfavorable(plan.pad.padded_shape, S, diameter=5), (
                "padded grid still unfavorable")
        if name == "favorable":
            assert not plan.pad.nonzero, "pad triggered on favorable grid"
        if name == "fused_t3":
            assert plan.fused_depth > 1, "T=3 plan did not fuse at VMEM scale"
            reduction = plan.single_pass_traffic_bytes / plan.traffic_bytes
            assert reduction >= 1.5, (
                f"fused reduction {reduction:.2f}x < 1.5x")
            flop_cut = plan.recompute_flops / max(plan.modeled_flops, 1)
            assert flop_cut >= 1.5, (
                f"streaming flop reduction {flop_cut:.2f}x < 1.5x")
        if name == "stage_chain_2":
            assert plan.time_steps == 2 and len(plan.request.stages) == 2
            assert len(plan.depth_scores) >= 1
            assert any(d == plan.fused_depth for d, _, _ in plan.depth_scores)
        if name == "ring_bf16":
            assert plan.window_kind == "ring", plan.window_kind
            # The final "float32" restates the input dtype: normalized.
            assert [st.dtype for st in plan.request.stages] == \
                ["bfloat16"] * 3 + [None]
            trap = planner.plan(**dict(kw, window_kind="trapezoid"))
            assert plan.traffic_bytes <= trap.traffic_bytes, (
                plan.traffic_bytes, trap.traffic_bytes)
            assert max(d for d, _, _ in plan.depth_scores) >= max(
                d for d, _, _ in trap.depth_scores
            ), "ring admitted fewer fusion depths than the trapezoid"
        if name == "sharded_4":
            base = planner.plan(**{k: v for k, v in kw.items()
                                   if k != "num_shards"})
            assert plan.num_shards == 4 and plan.shard_axis is not None
            assert plan.shard_axis != (plan.sweep_axis
                                       if plan.sweep_axis is not None else 0)
            assert plan.halo_exchange_bytes > 0
            assert plan.per_shard_traffic_bytes == plan.traffic_bytes
            # The per-core win: one shard's slab must move well under the
            # whole-grid single-device bytes (ideal = 1/4).
            assert plan.per_shard_traffic_bytes <= base.traffic_bytes / 2, (
                plan.per_shard_traffic_bytes, base.traffic_bytes)
            # 1-shard request == unsharded request: same canonical key.
            one = dict(kw, num_shards=1)
            assert planner.plan(**one) == base
            assert plan.request.cache_key() != base.request.cache_key()
        warm = []
        for _ in range(3):  # best-of-3: absorb one-time warmup/GC noise
            t0 = time.perf_counter()
            again = planner.plan(**kw)
            warm.append((time.perf_counter() - t0) * 1e3)
            assert again == plan
        warm_ms = min(warm)
        assert warm_ms < 1.0, f"warm cache hit took {warm_ms:.2f} ms"
        print(
            f"planner smoke [{name}] {shape}: pad={plan.pad.pad} "
            f"planned/legacy={plan.traffic_vs_legacy:.3f} "
            f"fused_depth={plan.fused_depth} "
            f"fused/single={plan.traffic_vs_single_pass:.3f} "
            f"flops_stream/recompute={plan.flops_vs_recompute:.3f} "
            f"warm_hit={warm_ms:.3f} ms  OK"
        )
    print("planner smoke: all gates passed")
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.plan.explain",
        description="Explain the stencil plan for one grid.",
    )
    ap.add_argument("shape", nargs="?", default="45x91x24",
                    help="grid shape, e.g. 45x91x24")
    ap.add_argument("--stencil", default="star:2",
                    help="star:R or box:R (default star:2)")
    ap.add_argument("--geom", default="2,512,4",
                    help="cache geometry a,z,w; 'none' for pure TPU mode")
    ap.add_argument("--budget", type=int, default=None,
                    help="VMEM/cache budget in bytes (default: geometry size)")
    ap.add_argument("--dtype-bytes", type=int, default=4)
    ap.add_argument("--time-steps", type=int, default=1,
                    help="fuse T stencil applications (§8 temporal blocking)")
    ap.add_argument("--num-shards", type=int, default=1,
                    help="plan the §10 column-sharded launch over N cores")
    ap.add_argument("--window-kind", default="auto",
                    choices=("auto", "ring", "trapezoid"),
                    help="§14 frontier layout (auto races both)")
    ap.add_argument("--dtypes", default=None,
                    help="comma-separated per-stage output dtypes for a "
                    "--time-steps chain, e.g. bfloat16,bfloat16,float32")
    ap.add_argument("--aligned", action="store_true",
                    help="restrict tiles to lane/sublane-aligned extents")
    ap.add_argument("--legacy", action="store_true",
                    help="use the pre-planner tile heuristic "
                    "(Planner strategy='legacy')")
    ap.add_argument("--validate", action="store_true",
                    help="cache-simulate original vs padded grid")
    ap.add_argument("--tuned", action="store_true",
                    help="show the §11 TunedPlanDB record for this request "
                    "(measured candidate table), if one exists")
    ap.add_argument("--db", default=None,
                    help="tuned-plan DB directory for --tuned "
                    "(default: REPRO_TUNED_DB_DIR or ~/.cache/repro/tuned)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output: the full plan, the "
                    "depth-score table, and the obs-report summary fields")
    ap.add_argument("--smoke", action="store_true",
                    help="run the CI smoke gates instead")
    args = ap.parse_args(argv)
    if args.smoke:
        return smoke()

    shape = _parse_shape(args.shape)
    offs = _parse_stencil(args.stencil, len(shape))
    geometry = None if args.geom.lower() == "none" else _parse_shape(args.geom)
    planner = Planner(strategy="legacy" if args.legacy else "paper")
    plan = planner.plan(
        shape=shape, offsets=offs, dtype_bytes=args.dtype_bytes,
        vmem_budget=args.budget, geometry=geometry, aligned=args.aligned,
        time_steps=args.time_steps, num_shards=args.num_shards,
        window_kind=args.window_kind,
        dtypes=args.dtypes.split(",") if args.dtypes else None,
    )
    if args.json:
        import json

        print(json.dumps(plan_json_doc(plan), indent=2, sort_keys=True))
        return 0
    validation = planner.validate(plan) if args.validate else None
    print(format_plan(plan, validation))
    if args.tuned:
        from .tune import backend_fingerprint, format_record
        from .tunedb import TunedPlanDB

        fp = backend_fingerprint()
        rec = TunedPlanDB(db_dir=args.db).get(plan.request.cache_key(), fp)
        if rec is None:
            print(
                f"\ntuned: no record for this request at fingerprint {fp}\n"
                "  (run `python -m repro.autotune "
                f"{args.shape} --stencil {args.stencil}` to measure one)"
            )
        else:
            print("\ntuned record (§11 measured candidates):")
            print(format_record(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
