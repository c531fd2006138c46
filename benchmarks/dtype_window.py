"""PR-tracked perf record: §14 ring windows + dtype-aware tiling.

Emits the machine-readable ``BENCH_PR9.json`` consumed by scripts/ci.sh:

* **Depth-uncapping gate** (the headline): at a fixed VMEM budget where
  the f32 trapezoid caps fusion at **T=2** for star(3,2)@256³, the
  bf16-frontier ring legally plans **T>=4** — the freed staged-cone
  bytes plus the halved frontier width together double the legal depth.
  The modeled HBM traffic of the deep ring plan vs the capped trapezoid
  plan is the achieved cut (gate: >= 1.5x).

* **Depth table**: max feasible fusion depth, ring vs trapezoid, across
  a budget sweep of the same-dtype f32 configuration — the ring's +Δ
  depth without any precision change.

* **Bit-parity gate**: a fused f32 ring launch is **bit-wise** equal to
  the trapezoid launch of the same chain (the §14 contract: the ring
  changes VMEM residency, never the values streamed between stages).

* The PR8 IR record (which embeds PR7 ⊃ … ⊃ PR1) rides along unchanged
  so the perf trajectory keeps its history.
"""
from __future__ import annotations

import json

from .common import force_cpu_devices

force_cpu_devices()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cache_fitting import star_stencil
from repro.kernels.stencil import stencil_iterate
from repro.plan import PlanCache, Planner

from .common import emit_bench, timed
from .timing import device_fingerprint
from . import ir_parity

# The headline configuration: star(3,2) on a 256^3 grid, one operand
# resident, unpipelined window (pure ring arithmetic, no prefetch slabs).
# The budget sits in the window (525,000-548,000 B, every buffer charged
# at its DMA-grain rounded size) where trapezoid-f32 depth 3 no longer
# fits but ring-bf16 depth 4 still does — both thresholds are exact
# outputs of the pure-arithmetic cost model, so the gate is
# deterministic, not timing-dependent.
SHAPE = (256, 256, 256)
T = 4
BUDGET = 536_000
BF16_CHAIN = ["bfloat16", "bfloat16", "bfloat16", "float32"]

# Same-dtype sweep for the depth table (pipelined f32, two operands).
TABLE_SHAPE = (128, 128, 128)
TABLE_T = 8
TABLE_BUDGETS = (500_000, 900_000, 1_400_000, 1_790_000)


def _planner() -> Planner:
    return Planner(cache=PlanCache(persistent=False))


def _max_depth(plan) -> int:
    return max(d for d, _, _ in plan.depth_scores)


def depth_uncapping() -> dict:
    """Trapezoid-f32 caps at 2; ring-bf16 reaches >= 4; traffic cut."""
    planner = _planner()
    offs = star_stencil(3, 2)
    kw = dict(shape=SHAPE, offsets=offs, time_steps=T, vmem_budget=BUDGET,
              n_operands=1, pipelined=False, aligned=True)
    trap = planner.plan(window_kind="trapezoid", **kw)
    ring = planner.plan(window_kind="ring", dtype_bytes=2,
                        dtypes=BF16_CHAIN, **kw)
    cut = trap.traffic_bytes / ring.traffic_bytes
    return {
        "shape": list(SHAPE),
        "time_steps": T,
        "vmem_budget": BUDGET,
        "bf16_chain": BF16_CHAIN,
        "trapezoid_f32": {
            "max_depth": _max_depth(trap),
            "fused_depth": trap.fused_depth,
            "traffic_bytes": trap.traffic_bytes,
            "tile": list(trap.tile),
        },
        "ring_bf16": {
            "max_depth": _max_depth(ring),
            "fused_depth": ring.fused_depth,
            "traffic_bytes": ring.traffic_bytes,
            "tile": list(ring.tile),
        },
        "traffic_cut": cut,
    }


def depth_table() -> dict:
    """Same-dtype f32: ring vs trapezoid max feasible depth by budget."""
    planner = _planner()
    offs = star_stencil(3, 2)
    rows = []
    for budget in TABLE_BUDGETS:
        kw = dict(shape=TABLE_SHAPE, offsets=offs, time_steps=TABLE_T,
                  vmem_budget=budget, n_operands=2, aligned=True)
        trap = planner.plan(window_kind="trapezoid", **kw)
        ring = planner.plan(window_kind="ring", **kw)
        rows.append({
            "vmem_budget": budget,
            "trapezoid_max_depth": _max_depth(trap),
            "ring_max_depth": _max_depth(ring),
        })
    return {
        "shape": list(TABLE_SHAPE),
        "time_steps": TABLE_T,
        "rows": rows,
        "ring_never_shallower": all(
            r["ring_max_depth"] >= r["trapezoid_max_depth"] for r in rows
        ),
        "ring_deeper_somewhere": any(
            r["ring_max_depth"] > r["trapezoid_max_depth"] for r in rows
        ),
    }


def ring_bit_parity() -> dict:
    """Fused f32 ring launch vs trapezoid launch: bit-wise equality."""
    u = jax.random.normal(jax.random.PRNGKey(0), (48, 56), jnp.float32)
    offs = star_stencil(2, 2)
    w = np.linspace(-0.3, 0.4, len(offs)).tolist()
    kw = dict(tile=(8, 16), sweep_axis=0)
    rows = []
    for steps in (2, 4):
        ring = stencil_iterate(u, offs, w, steps, window_kind="ring", **kw)
        trap = stencil_iterate(u, offs, w, steps, window_kind="trapezoid",
                               **kw)
        rows.append({
            "T": steps,
            "bitwise": bool(np.array_equal(np.asarray(ring),
                                           np.asarray(trap))),
        })
    return {"rows": rows, "all_bitwise": all(r["bitwise"] for r in rows)}


def build_report(quick: bool = True, pr8: dict | None = None) -> dict:
    """``pr8``: a pre-built PR8 IR report to embed — callers that already
    ran it (benchmarks.run's full pass) skip re-derivation."""
    uncap = depth_uncapping()
    table = depth_table()
    parity = ring_bit_parity()
    if pr8 is None:
        pr8 = ir_parity.build_report(quick)
    ok8 = pr8["acceptance"]
    return {
        "pr": 9,
        "benchmark": "dtype_window",
        "fingerprint": device_fingerprint(),
        "depth_uncapping": uncap,
        "depth_table": table,
        "ring_bit_parity": parity,
        "pr8_ir_parity": pr8,
        "acceptance": {
            "trapezoid_f32_capped_at_2": uncap["trapezoid_f32"]
            ["max_depth"] == 2,
            "ring_bf16_depth_ge_4": uncap["ring_bf16"]["max_depth"] >= 4,
            "achieved_traffic_cut": uncap["traffic_cut"],
            "traffic_cut_ok": uncap["traffic_cut"] >= 1.5,
            "ring_never_shallower_ok": table["ring_never_shallower"],
            "ring_deeper_somewhere_ok": table["ring_deeper_somewhere"],
            "ring_bitwise_ok": parity["all_bitwise"],
            # PR8 gates (which include PR7 ⊃ … ⊃ PR1) ride along.
            "pr8_spellings_bitwise_ok": ok8["spellings_bitwise_ok"],
            "pr8_spellings_one_key_ok": ok8["spellings_one_key_ok"],
            "pr8_bc_oracle_ok": ok8["bc_oracle_ok"],
            "pr8_mesh_bitwise_ok": ok8["mesh_bitwise_ok"],
            "pr8_mesh_no_host_pad_ok": ok8["mesh_no_host_pad_ok"],
            "pr7_reconcile_ok": ok8["pr7_reconcile_ok"],
            "pr6_never_slower_ok": ok8["pr6_never_slower_ok"],
            "pr5_sharded_bitwise_ok": ok8["pr5_sharded_bitwise_ok"],
            "pr4_flop_reduction_ok": ok8["pr4_flop_reduction_ok"],
            "pr3_fused_traffic_ok": ok8["pr3_fused_traffic_ok"],
            "pr2_planned_le_legacy_ok": ok8["pr2_planned_le_legacy_ok"],
            "pr1_traffic_ok": ok8["pr1_traffic_ok"],
        },
    }


def main(quick: bool = True, json_path: str | None = None,
         pr8: dict | None = None) -> dict:
    report, us = timed(build_report, quick, pr8)
    ok = report["acceptance"]
    emit_bench(
        "dtype_window",
        {
            "trapezoid_f32_capped_at_2": ok["trapezoid_f32_capped_at_2"],
            "ring_bf16_depth_ge_4": ok["ring_bf16_depth_ge_4"],
            "traffic_cut": ok["achieved_traffic_cut"],
            "traffic_cut_ok": ok["traffic_cut_ok"],
            "ring_bitwise_ok": ok["ring_bitwise_ok"],
        },
        report,
        json_path=json_path,
        us=us,
    )
    return report


if __name__ == "__main__":
    rep = main()
    print(json.dumps(rep["acceptance"], indent=2))
