"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per benchmark (spec format).
``--full`` runs paper-scale sweeps; default is the quick CI-sized pass.
``--json [PATH]`` runs only the PR-tracked quant-race record (which
embeds the PR9 ring-window record, which embeds PR8's, PR7's, …, PR1's)
and writes it to PATH (default: ``BENCH_PR10.json`` at the repo root) —
the perf trajectory artifact scripts/ci.sh checks on every PR.
"""
from __future__ import annotations

import os
import sys

from .common import force_cpu_devices


def main() -> None:
    argv = sys.argv[1:]
    quick = "--full" not in argv
    force_cpu_devices()
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    if "--json" in argv:
        from . import quant_race
        from .common import gates_ok

        i = argv.index("--json")
        if i + 1 < len(argv) and not argv[i + 1].startswith("--"):
            path = argv[i + 1]
        else:
            path = os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "BENCH_PR10.json",
            )
        report = quant_race.main(quick, json_path=path)
        ok = report["acceptance"]
        print(
            f"wrote {path}: quant_race "
            f"int8[cut {ok['achieved_int8_traffic_cut']:.2f}x "
            f"ok={ok['int8_traffic_cut_ok']} "
            f"deeper={ok['int8_fuses_deeper_ok']} "
            f"band={ok['int8_within_band_ok']}] "
            f"bc[menu={ok['boundary_menu_ok']}] "
            f"race[windows={ok['race_both_windows_ok']} "
            f"advisory={ok['race_advisory_dtypes_ok']} "
            f"never_slower={ok['race_never_slower_ok']}] "
            f"pr9[capped={ok['pr9_trap_capped_ok']} "
            f"cut_ok={ok['pr9_traffic_cut_ok']} "
            f"bitwise={ok['pr9_ring_bitwise_ok']}] "
            f"pr8[bitwise={ok['pr8_spellings_bitwise_ok']} "
            f"bc={ok['pr8_bc_oracle_ok']} "
            f"mesh_no_pad={ok['pr8_mesh_no_host_pad_ok']}] "
            f"pr7[reconcile={ok['pr7_reconcile_ok']}] "
            f"pr6[never_slower={ok['pr6_never_slower_ok']}] "
            f"pr5[bitwise={ok['pr5_sharded_bitwise_ok']}] "
            f"pr4[flops_ok={ok['pr4_flop_reduction_ok']}] "
            f"pr3[traffic_ok={ok['pr3_fused_traffic_ok']}] "
            f"pr2[planned<=legacy={ok['pr2_planned_le_legacy_ok']}] "
            f"pr1[traffic={ok['pr1_traffic_ok']}]"
        )
        if not gates_ok(ok):
            sys.exit(1)  # the perf gate IS the CI signal — fail loudly
        return
    from . import (
        autotune, bounds_table, dtype_window, fig4_miss_reduction,
        fig5_unfavorable, ir_parity, obs_overhead, padding_effect,
        planner_traffic, quant_race, roofline_report, shard_columns,
        stage_chain, sweep_traffic, temporal_fusion, tpu_tiling,
    )
    fig4_miss_reduction.main(quick)
    fig5_unfavorable.main(quick)
    bounds_table.main(quick)
    padding_effect.main(quick)
    tpu_tiling.main(quick)
    # The PR records nest (PR5 ⊃ PR4 ⊃ PR3 ⊃ PR2 ⊃ PR1); build each once
    # and pass the embedded reports down instead of re-deriving per level.
    pr1 = sweep_traffic.main(quick)
    pr2 = planner_traffic.main(quick, pr1=pr1)
    pr3 = temporal_fusion.main(quick, pr2=pr2)
    pr4 = stage_chain.main(quick, pr3=pr3)
    pr5 = shard_columns.main(quick, pr4=pr4)
    pr6 = autotune.main(quick, pr5=pr5)
    pr7 = obs_overhead.main(quick, pr6=pr6)
    pr8 = ir_parity.main(quick, pr7=pr7)
    pr9 = dtype_window.main(quick, pr8=pr8)
    quant_race.main(quick, pr9=pr9)
    roofline_report.main(quick)


if __name__ == "__main__":
    main()
