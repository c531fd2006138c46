#!/usr/bin/env python3
"""The lower-precision control of a cell, and the program's own readings
over many seeds, each in one process on the chip.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --seconds 2
    python3 bench/control.py --workload <cell> --seeds 1 2 3 --reference
    python3 bench/control.py --workload <cell> --seeds 1 ... 12 --seconds 2 --program

The control runs the cell's window through the program's own bfloat16
path: the grid is stored in bfloat16, so every stage of the program reads
and writes bfloat16 (its multiply-adds stay f32).  Where that path does
not run, ``--reference`` puts the plain reference in the program's place
instead, every value and product in bfloat16.  The check compares the
kept calls with the f32 reference, as a run of the benchmark does, and
must come out not correct.  ``--program`` runs the cell as configured
instead: the readings that set the lower end of the cell's limit.  Each
seed prints one line; the last line is a JSON summary.  The benchmark's
own runs never run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

CONTROL_DTYPE = "bfloat16"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--program", action="store_true",
                    help="run the program as configured, not the control")
    ap.add_argument("--reference", action="store_true",
                    help="control: the bfloat16 reference in the program's "
                    "place")
    args = ap.parse_args(argv)

    from bench import harness, reference

    dtype = None if args.program or args.reference else CONTROL_DTYPE
    wrap = None
    if args.reference:
        benchmark = harness.load_benchmark()
        cell = harness.find_cell(benchmark, args.workload)
        config = harness.load_json(harness.BENCH_DIR, "configs",
                                   cell["config"])
        traffic = harness.load_json(harness.BENCH_DIR, "traffic",
                                    cell["traffic"])
        op = config["operator"]

        def wrap(entry):
            return lambda u: reference.apply(
                u, op["offsets"], op["weights"], traffic["applications"],
                config["boundary"], CONTROL_DTYPE,
            )
    readings = {}
    for seed in args.seeds:
        try:
            r = harness.run(args.workload, seed, args.seconds, False,
                            time.perf_counter(), input_dtype=dtype,
                            wrap_entry=wrap)
        except harness.NoChip as e:
            print(f"control: {e}; nothing was measured", file=sys.stderr)
            return 2
        value = r["check"]["max_rel_err"]["value"]
        readings[seed] = value
        print(f"{'program' if args.program else 'control'} "
              f"{args.workload} seed {seed} max_rel_err {value!r} "
              f"correct {r['correct']} calls {r['attempted']}", flush=True)
    print(json.dumps({
        "workload": args.workload,
        "mode": "program" if args.program else
        f"control:{CONTROL_DTYPE}{':reference' if args.reference else ''}",
        "readings": readings,
        "max": max(readings.values()),
        "min": min(readings.values()),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
