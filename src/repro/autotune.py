"""Command line of the measured-cost autotuner (DESIGN.md §11):
``python -m repro.autotune 64x64x128`` races the planner's top-k
candidate plans on the live backend and keeps the measured winner;
``--smoke`` runs the CI gates.

It sits above the call entry, through whose :func:`repro.entry.run` the
candidates run; :mod:`repro.plan.tune` holds the tuner itself and never
imports the entry.  The entry (and with it jax) is imported only after
``--devices`` has set ``XLA_FLAGS``.
"""

from __future__ import annotations

import argparse
import sys

from repro.plan.cache import PlanCache
from repro.plan.planner import Planner
from repro.plan.tune import AutoTuner, format_record
from repro.plan.tunedb import TunedPlanDB, TuneRecord


def smoke() -> int:
    """CI gate: tune one tiny grid end-to-end (k=2, n=3 reps, interpret
    mode on CPU), assert the §11 promises — never_slower holds, the
    record round-trips, a Planner with the DB attached serves the
    measured winner on a warm hit in < 1 ms without re-measuring."""
    import time

    from repro.core.cache_fitting import star_stencil
    from repro.entry import run

    db = TunedPlanDB(persistent=False)
    tuner = AutoTuner(
        db=db,
        planner=Planner(cache=PlanCache(persistent=False)),
        k=2, reps=3, warmup=1,
    )
    kw = dict(
        shape=(16, 16, 128), offsets=star_stencil(3, 1),
        vmem_budget=256 * 1024, aligned=True,
    )
    t0 = time.perf_counter()
    rec = tuner.tune(runner=run, **kw)
    tune_s = time.perf_counter() - t0
    assert rec.never_slower, "never_slower gate failed"
    assert rec.speedup_vs_analytic >= 1.0
    assert len(rec.candidates) >= 1
    assert TuneRecord.from_dict(rec.to_dict()) == rec, "record round-trip"
    print(format_record(rec))

    # Warm preference: the planner serves the measured winner, fast.
    planner = Planner(cache=PlanCache(persistent=False), tuned_db=db)
    measured_before = db.stats["misses"]
    warm = []
    for _ in range(3):  # best-of-3: absorb one-time fingerprint warm-up
        t0 = time.perf_counter()
        served = planner.plan(**kw)
        warm.append((time.perf_counter() - t0) * 1e3)
        assert planner.last_plan_tuned, "warm hit not served from tuned DB"
        assert served == rec.winner_plan
    assert db.stats["misses"] == measured_before, "warm hit re-measured"
    warm_ms = min(warm)
    assert warm_ms < 1.0, f"warm tuned hit took {warm_ms:.2f} ms"
    print(
        f"tune smoke: {len(rec.candidates)} candidates in {tune_s:.2f} s, "
        f"winner {rec.winner} ({rec.speedup_vs_analytic:.3f}x), "
        f"warm_hit={warm_ms:.3f} ms  OK"
    )

    # §15 variant race: a fused chain must put the window flip and the
    # bf16/int8 storage variants on the track.  The int8-quantized ring
    # rows are advisory — measured, recorded, never the winner — and the
    # never-slower gate must still hold over the eligible rows.
    t0 = time.perf_counter()
    chain = tuner.tune(
        runner=run, shape=(32, 256), offsets=star_stencil(2, 1),
        time_steps=3, vmem_budget=256 * 1024, aligned=True,
    )
    chain_s = time.perf_counter() - t0
    assert chain.never_slower, "chain never_slower gate failed"
    kinds = {c.window_kind for c in chain.candidates}
    assert kinds >= {"ring", "trapezoid"}, f"window race missing: {kinds}"
    named = {
        dt for c in chain.candidates if c.stage_dtypes
        for dt in c.stage_dtypes if dt is not None
    }
    assert "int8" in named and "bfloat16" in named, (
        f"dtype variants missing from the race: {named}"
    )
    assert all(
        c.advisory for c in chain.candidates if c.stage_dtypes
    ), "a numerics-changing dtype row raced as winner-eligible"
    assert not chain.candidates[chain.winner].advisory
    assert TuneRecord.from_dict(chain.to_dict()) == chain
    print(format_record(chain))
    print(
        f"tune smoke (§15 chain): {len(chain.candidates)} rows in "
        f"{chain_s:.2f} s, windows={sorted(kinds)}, "
        f"advisory dtypes={sorted(named)}  OK"
    )
    return 0


def _parse_shape(s: str) -> tuple[int, ...]:
    for sep in ("x", ","):
        if sep in s:
            return tuple(int(p) for p in s.split(sep) if p)
    return (int(s),)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.autotune",
        description=(
            "Race the planner's top-k candidate plans on the live backend "
            "and persist the measured winner (DESIGN.md §11)."
        ),
    )
    ap.add_argument("shape", nargs="?", default="64x64x128",
                    help="grid shape, e.g. 64x64x128")
    ap.add_argument("--stencil", default="star:2",
                    help="star:R or box:R (default star:2)")
    ap.add_argument("--geom", default="none",
                    help="cache geometry a,z,w for the analytic model "
                         "(default none = explicitly managed memory; pass "
                         "the same value used with repro.plan.explain so "
                         "the request keys match)")
    ap.add_argument("--dtype-bytes", type=int, default=4)
    ap.add_argument("--budget", type=int, default=None,
                    help="VMEM budget in bytes (default: planner default)")
    ap.add_argument("--time-steps", type=int, default=1,
                    help="tune the T-application fused chain (§8)")
    ap.add_argument("--num-shards", type=int, default=1,
                    help="tune the §10 column-sharded launch over N devices")
    ap.add_argument("--aligned", action="store_true",
                    help="restrict tiles to lane/sublane-aligned extents")
    ap.add_argument("-k", type=int, default=4,
                    help="candidates to race (default 4)")
    ap.add_argument("--reps", type=int, default=5,
                    help="timed reps per candidate (default 5)")
    ap.add_argument("--warmup", type=int, default=1,
                    help="un-timed warm-up calls per candidate (default 1)")
    ap.add_argument("--db", default=None,
                    help="tuned DB dir (default $REPRO_TUNED_DB_DIR or "
                         "~/.cache/repro/tuned)")
    ap.add_argument("--memory-only", action="store_true",
                    help="do not persist the record to disk")
    ap.add_argument("--force", action="store_true",
                    help="re-measure even when a warm entry exists")
    ap.add_argument("--devices", type=int, default=None,
                    help="force N host platform devices (sets XLA_FLAGS; "
                         "needed for --num-shards > 1 on CPU)")
    ap.add_argument("--json", action="store_true",
                    help="dump the tune record JSON")
    ap.add_argument("--smoke", action="store_true",
                    help="run the CI smoke gates instead")
    args = ap.parse_args(argv)

    if args.devices:
        # Must land before the first jax import (lazy imports everywhere
        # in repro.plan and in this module exist exactly so this still
        # works here).
        import os
        assert "jax" not in sys.modules, (
            "--devices must be set before jax is imported"
        )
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

    if args.smoke:
        return smoke()

    from repro.core.cache_fitting import box_stencil, star_stencil
    from repro.entry import run

    shape = _parse_shape(args.shape)
    kind, _, r = args.stencil.partition(":")
    r = int(r or 2)
    if kind == "star":
        offs = star_stencil(len(shape), r)
    elif kind == "box":
        offs = box_stencil(len(shape), r)
    else:
        raise SystemExit(f"unknown stencil spec {args.stencil!r}")

    db = TunedPlanDB(db_dir=args.db, persistent=not args.memory_only)
    tuner = AutoTuner(
        db=db, k=args.k, reps=args.reps, warmup=args.warmup,
        force=args.force,
    )
    geometry = None if args.geom.lower() == "none" else _parse_shape(args.geom)
    tuner.plan(
        runner=run, shape=shape, offsets=offs,
        dtype_bytes=args.dtype_bytes, vmem_budget=args.budget,
        geometry=geometry,
        time_steps=args.time_steps, num_shards=args.num_shards,
        aligned=args.aligned,
    )
    rec = tuner.last_record
    if args.json:
        import json
        print(json.dumps(rec.to_dict(), indent=2, sort_keys=True))
        return 0
    served = "warm DB hit (no re-measurement)" if tuner.last_plan_tuned \
        else "measured fresh"
    print(format_record(rec))
    print(f"  served: {served}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
