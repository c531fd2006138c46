#!/usr/bin/env python3
"""Smoke run of the stencil engine on a TPU, through its public entry points.

    python chip_smoke.py            # phases a-d on one chip
    python chip_smoke.py --chips 4  # column-sharded star on four chips

One chip (the default):

  a  the paper's 13-point star(3,2), one application, 512^3 f32
     (``stencil_pallas``);
  b  the same star as a T=3 Jacobi chain, 256^3 f32 (``stencil_iterate``);
     the plan must fuse at least two stages into one launch;
  c  the T=4 chain with bf16 frontiers (the ring chain of BENCH_PR9):
     bf16 input, stage dtypes bf16, bf16, bf16, f32, 256^3
     (``stencil_iterate(dtypes=...)``);
  d  a 2-D 5-point Jacobi, 16384^2 f32, which sweeps along the sublane
     axis (``ir.run_program``).

``--chips 4`` runs only the column-sharded launch (``num_shards=4``) of
the star at 512^3 f32, T=1 and T=3, against the single-device result of
the same process, and prints each device's memory statistics.

Every phase is compared with the ``kernels/ref.py`` oracle, computed on
the chip in f32 at ``highest`` precision, within a tolerance that states
its reason.  The lines before the last give, per phase, the shape, the
plan (tile, sweep axis, fused depth), ``max|err|`` against its tolerance,
the compile seconds and the wall time per call: a smoke figure, not a
benchmark.  The last line is one JSON object::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The script exits non-zero, and prints no such line, before any work when
JAX finds no TPU, and after any phase that fails.  Everything runs in this
one process.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
import traceback

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
F32_EPS = 2.0 ** -23
BF16_UNIT = 2.0 ** -8  # unit roundoff of bf16 (8 significand bits)
REPEATS = 5
SHARDED_SHAPE = (512, 512, 512)


def tpu_devices():
    """The attached TPU devices; exits non-zero when there are none."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU attached (JAX platform "
            f"{devices[0].platform!r}); nothing was run",
            file=sys.stderr,
        )
        sys.exit(2)
    return devices


def f32_tol(steps, taps, weights, umax):
    """f32 rounding bound: each application sums ``taps`` products in some
    order (``gamma_{taps+1}``), and ``steps`` applications compound it by
    at most ``S = max(1, sum|w|)`` per step."""
    s = max(1.0, float(sum(abs(w) for w in weights)))
    return steps * (taps + 1) * F32_EPS * s ** steps * umax


def bf16_band(roundings, steps, taps, weights, umax):
    """bf16 frontier band: each of ``roundings`` bf16 stores of an
    intermediate rounds it by at most one bf16 unit roundoff, and later
    stages carry that error at most ``S`` per step — the f32 bound on
    top."""
    s = max(1.0, float(sum(abs(w) for w in weights)))
    return roundings * BF16_UNIT * s ** steps * umax + f32_tol(
        steps, taps, weights, umax
    )


def timed_calls(compiled, *args):
    """Wall seconds per call of an already compiled function."""
    out = None
    times = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        out = compiled(*args)
        out.block_until_ready()
        times.append(time.perf_counter() - t0)
    return out, times


def compile_entry(fn, *args):
    """jit + lower + compile one entry-point call; returns the compiled
    function, its compile seconds and the kernel launches it planned.
    Every launch must compile for the chip: none interpreted, and the
    ``interpret_fallback`` counter still 0."""
    import jax
    from repro import obs

    t0 = time.perf_counter()
    with obs.recording() as rec:
        compiled = jax.jit(fn).lower(*args).compile()
    seconds = time.perf_counter() - t0
    launches = [s.args for s in rec.spans if s.name == "kernel_launch"]
    if not launches:
        raise AssertionError("no kernel launch was traced")
    if any(a["interpret"] for a in launches):
        raise AssertionError("a kernel launch ran in interpret mode")
    fallbacks = rec.counters.get("interpret_fallback", 0)
    if fallbacks:
        raise AssertionError(f"interpret_fallback counter is {fallbacks}")
    return compiled, seconds, launches


def reference(offsets, weights, steps):
    """The ``kernels/ref.py`` oracle, iterated ``steps`` times on the chip
    in f32 at highest precision."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.ref import stencil_ref

    def run(u):
        v = u.astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            for _ in range(steps):
                v = stencil_ref(v, offsets, weights)
        return v

    return jax.jit(run)


def report(name, shape, launches, err, tol, compile_s, times):
    plans = sorted({
        (tuple(a["tile"]), a["sweep_axis"], a["fused_depth"])
        for a in launches
    })
    print(
        f"phase {name}: shape {tuple(shape)} plan "
        + ", ".join(f"tile {t} sweep {s} depth {d}" for t, s, d in plans)
        + f" ({len(launches)} launch(es)); max|err| {err!r} <= tol {tol!r}; "
        f"compile {compile_s:.2f} s; smoke wall per call (not a benchmark) "
        f"median {statistics.median(times) * 1e3:.3f} ms "
        f"min {min(times) * 1e3:.3f} ms",
        flush=True,
    )


def star_operators():
    """The paper's 13-point star(3,2) with its 4th-order Laplacian
    weights, and one explicit diffusion step ``u + Lap4(u) / 16`` on the
    same offsets: a stable Jacobi-type operator (sum|w| = 1.0625) for the
    chains."""
    from repro.kernels.ref import star_weights_2nd_order

    star, star_w = star_weights_2nd_order(3, 2)
    diff_w = [
        (1.0 + w / 16.0) if not any(o) else w / 16.0
        for o, w in zip(star.tolist(), star_w)
    ]
    return star, star_w, diff_w


def one_chip_phases():
    """(name, shape, dtype, entry point, steps, weights, offsets, bf16
    roundings, extra check) for phases a-d."""
    from repro import ir
    from repro.core.cache_fitting import star_stencil
    from repro.kernels.stencil import stencil_iterate, stencil_pallas

    star, star_w, diff_w = star_operators()
    jac, jac_w = star_stencil(2, 1), [0.0, 0.25, 0.25, 0.25, 0.25]
    jacobi = ir.stencil_program(jac, jac_w, d=2)
    bf16_chain = ["bfloat16", "bfloat16", "bfloat16", "float32"]

    def fused(launches):
        if len(launches) != 1 or launches[0]["fused_depth"] < 2:
            raise AssertionError(
                f"chain did not fuse into one launch: {launches}"
            )

    return [
        ("a", (512,) * 3, "float32",
         lambda u: stencil_pallas(u, star, star_w),
         1, star_w, star, 0, None),
        ("b", (256,) * 3, "float32",
         lambda u: stencil_iterate(u, star, diff_w, 3),
         3, diff_w, star, 0, fused),
        ("c", (256,) * 3, "bfloat16",
         lambda u: stencil_iterate(u, star, diff_w, 4, dtypes=bf16_chain),
         4, diff_w, star, 3, fused),
        ("d", (16384,) * 2, "float32",
         lambda u: ir.run_program(jacobi, u),
         1, jac_w, jac, 0, None),
    ]


def run_one_chip(seed):
    import jax
    import jax.numpy as jnp

    failed = []
    for i, (name, shape, dtype, fn, steps, w, offs, roundings, check) in (
        enumerate(one_chip_phases())
    ):
        try:
            key = jax.random.PRNGKey(seed + i)
            u = jax.random.normal(key, shape, jnp.float32).astype(dtype)
            compiled, compile_s, launches = compile_entry(fn, u)
            if check is not None:
                check(launches)
            out, times = timed_calls(compiled, u)
            ref = reference(offs, w, steps)(u)
            err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
            umax = float(jnp.max(jnp.abs(u.astype(jnp.float32))))
            taps = len(offs)
            tol = (
                bf16_band(roundings, steps, taps, w, umax) if roundings
                else f32_tol(steps, taps, w, umax)
            )
            report(name, shape, launches, err, tol, compile_s, times)
            if not err <= tol:
                raise AssertionError(f"max|err| {err} exceeds {tol}")
            del u, out, ref
        except Exception:
            traceback.print_exc()
            print(f"phase {name}: FAILED", flush=True)
            failed.append(name)
    return failed


def run_four_chips(seed, devices):
    import jax
    import jax.numpy as jnp
    from repro.kernels.stencil import stencil_iterate

    if len(devices) < 4:
        print(f"--chips 4 needs 4 devices, found {len(devices)}",
              file=sys.stderr)
        return ["devices"]
    star, _, diff_w = star_operators()
    shape = SHARDED_SHAPE
    failed = []
    u = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    for steps in (1, 3):
        name = f"shard4_T{steps}"
        try:
            single, _, _ = compile_entry(
                lambda v: stencil_iterate(v, star, diff_w, steps), u
            )
            sharded, compile_s, launches = compile_entry(
                lambda v: stencil_iterate(v, star, diff_w, steps,
                                          num_shards=4),
                u,
            )
            if any(a["num_shards"] != 4 for a in launches):
                raise AssertionError(f"launches not 4-way: {launches}")
            ref_out, _ = timed_calls(single, u)
            out, times = timed_calls(sharded, u)
            spans = len(out.sharding.device_set)
            if spans != 4:
                raise AssertionError(f"output spans {spans} devices, not 4")
            on_dev0 = jax.device_put(out, devices[0])
            equal = bool(jnp.array_equal(on_dev0, ref_out))
            err = float(jnp.max(jnp.abs(on_dev0 - ref_out)))
            report(name, shape, launches, err, 0.0, compile_s, times)
            print(f"phase {name}: bit-wise equal to single device: {equal}; "
                  f"output spans {spans} devices", flush=True)
            for dev in devices[:4]:
                stats = dev.memory_stats() or {}
                print(f"phase {name}: {dev} bytes_in_use "
                      f"{stats.get('bytes_in_use')} peak_bytes_in_use "
                      f"{stats.get('peak_bytes_in_use')}", flush=True)
            if not equal:
                raise AssertionError("sharded result differs from single")
            del out, on_dev0, ref_out
        except Exception:
            traceback.print_exc()
            print(f"phase {name}: FAILED", flush=True)
            failed.append(name)
    return failed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the column-sharded path on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = tpu_devices()
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    from repro.runtime.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        failed = run_four_chips(args.seed, devices)
    else:
        failed = run_one_chip(args.seed)
    if failed:
        print(f"chip_smoke: failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
