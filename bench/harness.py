"""The benchmark's run: one cell, one seed, one measured window.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by name:

* ``configs/<config>.json``: the deployment (operator as offsets and
  weights, grid, dtype, boundary, chips, shard layout, source);
* ``traffic/<mix>.json``: the public entry the window drives, the
  applications per call, whether each call's output feeds the next, and
  the sync rule;
* ``metrics/<metric>.py``: a reader with ``read(ctx)`` that returns the
  metric, or ``None`` where the run has nothing for it to read;
* ``limits/<cell>.json``: the limit on the number the check compares,
  with the readings it was set from.

``BENCHMARK.json`` at the root of the checkout names the cells, and which
metrics each reports.  A run:

1. refuses to go on unless JAX's first device is a TPU and there are as
   many as the cell asks for;
2. makes the grid on the device from the seed, builds the entry, and
   warms it up (compiles or loads from JAX's persistent cache, plans);
3. runs a closed loop for ``--seconds``: each call goes through the eager
   public entry and ends in ``block_until_ready`` before the next one;
4. with ``--trace 1`` the loop runs under ``jax.profiler`` instead, and
   the per-layer metrics are read from the trace;
5. compares the outputs of two calls of the window (one drawn from the
   seed, and the last) with the plain reference;
6. prints the result as one JSON line, last on standard output.
"""

from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import random
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# The outputs of one call drawn from the first KEEP_SPAN calls of the
# window are kept for the check, beside those of the last call.
KEEP_SPAN = 8
# The traced window is at most this long: a trace of a few seconds holds
# thousands of calls, and its reduction has to fit in the run's time.
TRACE_SECONDS = 3.0


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_json(bench_dir: Path, kind: str, name: str) -> dict:
    with open(bench_dir / kind / f"{name}.json") as f:
        return json.load(f)


def find_cell(benchmark: dict, name: str) -> dict:
    for cell in benchmark["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(benchmark: dict, cell_name: str, group: str) -> list:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) that
    the cell reports."""
    return [
        m for m in benchmark[group]
        if "workloads" not in m or cell_name in m["workloads"]
    ]


def load_reader(bench_dir: Path, metric: str):
    """The ``read`` function of ``metrics/<metric>.py``."""
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def chip_devices(chips: int):
    """The TPU devices the cell runs on; :class:`NoChip` otherwise."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(
            f"JAX's first device is {devices[0].platform!r}, not a TPU"
        )
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache/`` at the root of the checkout (a
    fixed path: the path is part of the cache's key).  Every compiled
    program is written, however quickly it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def input_sharding(config: dict, devices):
    """Where the grid lives: on the one chip, or split along the
    configuration's shard axis over the column mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    shard = config.get("shard")
    if not shard:
        return jax.sharding.SingleDeviceSharding(devices[0])
    from repro.launch.mesh import make_column_mesh

    mesh = make_column_mesh(int(shard["num_shards"]), devices=devices)
    spec = [None] * len(config["grid"])
    spec[int(shard["axis"])] = mesh.axis_names[0]
    return NamedSharding(mesh, PartitionSpec(*spec))


def make_grid(config: dict, seed: int, sharding, dtype=None):
    """The cell's input, standard normal in f32 from the seed, made on the
    device(s) in one jitted call and stored at ``dtype`` (default: the
    configuration's)."""
    import jax
    import jax.numpy as jnp

    shape = tuple(int(n) for n in config["grid"])
    dt = jnp.dtype(dtype or config["dtype"])
    gen = jax.jit(
        lambda k: jax.random.normal(k, shape, jnp.float32).astype(dt),
        out_shardings=sharding,
    )
    return gen(seed_key(seed))


def make_entry(config: dict, traffic: dict, **extra):
    """The public entry one call of the window makes, as a function of
    the call's input.  ``extra`` keyword arguments go to the entry as
    they are (the compile rehearsal passes ``interpret`` and ``mesh``)."""
    import numpy as np

    from repro import ir
    from repro.kernels.stencil import stencil_iterate

    offsets = np.asarray(config["operator"]["offsets"], dtype=np.int64)
    weights = [float(w) for w in config["operator"]["weights"]]
    steps = int(traffic["applications"])
    boundary = config["boundary"]
    kw = {}
    if config.get("shard"):
        kw = {"num_shards": int(config["shard"]["num_shards"]),
              "shard_axis": int(config["shard"]["axis"])}
    kw.update(extra)
    entry = traffic["entry"]
    if entry == "stencil_iterate":
        if boundary != "zero":
            raise ValueError("stencil_iterate has only the zero boundary")
        return lambda u: stencil_iterate(u, offsets, weights, steps, **kw)
    if entry == "run_program":
        program = ir.stencil_program(
            offsets, weights, steps, d=offsets.shape[1],
            boundary=None if boundary == "zero" else boundary,
        )
        return lambda u: ir.run_program(program, u, **kw)
    raise ValueError(f"unknown entry {entry!r}")


def closed_loop(entry, x, feed_back, seconds, keep_at, annotate=False):
    """Call ``entry`` until ``seconds`` have passed (and at least
    ``keep_at + 1`` calls were made), each call ready before the next.

    Returns the per-call latencies and host dispatch times in seconds, the
    window's length, and the kept ``(input, output)`` pairs of call
    ``keep_at`` and of the last call (one pair where they are the same
    call)."""
    import jax

    if annotate:
        dispatch_span = lambda: jax.profiler.TraceAnnotation("bench.dispatch")
        block_span = lambda: jax.profiler.TraceAnnotation("bench.block")
    else:
        dispatch_span = block_span = contextlib.nullcontext
    latencies, dispatch = [], []
    kept = None
    n = 0
    start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with dispatch_span():
            y = entry(x)
        t1 = time.perf_counter()
        with block_span():
            y.block_until_ready()
        t2 = time.perf_counter()
        latencies.append(t2 - t0)
        dispatch.append(t1 - t0)
        if n == keep_at:
            kept = (x, y)
        n += 1
        if t2 - start >= seconds and n > keep_at:
            window = t2 - start
            last = (x, y)
            break
        if feed_back:
            x = y
    pairs = [last] if kept[1] is last[1] else [kept, last]
    return latencies, dispatch, window, pairs


def percentile(values, q):
    """The ``q``-th percentile (0-100), linear between order statistics."""
    s = sorted(values)
    k = (len(s) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def check(kept, config: dict, traffic: dict) -> float:
    """The widest gap between a kept output and the reference applied to
    its input, relative to ``(sum|w|)**steps * max|input|``: the largest
    value the result could take.  The largest over the kept calls."""
    from . import reference

    op = config["operator"]
    steps = int(traffic["applications"])
    growth = sum(abs(float(w)) for w in op["weights"]) ** steps
    worst = 0.0
    for inp, out in kept:
        gap, big = reference.compare(
            inp, out, op["offsets"], op["weights"], steps, config["boundary"]
        )
        worst = max(worst, gap / (growth * big) if big else float("inf"))
    return worst


def compiled_only(rec) -> None:
    """Every kernel launch of the entry compiled for the chip: none ran
    in the Pallas interpreter, and the ``interpret_fallback`` counter is
    0 (the program's own telemetry, recorded during the first call)."""
    launches = [s.args for s in rec.spans if s.name == "kernel_launch"]
    if not launches:
        raise RuntimeError("the entry traced no kernel launch")
    if any(a["interpret"] for a in launches):
        raise RuntimeError("a kernel launch ran in interpret mode")
    if rec.counters.get("interpret_fallback", 0):
        raise RuntimeError("the interpret_fallback counter is not 0")


def memory_peak(devices) -> int:
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices
    ]
    return int(max(peaks))


def traced_window(entry, x, feed_back, seconds, keep_at, devices):
    """The closed loop under ``jax.profiler``; returns what
    :func:`closed_loop` returns and the trace's reduction."""
    import jax

    from . import trace_reduce

    logdir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        # The Python tracer would time every Python call of the entry
        # and inflate the host time the trace is read for.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        with jax.profiler.trace(logdir, profiler_options=options):
            result = closed_loop(entry, x, feed_back, seconds, keep_at,
                                 annotate=True)
        paths = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not paths:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        reduction = trace_reduce.reduce_file(
            paths[0], devices=[d.id for d in devices]
        )
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    return result, reduction


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, root: Path = ROOT, bench_dir: Path = BENCH_DIR,
        require_chip: bool = True, input_dtype: str | None = None,
        wrap_entry=None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``input_dtype`` stores the grid (and so the program's whole chain) at
    another dtype: the lower-precision control.  ``wrap_entry`` wraps the
    entry the window calls: the tests plant faults through it.  Neither
    is reachable from the command line.
    """
    import jax

    from . import work

    benchmark = load_benchmark(root)
    cell = find_cell(benchmark, cell_name)
    config = load_json(bench_dir, "configs", cell["config"])
    traffic = load_json(bench_dir, "traffic", cell["traffic"])
    if traffic.get("sync", "every_call") != "every_call":
        raise ValueError(f"unknown sync rule {traffic['sync']!r}")
    chips = int(cell["chips"])
    if require_chip:
        devices = chip_devices(chips)
    else:
        devices = jax.devices()[:chips]
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    enable_compile_cache(root)
    from repro import obs

    # -- set-up: grid, entry, warm-up ----------------------------------------
    marks = {"devices_s": time.perf_counter() - t_start}
    sharding = input_sharding(config, devices)
    u = make_grid(config, seed, sharding, input_dtype)
    u.block_until_ready()
    marks["grid_s"] = time.perf_counter() - t_start
    entry = make_entry(config, traffic)
    if wrap_entry is not None:
        entry = wrap_entry(entry)
    feed_back = bool(traffic["feed_back"])
    with obs.recording() as rec:  # compile (or load) and plan
        entry(u).block_until_ready()
    if require_chip and wrap_entry is None:
        compiled_only(rec)
    marks["first_call_s"] = time.perf_counter() - t_start
    entry(u).block_until_ready()  # one warm call
    keep_at = random.Random(seed).randrange(KEEP_SPAN)
    setup_s = time.perf_counter() - t_start

    # -- the measured window ---------------------------------------------------
    reduction = None
    if trace:
        (lat, dispatch, window, kept), reduction = traced_window(
            entry, u, feed_back, min(seconds, TRACE_SECONDS), keep_at,
            devices,
        )
    else:
        lat, dispatch, window, kept = closed_loop(
            entry, u, feed_back, seconds, keep_at
        )
    peak = memory_peak(devices)
    del u

    # -- the check -----------------------------------------------------------
    rel_err = check(kept, config, traffic)
    limit = cell_limit(bench_dir, cell_name)
    correct = rel_err <= limit

    w = work.call_work(config, traffic)
    metrics = {}
    if not trace:
        values = {
            "gpts_per_s": len(lat) * w["points"] * w["applications"]
            / window / 1e9,
            "call_p95_ms": percentile(lat, 95) * 1e3,
            "setup_s": setup_s,
        }
        for m in cell_metrics(benchmark, cell_name, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = {
            "reduction": reduction,
            "calls": len(lat),
            "dispatch_s": dispatch,
            "latency_s": lat,
            "work": w,
            "peaks": peaks_for(bench_dir, devices[0].device_kind),
            "chips": chips,
        }
        for m in cell_metrics(benchmark, cell_name, "per_layer"):
            value = load_reader(bench_dir, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    result = {
        "correct": bool(correct),
        "attempted": len(lat),
        "failed": 0,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = reduction["busy_s"]
        device["window_s"] = reduction["window_s"]
        result["breakdown"] = {
            "device_ops": reduction["top_ops"],
            "idle_gaps": reduction["idle_gaps"],
        }
    # Where set-up and the window's time went on the host (seconds since
    # process start at the end of each set-up step; the window's mean
    # split), so that a reader of the ledger can tell which part of a run
    # that reads far off was slow.  No check reads these keys.
    result["setup"] = marks
    result["window"] = {
        "seconds": window,
        "latency_median_ms": percentile(lat, 50) * 1e3,
        "dispatch_mean_us": sum(dispatch) / len(dispatch) * 1e6,
        "wait_mean_us": (sum(lat) - sum(dispatch)) / len(lat) * 1e6,
    }
    result["check"] = {"max_rel_err": {"value": rel_err, "limit": limit}}
    return result


def cell_limit(bench_dir: Path, cell_name: str) -> float:
    """The limit on the cell's compared number, from
    ``limits/<cell>.json``."""
    return float(load_json(bench_dir, "limits", cell_name)["max_rel_err"])


def peaks_for(bench_dir: Path, device_kind: str) -> dict:
    """The row of ``peaks.json`` for this device; an unknown kind is an
    error, never a default."""
    with open(bench_dir / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(known: {sorted(table)})"
        )
    return table[device_kind]
