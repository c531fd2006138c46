#!/usr/bin/env python3
"""Compile each cell's call for a described TPU v5e, with no chip attached.

    JAX_PLATFORMS=cpu python3 bench/aot_rehearsal.py [cell ...]
    JAX_PLATFORMS=cpu python3 bench/aot_rehearsal.py <config>:<traffic>:<chips>

For each cell (default: every cell in ``BENCHMARK.json``; a
``config:traffic:chips`` triple names a pairing that is not a cell) this
lowers the
entry the window drives, with ``interpret=False``, for one chip or all
four of a described ``v5e:2x2`` topology, at the cell's own shapes and
input layout, and compiles it with the chip's own compiler.  That refuses
what interpret mode lets through: DMAs off the (sublane, lane) grain,
more VMEM than a core has, a program that does not fit the device.  It
prints, per cell, whether the Mosaic kernel and the collectives are in
the compiled program and its memory analysis.  Run it by hand before a
call on the chip; it is not a test.
"""

import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies

    from bench import harness
    from repro.kernels import _backend
    from repro.launch.mesh import make_column_mesh

    # Compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep them out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    _backend.device_kind = lambda: topo.devices[0].device_kind
    benchmark = harness.load_benchmark()
    names = (argv if argv is not None else sys.argv[1:]) or [
        c["name"] for c in benchmark["workloads"]
    ]
    failed = []
    for name in names:
        if name.count(":") == 2:
            cfg, mix, chips = name.split(":")
            cell = {"config": cfg, "traffic": mix, "chips": int(chips)}
        else:
            cell = harness.find_cell(benchmark, name)
        config = harness.load_json(harness.BENCH_DIR, "configs",
                                   cell["config"])
        traffic = harness.load_json(harness.BENCH_DIR, "traffic",
                                    cell["traffic"])
        devices = topo.devices[: int(cell["chips"])]
        extra = {"interpret": False}
        if config.get("shard"):
            extra["mesh"] = make_column_mesh(
                int(config["shard"]["num_shards"]), devices=devices
            )
        sharding = harness.input_sharding(config, devices)
        arg = jax.ShapeDtypeStruct(tuple(config["grid"]),
                                   jax.numpy.dtype(config["dtype"]),
                                   sharding=sharding)
        t0 = time.perf_counter()
        try:
            entry = harness.make_entry(config, traffic, **extra)
            compiled = jax.jit(entry).lower(arg).compile()
        except Exception:
            traceback.print_exc()
            print(f"{name}: FAILED to compile", flush=True)
            failed.append(name)
            continue
        text = compiled.as_text()
        print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s; "
              f"tpu_custom_call {'tpu_custom_call' in text}; "
              f"collective-permute {'collective-permute' in text}; "
              f"{compiled.memory_analysis()}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
