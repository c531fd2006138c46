import json
import os
import shutil
import sys

import pytest

# The host platform is fixed at the first jax import: the sharded cell
# needs four CPU devices, pinned the way the repository's own tests pin
# them (this directory is collected before tests/).
_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
_SRC = os.path.join(_ROOT, "src")
for _p in (_ROOT, _SRC):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from repro.runtime import isa  # noqa: E402

isa.pin_xla_flags(n_devices=4)

# Grids small enough for the Pallas interpreter, each config's own shape
# otherwise (operator, boundary, dtype, shard layout).
TINY_GRIDS = {
    "star13_512": [16, 16, 256],
    "mg27_512": [32, 16, 128],
    "mg27_1024": [32, 16, 128],
    "jacobi2d_2800": [64, 256],
}
SHARDED_CELL = {"name": "mg27_512.apply_4chip", "config": "mg27_512",
                "traffic": "apply_fixed", "chips": 4, "why": "test"}


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout with ``BENCHMARK.json`` and ``bench/`` as committed, the
    configurations cut to tiny grids, and a ``cpu`` row in the peaks
    table so that a run can go on without a chip."""
    bench = tmp_path / "bench"
    shutil.copytree(
        os.path.join(_ROOT, "bench"), bench,
        ignore=shutil.ignore_patterns("tests", "__pycache__"),
    )
    shutil.copy(os.path.join(_ROOT, "BENCHMARK.json"), tmp_path)
    for name, grid in TINY_GRIDS.items():
        path = bench / "configs" / f"{name}.json"
        if path.exists():
            config = json.loads(path.read_text())
            config["grid"] = grid
            path.write_text(json.dumps(config))
    # The sharded cell's faults are tested whether or not the committed
    # benchmark measures the cell.
    benchmark = json.loads((tmp_path / "BENCHMARK.json").read_text())
    if not any(c["name"] == SHARDED_CELL["name"]
               for c in benchmark["workloads"]):
        benchmark["workloads"].append(SHARDED_CELL)
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(benchmark))
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["cpu"] = peaks["TPU v5 lite"]
    (bench / "peaks.json").write_text(json.dumps(peaks))
    return tmp_path
