"""The harness finds configurations, traffic mixes and metrics by name,
runs every committed cell through to a correct result on the CPU at tiny
grids, and refuses to measure without a TPU."""

import hashlib
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
SEED = 2**33 + 12345  # wider than 32 bits, as a checker's seeds may be


def _run(root, cell, trace=False, **kw):
    return harness.run(cell, SEED, 0.3, trace, time.perf_counter(),
                       root=root, bench_dir=root / "bench",
                       require_chip=False, **kw)


def _digests(root):
    out = {}
    for dirpath, _, files in os.walk(root):
        for f in files:
            p = os.path.join(dirpath, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.mark.parametrize(
    "cell", [c["name"] for c in harness.load_benchmark()["workloads"]]
)
def test_committed_cell_runs_correct(tiny_root, cell):
    r = _run(tiny_root, cell)
    assert r["correct"], r["check"]
    assert r["attempted"] > 0 and r["failed"] == 0
    benchmark = harness.load_benchmark(tiny_root)
    assert set(r["metrics"]) == {
        m["name"] for m in harness.cell_metrics(benchmark, cell, "end_to_end")
    }
    assert list(r)[-1] == "check"
    assert r["check"]["max_rel_err"]["value"] <= \
        r["check"]["max_rel_err"]["limit"]


def test_new_config_traffic_and_metric_found_by_name(tiny_root):
    """A cell of a new deployment under a new traffic mix, with a new
    per-layer metric, from new files and new entries alone."""
    bench = tiny_root / "bench"
    before = _digests(bench)
    (bench / "configs" / "tiny5pt.json").write_text(json.dumps({
        "name": "tiny5pt", "grid": [16, 256], "dtype": "float32",
        "boundary": "zero", "chips": 1, "shard": None,
        "operator": {"offsets": [[0, 0], [-1, 0], [1, 0], [0, -1], [0, 1]],
                     "weights": [0.5, 0.125, 0.125, 0.125, 0.125]},
    }))
    (bench / "traffic" / "twice.json").write_text(json.dumps({
        "name": "twice", "entry": "stencil_iterate", "applications": 2,
        "feed_back": False, "sync": "every_call",
    }))
    (bench / "metrics" / "calls.count.py").write_text(
        "def read(ctx):\n    return float(ctx['calls'])\n"
    )
    (bench / "limits" / "tiny5pt.twice.json").write_text(
        json.dumps({"max_rel_err": 1e-5})
    )
    benchmark = json.loads((tiny_root / "BENCHMARK.json").read_text())
    benchmark["workloads"].append({"name": "tiny5pt.twice",
                                   "config": "tiny5pt", "traffic": "twice",
                                   "chips": 1, "why": "test"})
    benchmark["per_layer"].append({
        "name": "calls.count", "unit": "calls", "better": "higher",
        "source": "host_clock", "layer": "entry and planner",
        "moves": "gpts_per_s", "workloads": ["tiny5pt.twice"],
    })
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(benchmark))

    r = _run(tiny_root, "tiny5pt.twice")
    assert r["correct"], r["check"]
    assert set(r["metrics"]) == {"gpts_per_s", "call_p95_ms", "setup_s"}
    traced = _run(tiny_root, "tiny5pt.twice", trace=True)
    assert traced["correct"]
    assert traced["metrics"]["calls.count"]["value"] == traced["attempted"]
    assert "shard.collective_ms" not in traced["metrics"]
    after = _digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_no_tpu_exits_without_a_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "star13_512.step1", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == 2
    assert "not a TPU" in p.stderr
    assert "{" not in p.stdout


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks_for(harness.BENCH_DIR, "TPU v99")
    assert harness.peaks_for(harness.BENCH_DIR, "TPU v5 lite")[
        "hbm_bytes_per_s"] == 819e9


def test_interpreted_launch_is_refused():
    """On the chip a run refuses an entry whose kernels did not compile
    for it: the program's own launch spans say whether they did."""
    import jax.numpy as jnp

    from repro import obs

    config = harness.load_json(harness.BENCH_DIR, "configs", "star13_512")
    config["grid"] = [16, 16, 256]
    traffic = harness.load_json(harness.BENCH_DIR, "traffic", "step1")
    entry = harness.make_entry(config, traffic, interpret=True)
    with obs.recording() as rec:
        entry(jnp.ones(config["grid"], jnp.float32)).block_until_ready()
    with pytest.raises(RuntimeError, match="interpret mode"):
        harness.compiled_only(rec)
