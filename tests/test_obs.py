"""Telemetry subsystem (DESIGN.md §12): recorder, trace export, report
reconciliation, instrumentation of plan/cache/tune/launch layers, the
near-zero disabled path, and the obs-adjacent satellites (cache stats(),
interpret-fallback counting, explain --json, bench_history)."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.core.cache_fitting import star_stencil
from repro.obs.report import reconcile, summarize
from repro.obs.trace_event import validate_trace
from repro.plan import PlanCache, Planner
from repro.plan.tunedb import TunedPlanDB

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(autouse=True)
def _no_leaked_recorder():
    """Every test starts and ends with recording disabled."""
    assert obs.active() is None, "a previous test leaked a recorder"
    yield
    assert obs.active() is None, "this test leaked a recorder"


# ---------------------------------------------------------------------------
# Recorder core.
# ---------------------------------------------------------------------------

def test_recorder_spans_counters_events(tmp_path):
    path = str(tmp_path / "t.json")
    with obs.recording(path) as rec:
        assert obs.enabled() and obs.active() is rec
        with obs.span("plan", key="abc") as sp:
            sp.set(depth=3)
        obs.add("launches")
        obs.add("modeled_bytes", 1234)
        obs.add("modeled_bytes", 66)
        obs.event("interpret_fallback", backend="gpu")
    assert not obs.enabled()
    assert [s.name for s in rec.spans] == ["plan"]
    assert rec.spans[0].args == {"key": "abc", "depth": 3}
    assert rec.spans[0].dur_us >= 0.0
    assert rec.counters == {"launches": 1, "modeled_bytes": 1300}
    assert rec.events[0]["name"] == "interpret_fallback"
    # recording(path) wrote a valid trace on exit
    doc = validate_trace(json.load(open(path)))
    assert doc["otherData"]["counters"]["modeled_bytes"] == 1300


def test_recording_nests():
    with obs.recording() as outer:
        obs.add("n")
        with obs.recording() as inner:
            obs.add("n", 5)  # innermost recorder shadows
        assert obs.active() is outer
        obs.add("n")
    assert outer.counters == {"n": 2}
    assert inner.counters == {"n": 5}


def test_trace_event_shape():
    with obs.recording() as rec:
        with obs.span("kernel_launch", modeled_bytes=10):
            pass
        obs.add("launches")
        obs.event("mark")
    doc = rec.to_trace_events()
    validate_trace(doc)
    phs = {ev["ph"] for ev in doc["traceEvents"]}
    assert {"M", "X", "C", "i"} <= phs
    x = [e for e in doc["traceEvents"] if e["ph"] == "X"][0]
    assert x["name"] == "kernel_launch" and x["args"]["modeled_bytes"] == 10


def test_validate_trace_rejects_garbage():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace({"events": []})
    with pytest.raises(ValueError, match="unknown ph"):
        validate_trace({"traceEvents": [{"ph": "Z", "name": "x",
                                         "pid": 0, "tid": 0}]})
    with pytest.raises(ValueError, match="non-numeric"):
        validate_trace({"traceEvents": [
            {"ph": "X", "name": "x", "pid": 0, "tid": 0, "ts": "now"}
        ]})


def test_env_activation_writes_trace_at_exit(tmp_path):
    trace = tmp_path / "env.json"
    env = dict(os.environ)
    env["REPRO_TRACE"] = str(trace)
    env["PYTHONPATH"] = (
        str(ROOT / "src") + os.pathsep + env.get("PYTHONPATH", "")
    ).rstrip(os.pathsep)
    code = (
        "from repro import obs\n"
        "assert obs.enabled()\n"
        "obs.add('launches', 2)\n"
        "with obs.span('plan', key='k'):\n"
        "    pass\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=ROOT)
    doc = validate_trace(json.load(open(trace)))
    assert doc["otherData"]["counters"]["launches"] == 2
    assert any(e["ph"] == "X" and e["name"] == "plan"
               for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# The disabled path: one predicate check, no allocation.
# ---------------------------------------------------------------------------

def test_disabled_path_allocates_nothing():
    assert not obs.enabled()
    assert obs.span("a") is obs.span("b") is obs.NULL_SPAN
    assert obs.NULL_SPAN.set(x=1) is obs.NULL_SPAN

    def hot():
        # The exact shape of every instrumented hot path: a predicate
        # check, a bare span, a counter bump.
        if obs.enabled():
            raise AssertionError("recording must be off")
        with obs.span("kernel_launch"):
            pass
        obs.add("launches")

    import gc

    for _ in range(64):  # warm caches/freelists
        hot()
    gc.collect()
    before = sys.getallocatedblocks()
    for _ in range(512):
        hot()
    gc.collect()
    after = sys.getallocatedblocks()
    assert after - before <= 2, (
        f"no-op obs path leaked {after - before} blocks over 512 calls"
    )


def test_plan_cache_warm_hit_stays_fast_with_obs_disabled():
    import time

    planner = Planner(cache=PlanCache(persistent=False))
    kw = dict(shape=(32, 64, 128), offsets=star_stencil(3, 1),
              vmem_budget=256 * 1024)
    plan = planner.plan(**kw)
    warm = []
    for _ in range(3):
        t0 = time.perf_counter()
        again = planner.plan(**kw)
        warm.append((time.perf_counter() - t0) * 1e3)
        assert again == plan
    assert min(warm) < 1.0, f"warm hit took {min(warm):.3f} ms"


# ---------------------------------------------------------------------------
# Layer instrumentation.
# ---------------------------------------------------------------------------

def test_plan_span_and_cache_counters():
    planner = Planner(cache=PlanCache(persistent=False))
    kw = dict(shape=(32, 64, 128), offsets=star_stencil(3, 1),
              vmem_budget=256 * 1024)
    with obs.recording() as rec:
        planner.plan(**kw)   # miss -> compile
        planner.plan(**kw)   # warm hit
    assert rec.counters["plan_cache_miss"] == 1
    assert rec.counters["plan_cache_hit"] == 1
    plans = [s for s in rec.spans if s.name == "plan"]
    assert len(plans) == 2
    assert plans[0].args["key"] == plans[1].args["key"]
    assert plans[0].args["tuned"] is False
    lookups = [s for s in rec.spans if s.name == "plan_cache_lookup"]
    assert [s.args["outcome"] for s in lookups] == ["miss", "hit"]


def test_measure_emits_span_and_counter():
    from repro.runtime.timing import measure

    with obs.recording() as rec:
        res = measure(lambda: 1 + 1, reps=3, warmup=1)
    assert res.reps == 3
    spans = [s for s in rec.spans if s.name == "measure"]
    assert len(spans) == 1
    assert spans[0].args["measured_ns"] == rec.counters["measured_ns"]
    assert rec.counters["measured_ns"] > 0


def test_interpret_fallback_counted_per_kernel(monkeypatch):
    """Every kernel that runs interpreted on a chip (an explicit
    ``interpret=True`` on a TPU backend) records the fallback, per
    kernel; the default resolution there compiles and records nothing."""
    import jax

    from repro.kernels import _backend

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with obs.recording() as rec:
        assert _backend.resolve_interpret(None, kernel="stencil") is False
        assert _backend.resolve_interpret(True, kernel="stencil") is True
        assert _backend.resolve_interpret(True, kernel="conv1d") is True
    assert rec.counters["interpret_fallback"] == 2
    kernels = [e["args"]["kernel"] for e in rec.events
               if e["name"] == "interpret_fallback"]
    assert kernels == ["stencil", "conv1d"]


def test_cache_stats_callable_and_degrade(tmp_path):
    # stats stays dict-compatible AND callable (satellite 2).
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("file where the cache dir should be")
    cache = PlanCache(cache_dir=str(blocker))
    planner = Planner(cache=cache)
    assert cache.stats["misses"] == 0          # dict spelling
    assert cache.stats()["degraded"] is False  # callable spelling
    with obs.recording() as rec:
        planner.plan(shape=(16, 32, 128), offsets=star_stencil(3, 1),
                     vmem_budget=128 * 1024)
    assert cache.degraded is True
    snap = cache.stats()
    assert snap["degraded"] is True and snap["disk_errors"] == 1
    assert rec.counters["plan_cache_degrade"] == 1
    assert any(e["name"] == "plan_cache_degrade" for e in rec.events)


def test_tunedb_stats_callable_and_degrade(tmp_path):
    from repro.plan.tune import AutoTuner

    blocker = tmp_path / "not-a-dir"
    blocker.write_text("file where the DB dir should be")
    db = TunedPlanDB(db_dir=str(blocker))
    assert db.stats["misses"] == 0
    assert db.stats()["degraded"] is False
    tuner = AutoTuner(db=db, planner=Planner(cache=PlanCache(
        persistent=False)), k=2, reps=1, warmup=0)
    with obs.recording() as rec:
        tuner.plan(shape=(16, 16, 128), offsets=star_stencil(3, 1),
                   vmem_budget=128 * 1024, aligned=True)
    assert db.degraded is True
    assert db.stats()["degraded"] is True
    assert rec.counters["tunedb_degrade"] == 1
    assert rec.counters["tunedb_miss"] == 1
    races = [s for s in rec.spans if s.name == "tune_race"]
    assert len(races) == 1
    assert races[0].args["source"] == "measured"
    assert isinstance(races[0].args["never_slower"], bool)
    ranks = [s.args["rank"] for s in rec.spans
             if s.name == "tune_candidate"]
    assert ranks == list(range(len(ranks))) and len(ranks) >= 1


# ---------------------------------------------------------------------------
# End-to-end: traced fused + sharded + tuned run reconciles in the report.
# ---------------------------------------------------------------------------

def test_traced_tuned_sharded_run_reconciles(tmp_path):
    import jax.numpy as jnp

    from repro.kernels.ref import stencil_ref
    from repro.kernels.stencil import stencil_iterate
    from repro.obs.report import main as report_main
    from repro.plan.tune import AutoTuner

    trace = str(tmp_path / "run.json")
    offs = star_stencil(3, 1)
    w = [1.0 / len(offs)] * len(offs)
    u = jnp.asarray(
        np.random.default_rng(0).standard_normal((16, 32, 128)),
        jnp.float32,
    )
    tuner = AutoTuner(
        db=TunedPlanDB(persistent=False),
        planner=Planner(cache=PlanCache(persistent=False)),
        k=2, reps=2, warmup=1,
    )
    out = stencil_iterate(u, offs, w, 3, num_shards=4, tune=tuner,
                          trace=trace)
    ref = np.asarray(u)
    for _ in range(3):
        ref = np.asarray(stencil_ref(jnp.asarray(ref), offs, w))
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)
    assert not obs.enabled(), "trace= must restore the disabled state"

    doc = validate_trace(json.load(open(trace)))
    summary = summarize(doc)
    assert reconcile(summary) == [], "trace does not reconcile"
    assert summary["counters"]["launches"] == len(summary["launches"]) > 0
    assert summary["n_exchange_spans"] > 0  # 4-shard halo exchanges
    # k=2 analytic candidates plus the §15 window-flip and advisory
    # bf16/int8 dtype variants the race appends beyond top-k.
    assert summary["races"] and summary["races"][0]["candidates"] >= 2
    launch = summary["launches"][-1]
    assert launch["num_shards"] == 4
    assert launch["modeled_bytes"] > 0
    assert launch["fused_depth"] >= 1
    # the CLI agrees
    assert report_main([trace, "--check"]) == 0


# ---------------------------------------------------------------------------
# Satellites: explain --json, bench_history.
# ---------------------------------------------------------------------------

def test_explain_json_round_trips(monkeypatch, tmp_path, capsys):
    from repro.plan.explain import main as explain_main
    from repro.plan.schema import StencilPlan

    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path / "plans"))
    rc = explain_main(["64x64x128", "--stencil", "star:1", "--geom", "none",
                       "--time-steps", "3", "--json"])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    plan = StencilPlan.from_dict(doc["plan"])
    # round trip (JSON turns tuples into lists; normalize before comparing)
    assert json.loads(json.dumps(plan.to_dict())) == doc["plan"]
    rep = doc["report"]
    assert rep["plan_key"] == plan.request.cache_key()
    assert tuple(rep["tile"]) == plan.tile
    assert rep["fused_depth"] == plan.fused_depth
    assert rep["modeled_bytes"] == (
        plan.per_shard_traffic_bytes * plan.num_shards
        + plan.halo_exchange_bytes
    )
    scores = doc["depth_scores"]
    assert [s["depth"] for s in scores] == [d for d, _, _ in
                                            plan.depth_scores]
    assert sum(s["chosen"] for s in scores) == 1


def test_bench_history_verifies_chain(capsys):
    spec = importlib.util.spec_from_file_location(
        "bench_history", ROOT / "scripts" / "bench_history.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main(["--root", str(ROOT)]) == 0
    out = capsys.readouterr().out
    assert "all gates hold" in out
    # --json mode carries the same verdict machine-readably
    assert mod.main(["--root", str(ROOT), "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["ok"] is True
    assert max(len(r["chain"]) for r in doc["rows"]) >= 2
    # a broken gate is detected
    assert mod.gates_ok({"a_ok": True, "b_ok": False, "x": 1.0}) is False
    _, problems = mod.verify_chain(
        {"pr": 3, "acceptance": {"ok": True},
         "pr2_thing": {"pr": 1, "acceptance": {"ok": True}}}
    )
    assert any("chain gap" in p for p in problems)
