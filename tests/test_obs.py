"""Telemetry subsystem (DESIGN.md §12): recorder, trace export, report
reconciliation, instrumentation of plan/cache/tune/launch layers, the
near-zero disabled path, and the obs-adjacent satellites (cache stats(),
interpret-fallback counting, explain --json), and that recording changes
no result."""

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
    assert not obs.enabled() and not obs.tracing()
    assert obs.span("a") is obs.span("b") is obs.NULL_SPAN
    assert obs.call_span("stencil_call") is obs.NULL_SPAN
    assert obs.NULL_SPAN.set(x=1) is obs.NULL_SPAN

    def hot():
        # The exact shape of every instrumented hot path: a predicate
        # check, a bare span, a counter bump — and the call's own sites:
        # the numbered root, the lowering, the plan with its outcome
        # guard, the launch.
        if obs.enabled() or obs.tracing():
            raise AssertionError("recording must be off")
        with obs.call_span("stencil_call"):
            with obs.span("ir_lower"):
                pass
            with obs.span("plan") as sp:
                if sp is not obs.NULL_SPAN:
                    raise AssertionError("the plan span must be null")
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


def test_profiler_session_is_detected(tmp_path):
    """``obs.tracing()`` rests on ``jax.profiler.TraceAnnotation.
    is_enabled``: false with no session, true while one collects.  With
    no recorder, a span is then a bare annotation and nothing is kept."""
    import jax

    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert not obs.tracing()
    with jax.profiler.trace(str(tmp_path)):
        assert jax.profiler.TraceAnnotation.is_enabled()
        assert obs.tracing() and not obs.enabled()
        sp = obs.span("plan")
        assert sp is not obs.NULL_SPAN
        with sp:
            sp.set(cache="hit")
        with obs.call_span("stencil_call") as root:
            assert root is not obs.NULL_SPAN
        assert obs.active() is None
    assert not obs.tracing()
    assert obs.span("plan") is obs.NULL_SPAN


def _stencil_calls(kind):
    """Two spellings of one warm call of a public entry: ``stencil_iterate``
    on one device, or a periodic 27-point ``repro.entry.run`` over a
    4-device mesh.  The second spelling (offsets as int32, or an equal new
    program) is a second key of the entry's memo that resolves to the
    same plan and launches."""
    import jax
    import jax.numpy as jnp

    from repro import entry, ir
    from repro.entry import run, stencil_iterate

    entry._memo.clear()  # the second key misses whatever ran before
    if kind == "stencil_iterate":
        offs = star_stencil(3, 1)
        w = [1.0 / len(offs)] * len(offs)
        u = jnp.ones((16, 16, 128), jnp.float32)
        offs32 = offs.astype(np.int32)
        return (lambda: stencil_iterate(u, offs, w, 1),
                lambda: stencil_iterate(u, offs32, w, 1))
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_column_mesh

    offs = np.array([(i, j, k) for i in (-1, 0, 1) for j in (-1, 0, 1)
                     for k in (-1, 0, 1)])
    w = [1.0 / 27] * 27
    prog = ir.stencil_program(offs, w, 1, d=3, boundary="periodic")
    prog2 = ir.stencil_program(offs, w, 1, d=3, boundary="periodic")
    mesh = make_column_mesh(4)
    u = jax.device_put(jnp.ones((32, 16, 128), jnp.float32),
                       NamedSharding(mesh, P("columns")))
    return (lambda: run(prog, u, num_shards=4, shard_axis=0),
            lambda: run(prog2, u, num_shards=4, shard_axis=0))


CALL_SPANS = ("stencil_call", "ir_lower", "plan", "kernel_launch")


@pytest.mark.parametrize("kind", ["stencil_iterate", "run_program_4dev"])
def test_profiler_trace_holds_call_spans(tmp_path, kind):
    """Under ``jax.profiler`` with no recorder, each call leaves one
    numbered ``stencil_call`` on the caller's thread carrying its memo
    outcome: a miss (a second key of a planned call) holds ``ir_lower``,
    ``plan`` (with the plan cache's hit) and its launch spans; the hits
    after it hold the launch spans alone."""
    import glob

    import jax
    from jax.profiler import ProfileData

    call, second_key = _stencil_calls(kind)
    call().block_until_ready()  # compile, plan and memoize outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=options):
        second_key().block_until_ready()
        for _ in range(2):
            call().block_until_ready()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    events = [
        (line.name, e.name, e.start_ns, e.start_ns + e.duration_ns,
         dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        for line in plane.lines for e in line.events
        if e.name in CALL_SPANS
    ]
    assert {line for line, *_ in events} == {events[0][0]}  # one thread
    roots = sorted((s, e, st) for _, name, s, e, st in events
                   if name == "stencil_call")
    assert len(roots) == 3
    numbers = [st["call"] for _, _, st in roots]
    assert numbers == list(range(numbers[0], numbers[0] + 3))
    assert [st["memo"] for _, _, st in roots] == ["miss", "hit", "hit"]
    n_launch = None
    for i, (s, e, _) in enumerate(roots):
        inside = sorted((cs, name, st) for _, name, cs, ce, st in events
                        if name != "stencil_call" and s <= cs and ce <= e)
        names = [name for _, name, _ in inside]
        if i == 0:
            assert names[:2] == ["ir_lower", "plan"]
            assert inside[1][2]["cache"] == "hit"
            names = names[2:]
            n_launch = len(names)
        assert names and set(names) == {"kernel_launch"}
        assert len(names) == n_launch
    assert len(events) == 3 * (1 + n_launch) + 2


def test_recorder_gets_call_spans():
    """With a recorder the same spans land in it, with their arguments:
    the call number and memo outcome, the plan's key and cache outcome on
    the miss, the launch's pricing on both."""
    call, second_key = _stencil_calls("stencil_iterate")
    call().block_until_ready()
    with obs.recording() as rec:
        second_key().block_until_ready()
        call().block_until_ready()
    by_name = {}
    for sp in rec.spans:
        by_name.setdefault(sp.name, []).append(sp)
    roots = by_name["stencil_call"]
    assert [r.args["call"] for r in roots][1] == roots[0].args["call"] + 1
    assert [r.args["memo"] for r in roots] == ["miss", "hit"]
    assert rec.counters["entry_memo_miss"] == 1
    assert rec.counters["entry_memo_hit"] == 1
    for name, owners in (("ir_lower", roots[:1]), ("plan", roots[:1]),
                         ("kernel_launch", roots)):
        assert len(by_name[name]) == len(owners)
        for child, root in zip(by_name[name], owners):
            assert child.tid == root.tid
            assert root.ts_us <= child.ts_us
            assert child.ts_us + child.dur_us <= root.ts_us + root.dur_us
    assert all(p.args["cache"] == "hit" and p.args["key"]
               for p in by_name["plan"])
    assert all("modeled_bytes" in k.args for k in by_name["kernel_launch"])
    miss_launch, hit_launch = by_name["kernel_launch"]
    assert miss_launch.args == hit_launch.args
    assert rec.counters["launches"] == 2


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
    assert [p.args["cache"] for p in plans] == ["miss", "hit"]
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
    from repro.entry import run
    from repro.plan.tune import AutoTuner

    blocker = tmp_path / "not-a-dir"
    blocker.write_text("file where the DB dir should be")
    db = TunedPlanDB(db_dir=str(blocker))
    assert db.stats["misses"] == 0
    assert db.stats()["degraded"] is False
    tuner = AutoTuner(db=db, planner=Planner(cache=PlanCache(
        persistent=False)), k=2, reps=1, warmup=0)
    with obs.recording() as rec:
        tuner.plan(runner=run, shape=(16, 16, 128),
                   offsets=star_stencil(3, 1),
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
    from repro.entry import stencil_iterate
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
    with obs.recording(trace):
        out = stencil_iterate(u, offs, w, 3, num_shards=4, tune=tuner)
    ref = np.asarray(u)
    for _ in range(3):
        ref = np.asarray(stencil_ref(jnp.asarray(ref), offs, w))
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=2e-5)
    assert not obs.enabled(), "recording must restore the disabled state"

    doc = validate_trace(json.load(open(trace)))
    summary = summarize(doc)
    assert reconcile(summary) == [], "trace does not reconcile"
    assert summary["counters"]["launches"] == len(summary["launches"]) > 0
    assert summary["counters"]["halo_exchange_bytes"] > 0  # 4 shards
    # k=2 analytic candidates plus the §15 window-flip and advisory
    # bf16/int8 dtype variants the race appends beyond top-k.
    assert summary["races"] and summary["races"][0]["candidates"] >= 2
    launch = summary["launches"][-1]
    assert launch["num_shards"] == 4
    assert launch["modeled_bytes"] > 0
    assert launch["fused_depth"] >= 1
    # the CLI agrees
    assert report_main([trace, "--check"]) == 0


def test_report_prints_no_device_time():
    """``kernel_launch`` times the host's enqueue, so the report's launch
    table shows no wall time or bandwidth derived from it."""
    from repro.obs.report import render

    with obs.recording() as rec:
        obs.add("launches")
        obs.add("modeled_bytes", 1 << 20)
        with obs.span("kernel_launch", plan_key="k", tile=[8, 128],
                      fused_depth=1, num_shards=1, modeled_bytes=1 << 20):
            pass
    summary = summarize(rec.to_trace_events())
    assert reconcile(summary) == []
    assert "modeled_bytes" in summary["launches"][0]
    assert not {"dur_us", "gb_per_s"} & set(summary["launches"][0])
    text = render(summary)
    assert "1.00 MiB" in text
    assert "wall" not in text and "GB/s" not in text
    assert "halo_exchange" not in text


# ---------------------------------------------------------------------------
# Satellites: explain --json; recording is pure.
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


def test_recording_changes_no_bits_and_warm_hit_stays_fast(tmp_path):
    """Recording a call changes none of its bits, and a warm plan-cache
    hit stays under 5 ms (median of 50) with a recorder installed."""
    import time

    import jax.numpy as jnp

    from repro.entry import stencil_iterate

    offs = star_stencil(3, 1)
    w = [1.0 / len(offs)] * len(offs)
    u = jnp.asarray(
        np.random.default_rng(0).standard_normal((16, 32, 128)),
        jnp.float32,
    )
    plain = stencil_iterate(u, offs, w, 3, num_shards=4)
    planner = Planner(cache=PlanCache(persistent=False))
    kw = dict(shape=(16, 32, 128), offsets=offs, vmem_budget=4 << 20,
              aligned=True, time_steps=3)
    planner.plan(**kw)
    with obs.recording(str(tmp_path / "t.json")):
        traced = stencil_iterate(u, offs, w, 3, num_shards=4)
        warm = []
        for _ in range(50):
            t0 = time.perf_counter()
            planner.plan(**kw)
            warm.append((time.perf_counter() - t0) * 1e3)
    assert np.array_equal(np.asarray(plain), np.asarray(traced))
    assert sorted(warm)[len(warm) // 2] < 5.0, sorted(warm)
