"""Measured-cost autotune loop (§11): candidate enumeration invariants,
TunedPlanDB robustness (corrupt / stale-schema / fingerprint-mismatch
entries), sharded tuning, the planner's measured-winner preference, the
``stencil_iterate(tune=...)`` plumb-through, and the shared timing
harness."""

import json
import os
import shutil
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.cache_fitting import star_stencil
from repro.entry import run, stencil_iterate
from repro.kernels.ref import stencil_ref
from repro.plan import (
    TUNEDB_SCHEMA, AutoTuner, PlanCache, PlanRequest, Planner, StencilPlan,
    TunedPlanDB, TuneRecord, resolve_tuner,
)
from repro.plan.tune import _spearman, backend_fingerprint
from repro.runtime.timing import _median_iqr, device_fingerprint, measure

KW = dict(
    shape=(16, 16, 128), offsets=star_stencil(3, 1),
    vmem_budget=256 * 1024, aligned=True,
)


def _request(**over):
    kw = dict(KW)
    kw.update(over)
    return PlanRequest.make(**kw)


def _tuner(db=None, **kw):
    kw.setdefault("k", 2)
    kw.setdefault("reps", 2)
    kw.setdefault("warmup", 1)
    return AutoTuner(
        db=db if db is not None else TunedPlanDB(persistent=False),
        planner=Planner(cache=PlanCache(persistent=False)),
        **kw,
    )


@pytest.fixture(scope="module")
def tuned():
    """One real tune pass shared by every test that only needs a record."""
    db = TunedPlanDB(persistent=False)
    tuner = _tuner(db)
    rec = tuner.tune(_request(), runner=run)
    return db, tuner, rec


# -- Planner.candidates ------------------------------------------------------


def test_candidates_analytic_first():
    planner = Planner(cache=PlanCache(persistent=False))
    req = _request()
    cands = planner.candidates(req, k=4)
    assert 1 <= len(cands) <= 4
    assert all(isinstance(c, StencilPlan) for c in cands)
    # Candidate 0 IS the analytic plan — same object the argmin freezes.
    assert cands[0] == planner.plan(req)
    assert all(c.request == req for c in cands)


def test_candidates_distinct_launch_signatures():
    planner = Planner(cache=PlanCache(persistent=False))
    cands = planner.candidates(_request(), k=8)
    sigs = [
        (c.tile, c.sweep_axis, c.fused_depth, c.shard_axis) for c in cands
    ]
    assert len(sigs) == len(set(sigs)), "duplicate launch signature raced"


def test_candidates_k1_is_the_plan():
    planner = Planner(cache=PlanCache(persistent=False))
    req = _request()
    assert planner.candidates(req, k=1) == [planner.plan(req)]


# -- the tune pass -----------------------------------------------------------


def test_tune_never_slower_and_record_roundtrip(tuned):
    _, _, rec = tuned
    assert rec.never_slower
    assert rec.analytic == 0
    assert 0 <= rec.winner < len(rec.candidates)
    assert rec.speedup_vs_analytic >= 1.0
    assert rec.key == _request().cache_key()
    assert rec.fingerprint == backend_fingerprint()
    assert all(c.median_s > 0 and c.reps == 2 for c in rec.candidates)
    # The analytic candidate's ratio is 1 by definition of the baseline.
    assert rec.candidates[0].model_measured_ratio == pytest.approx(1.0)
    assert TuneRecord.from_dict(rec.to_dict()) == rec
    assert TuneRecord.from_dict(
        json.loads(json.dumps(rec.to_dict()))
    ) == rec


def test_planner_prefers_measured_winner_without_remeasuring(tuned):
    db, _, rec = tuned
    planner = Planner(cache=PlanCache(persistent=False), tuned_db=db)
    misses_before = db.stats["misses"]
    warm = []
    for _ in range(5):
        t0 = time.perf_counter()
        served = planner.plan(_request())
        warm.append(time.perf_counter() - t0)
        assert planner.last_plan_tuned
        assert served == rec.winner_plan
    assert db.stats["misses"] == misses_before, "warm hit re-measured"
    # The <1ms contract is gated tightly in the tune smoke;
    # here a loose bound guards against a re-tune hiding in the hit path.
    assert min(warm) < 0.05


def test_planner_miss_falls_back_to_analytic_unchanged():
    db = TunedPlanDB(persistent=False)        # empty: every get misses
    with_db = Planner(cache=PlanCache(persistent=False), tuned_db=db)
    plain = Planner(cache=PlanCache(persistent=False))
    req = _request()
    assert with_db.plan(req) == plain.plan(req)
    assert not with_db.last_plan_tuned
    assert db.stats["misses"] == 1


def test_autotuner_plan_warm_vs_fresh(tuned):
    db, tuner, rec = tuned
    assert tuner.plan(_request(), runner=run) == rec.winner_plan
    assert tuner.last_plan_tuned        # served from the DB, not re-raced
    force = _tuner(db, force=True)
    assert force.plan(_request(), runner=run) is not None
    assert not force.last_plan_tuned    # force=True re-measures


# -- TunedPlanDB robustness --------------------------------------------------


def _store(tmp_path, rec):
    db = TunedPlanDB(db_dir=str(tmp_path))
    db.put(rec)
    path = db._path(rec.key, rec.fingerprint)
    assert os.path.exists(path)
    return path


def test_disk_roundtrip(tmp_path, tuned):
    _, _, rec = tuned
    _store(tmp_path, rec)
    cold = TunedPlanDB(db_dir=str(tmp_path))
    assert cold.get(rec.key, rec.fingerprint) == rec
    assert cold.stats["disk_hits"] == 1


def test_corrupt_entry_dropped_and_retuned(tmp_path, tuned):
    _, _, rec = tuned
    path = _store(tmp_path, rec)
    with open(path, "w") as f:
        f.write("{not json")
    cold = TunedPlanDB(db_dir=str(tmp_path))
    assert cold.get(rec.key, rec.fingerprint) is None
    assert cold.stats["corrupt"] == 1
    assert not os.path.exists(path)          # poisoned entry dropped
    # ... and the autotuner heals it with a fresh measurement.
    tuner = _tuner(cold)
    assert tuner.plan(_request(), runner=run) is not None
    assert not tuner.last_plan_tuned         # tuned fresh, not served stale
    assert cold.get(rec.key, rec.fingerprint) is not None


def test_schema_bump_invalidates(tmp_path, tuned):
    _, _, rec = tuned
    path = _store(tmp_path, rec)
    d = json.load(open(path))
    d["schema"] = TUNEDB_SCHEMA + 1
    json.dump(d, open(path, "w"))
    cold = TunedPlanDB(db_dir=str(tmp_path))
    assert cold.get(rec.key, rec.fingerprint) is None
    assert cold.stats["stale_schema"] == 1
    assert cold.stats["corrupt"] == 1
    assert not os.path.exists(path)          # stale layout never re-read


def test_planner_version_bump_invalidates(tmp_path, tuned):
    _, _, rec = tuned
    path = _store(tmp_path, rec)
    d = json.load(open(path))
    d["planner_version"] += 1
    json.dump(d, open(path, "w"))
    cold = TunedPlanDB(db_dir=str(tmp_path))
    assert cold.get(rec.key, rec.fingerprint) is None
    assert cold.stats["stale_schema"] == 1
    assert not os.path.exists(path)


def test_fingerprint_mismatch_never_served(tmp_path, tuned):
    """A record taken on another backend is a clean miss: never served,
    never deleted (it still answers for the backend that wrote it)."""
    _, _, rec = tuned
    path = _store(tmp_path, rec)
    other = rec.fingerprint + "|other-backend"
    cold = TunedPlanDB(db_dir=str(tmp_path))
    # Same key, foreign fingerprint tag: plain file-not-found miss.
    assert cold.get(rec.key, other) is None
    assert cold.stats["corrupt"] == 0
    # A file sitting under the requested tag but recording a different
    # fingerprint inside (copied caches, shared NFS dirs) is the
    # dangerous case — content wins over filename.
    shutil.copy(path, cold._path(rec.key, other))
    assert cold.get(rec.key, other) is None
    assert cold.stats["fingerprint_misses"] == 1
    assert cold.stats["corrupt"] == 0
    assert os.path.exists(path)              # original entry preserved
    # The rightful owner still gets served.
    assert cold.get(rec.key, rec.fingerprint) == rec


def test_unwritable_dir_degrades_once(tuned, tmp_path, caplog):
    _, _, rec = tuned
    blocked = tmp_path / "a-file-not-a-dir"
    blocked.write_text("")
    db = TunedPlanDB(db_dir=str(blocked / "sub"))
    with caplog.at_level("WARNING", logger="repro.plan.tunedb"):
        db.put(rec)
        db.put(rec)
    assert db.dir is None                    # degraded to memory-only
    assert db.stats["disk_errors"] == 1      # ... after exactly one error
    assert len(caplog.records) == 1          # ... and exactly one warning
    assert db.get(rec.key, rec.fingerprint) == rec   # memory still serves


# -- the §15 variant race + TUNEDB_SCHEMA v2 ---------------------------------


CHAIN_KW = dict(
    shape=(32, 256), offsets=star_stencil(2, 1), time_steps=3,
    vmem_budget=256 * 1024, aligned=True,
)


def _chain_request(**over):
    kw = dict(CHAIN_KW)
    kw.update(over)
    return PlanRequest.make(**kw)


@pytest.fixture(scope="module")
def tuned_chain():
    """One fused-chain tune pass: races geometry + window flip + the
    advisory bf16/int8 storage variants (DESIGN.md §15)."""
    db = TunedPlanDB(persistent=False)
    tuner = _tuner(db)
    rec = tuner.tune(_chain_request(), runner=run)
    return db, tuner, rec


def test_chain_race_covers_windows_and_dtypes(tuned_chain):
    _, _, rec = tuned_chain
    assert {c.window_kind for c in rec.candidates} >= {"ring", "trapezoid"}
    named = {
        dt for c in rec.candidates if c.stage_dtypes
        for dt in c.stage_dtypes if dt is not None
    }
    assert named == {"bfloat16", "int8"}
    # Every dtype-variant row is advisory; every geometry/window row is
    # winner-eligible; the analytic f32 plan is always candidate 0.
    assert all(c.advisory == bool(c.stage_dtypes) for c in rec.candidates)
    assert rec.analytic == 0
    assert rec.candidates[0].stage_dtypes is None
    assert not rec.candidates[rec.winner].advisory
    assert rec.never_slower
    # The served winner answers the ORIGINAL request key, even when the
    # window flip won (the flip is bit-wise neutral, the key identical).
    assert rec.winner_plan.request.cache_key() == rec.key


def test_schema_v2_round_trip_with_variant_fields(tuned_chain):
    _, _, rec = tuned_chain
    assert rec.schema == TUNEDB_SCHEMA == 2
    assert TuneRecord.from_dict(rec.to_dict()) == rec
    assert TuneRecord.from_dict(
        json.loads(json.dumps(rec.to_dict()))
    ) == rec
    # The v2 columns survive the JSON trip typed, not stringified.
    back = TuneRecord.from_dict(json.loads(json.dumps(rec.to_dict())))
    int8_rows = [
        c for c in back.candidates
        if c.stage_dtypes and "int8" in c.stage_dtypes
    ]
    assert int8_rows and int8_rows[0].advisory
    assert int8_rows[0].stage_dtypes == ("int8", "int8", None)


def test_v1_stale_entry_dropped_and_retuned(tmp_path, tuned_chain):
    """A pre-§15 record (schema 1, no variant columns) must never be
    served into the v2 race — dropped, counted, re-tuned."""
    _, _, rec = tuned_chain
    path = _store(tmp_path, rec)
    d = json.load(open(path))
    d["schema"] = 1
    for c in d["candidates"]:   # v1 rows predate the variant columns
        c.pop("window_kind"), c.pop("stage_dtypes"), c.pop("advisory")
    json.dump(d, open(path, "w"))
    cold = TunedPlanDB(db_dir=str(tmp_path))
    assert cold.get(rec.key, rec.fingerprint) is None
    assert cold.stats["stale_schema"] == 1
    assert not os.path.exists(path)
    tuner = _tuner(cold)
    assert tuner.plan(_chain_request(), runner=run) is not None
    assert not tuner.last_plan_tuned     # healed by a fresh measurement
    healed = cold.get(rec.key, rec.fingerprint)
    assert healed is not None and healed.schema == TUNEDB_SCHEMA


def test_advisory_winner_record_rejected(tmp_path, tuned_chain):
    """A record claiming an advisory (numerics-changing) row won is
    corrupt by construction — never served."""
    _, _, rec = tuned_chain
    path = _store(tmp_path, rec)
    d = json.load(open(path))
    advisory = [i for i, c in enumerate(d["candidates"]) if c["advisory"]]
    assert advisory, "chain tune raced no advisory rows"
    d["winner"] = advisory[0]
    json.dump(d, open(path, "w"))
    cold = TunedPlanDB(db_dir=str(tmp_path))
    assert cold.get(rec.key, rec.fingerprint) is None
    assert cold.stats["corrupt"] == 1
    assert not os.path.exists(path)


def test_variant_record_fingerprint_mismatch_is_clean_miss(tmp_path,
                                                           tuned_chain):
    _, _, rec = tuned_chain
    _store(tmp_path, rec)
    cold = TunedPlanDB(db_dir=str(tmp_path))
    assert cold.get(rec.key, rec.fingerprint + "|other") is None
    assert cold.stats["corrupt"] == 0
    assert cold.get(rec.key, rec.fingerprint) == rec


def test_pinned_window_kind_skips_the_flip():
    """A request that already pins ring/trapezoid races no flip — the
    user's choice is part of the planning problem, not a knob."""
    db = TunedPlanDB(persistent=False)
    rec = _tuner(db).tune(_chain_request(window_kind="ring"), runner=run)
    assert all(c.window_kind == "ring" for c in rec.candidates)


def test_dtyped_request_races_no_dtype_variants():
    """An explicitly mixed-precision request IS the dtype assignment —
    nothing to advise on; its rows race winner-eligible as usual."""
    db = TunedPlanDB(persistent=False)
    rec = _tuner(db).tune(_chain_request(
        dtypes=["bfloat16", "bfloat16", "float32"],
    ), runner=run)
    assert all(not c.advisory for c in rec.candidates)
    # The final "float32" restates the input dtype: None-normalized.
    assert all(
        c.stage_dtypes == ("bfloat16", "bfloat16", None)
        for c in rec.candidates
    )
    assert rec.never_slower


# -- sharded tuning ----------------------------------------------------------


def test_sharded_request_tunes_sharded_launch():
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    tuner = _tuner()
    rec = tuner.tune(_request(num_shards=2), runner=run)
    assert rec.never_slower
    assert rec.winner_plan.num_shards == 2
    assert all(c.shard_axis is not None for c in rec.candidates)
    # Modeled bytes price all shards + the exchange, not one shard's slab.
    w = rec.winner_plan
    assert rec.candidates[rec.winner].modeled_bytes == (
        w.per_shard_traffic_bytes * w.num_shards + w.halo_exchange_bytes
    )


# -- entry plumb-through -----------------------------------------------------


def test_entry_tune_parity_and_warm_reuse():
    u = jax.random.normal(jax.random.PRNGKey(0), (16, 16, 128), jnp.float32)
    offs = star_stencil(3, 1)
    w = [1.0 / len(offs)] * len(offs)
    tuner = _tuner()
    out = stencil_iterate(u, offs, w, 1, vmem_budget=256 * 1024, tune=tuner)
    assert not tuner.last_plan_tuned         # first call measured fresh
    ref = stencil_ref(u, offs, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    again = stencil_iterate(u, offs, w, 1, vmem_budget=256 * 1024, tune=tuner)
    assert tuner.last_plan_tuned             # second call: warm DB hit
    np.testing.assert_array_equal(np.asarray(out), np.asarray(again))


def test_tune_mutually_exclusive_with_pinned_decisions():
    u = jnp.zeros((16, 16, 128), jnp.float32)
    offs = star_stencil(3, 1)
    w = [1.0 / len(offs)] * len(offs)
    tuner = _tuner()
    with pytest.raises(ValueError, match="tune="):
        stencil_iterate(u, offs, w, 1, tile=(8, 16, 128), tune=tuner)
    plan = Planner(cache=PlanCache(persistent=False)).plan(_request())
    with pytest.raises(ValueError, match="tune="):
        stencil_iterate(u, offs, w, 1, plan=plan, tune=tuner)


def test_resolve_tuner():
    assert resolve_tuner(None) is None
    assert resolve_tuner(False) is None
    t = resolve_tuner(True)
    assert isinstance(t, AutoTuner)
    assert resolve_tuner(True) is t          # process-wide singleton
    mine = _tuner()
    assert resolve_tuner(mine) is mine


# -- the shared timing harness ----------------------------------------------


def test_median_iqr_math():
    med, iqr = _median_iqr([3.0, 1.0, 2.0])
    assert med == 2.0
    assert iqr == pytest.approx(1.0)         # q75=2.5, q25=1.5 (interp)
    med, iqr = _median_iqr([4.0, 1.0, 2.0, 3.0])
    assert med == 2.5
    assert iqr == pytest.approx(1.5)
    med, iqr = _median_iqr([7.0])
    assert med == 7.0 and iqr == 0.0


def test_measure_call_accounting_and_validation():
    calls = []
    res = measure(lambda: calls.append(0), reps=4, warmup=2)
    assert len(calls) == 6                   # warmup excluded from reps
    assert res.reps == 4 and res.warmup == 2
    assert len(res.times_s) == 4
    assert res.median_s >= 0.0 and res.iqr_s >= 0.0
    with pytest.raises(ValueError):
        measure(lambda: None, reps=0)
    with pytest.raises(ValueError):
        measure(lambda: None, warmup=-1)


def test_device_fingerprint_shape():
    fp = device_fingerprint()
    backend, kind, count, ver = fp.split(":")
    assert backend == jax.default_backend()
    assert count == f"x{len(jax.devices())}"
    assert ver == f"jax-{jax.__version__}"
    # The tuner's composite adds the kernel mode on top.
    assert backend_fingerprint().startswith(fp + "|interpret=")


def test_spearman():
    assert _spearman([1, 2, 3], [10, 20, 30]) == pytest.approx(1.0)
    assert _spearman([1, 2, 3], [30, 20, 10]) == pytest.approx(-1.0)
    assert _spearman([1], [1]) == 0.0
    assert _spearman([5, 5, 5], [1, 2, 3]) == 0.0
    # Rank-based: monotone but non-linear is still a perfect +1.
    assert _spearman([1, 2, 3, 4], [1, 8, 27, 1000]) == pytest.approx(1.0)
