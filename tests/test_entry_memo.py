"""The call entry's memo of resolved launches (DESIGN.md §17): a
repeated call signature skips lowering and planning and runs the bound
launches; anything that can change the launches is a miss."""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import entry as call_entry
from repro import ir, obs
from repro.core.cache_fitting import star_stencil
from repro.entry import run, stencil_iterate
from repro.kernels.stencil import _stencil_call
from repro.plan import default_planner
from repro.plan.tunedb import TunedPlanDB

STAR = star_stencil(2, 1)
STAR_W = [0.4, 0.15, 0.15, 0.15, 0.15]


@pytest.fixture(autouse=True)
def _fresh_memo():
    """Every test starts from an empty memo and no recorder."""
    assert obs.active() is None
    call_entry._memo.clear()
    yield
    call_entry._memo.clear()


def _u(shape=(16, 128), dtype=jnp.float32, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def _entry_kinds():
    """One warm call per public spelling the entry serves: each returns
    ``(call, arrays)`` where ``call(*arrays)`` runs the entry."""
    offs3 = star_stencil(3, 1)
    w3 = [1.0 / len(offs3)] * len(offs3)
    star_prog = ir.stencil_program(STAR, STAR_W, 1, d=2)
    chain = ir.chain_program([(STAR, STAR_W)] * 2, 2,
                             boundary="dirichlet", value=0.5)
    rhs_offs = [star_stencil(2, 1), star_stencil(2, 2)]
    rhs_w = [[0.3] * len(rhs_offs[0]), [0.1] * len(rhs_offs[1])]
    rhs = ir.rhs_program(rhs_offs, rhs_w, d=2)

    def sharded():
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_column_mesh

        box = np.array([(i, j, k) for i in (-1, 0, 1) for j in (-1, 0, 1)
                        for k in (-1, 0, 1)])
        prog = ir.stencil_program(box, [1.0 / 27] * 27, 1, d=3,
                                  boundary="periodic")
        u = jax.device_put(_u((32, 16, 128)),
                           NamedSharding(make_column_mesh(4), P("columns")))
        return (lambda x: run(prog, x, num_shards=4,
                                         shard_axis=0)), (u,)

    return {
        "stencil_iterate": lambda: (
            (lambda x: stencil_iterate(x, offs3, w3, 1)),
            (_u((16, 16, 128)),),
        ),
        "run_program": lambda: (
            (lambda x: run(star_prog, x)), (_u(),)
        ),
        "multi_rhs": lambda: (
            (lambda a, b: run(rhs, [a, b])),
            (_u((24, 140)), _u((24, 140), seed=1)),
        ),
        "boundary_chain": lambda: (
            (lambda x: run(chain, x)), (_u((24, 128)),)
        ),
        "sharded_4dev": sharded,
    }


KINDS = list(_entry_kinds())


def _roots(rec):
    return [s for s in rec.spans if s.name == "stencil_call"]


def _children(rec, root):
    """The call's own spans inside ``root``, in start order."""
    inside = [s for s in rec.spans
              if s.name in ("ir_lower", "plan", "kernel_launch")
              and s.tid == root.tid and root.ts_us <= s.ts_us
              and s.ts_us + s.dur_us <= root.ts_us + root.dur_us]
    return [s.name for s in sorted(inside, key=lambda s: s.ts_us)]


@pytest.mark.parametrize("kind", KINDS)
def test_second_identical_call_hits(kind):
    """The first call misses (lowering, plan, launches); the second hits
    (launches only), bit-identical, with the launch's counters and span
    arguments served from the memo."""
    call, arrays = _entry_kinds()[kind]()
    with obs.recording() as rec:
        first = call(*arrays)
        second = call(*arrays)
    assert bool(jnp.all(first == second))
    miss, hit = _roots(rec)
    assert miss.args["memo"] == "miss" and hit.args["memo"] == "hit"
    assert rec.counters["entry_memo_miss"] == 1
    assert rec.counters["entry_memo_hit"] == 1
    names = _children(rec, miss)
    assert names[:2] == ["ir_lower", "plan"]
    assert names[2:] and set(names[2:]) == {"kernel_launch"}
    hit_names = _children(rec, hit)
    assert len(hit_names) == len(names) - 2
    assert hit_names and set(hit_names) == {"kernel_launch"}
    launches = [s for s in rec.spans if s.name == "kernel_launch"]
    assert len(launches) == 2 * len(hit_names)
    n = len(hit_names)
    for a, b in zip(launches[:n], launches[n:]):
        assert a.args == b.args
    assert rec.counters["launches"] == len(launches)


@pytest.mark.parametrize("kind", KINDS)
def test_hit_matches_a_fresh_resolve(kind):
    """A hit's output equals the output of resolving the same call
    anew, bit for bit."""
    call, arrays = _entry_kinds()[kind]()
    call(*arrays)
    hit = call(*arrays)
    call_entry._memo.clear()
    fresh = call(*arrays)
    assert hit.dtype == fresh.dtype
    assert bool(jnp.all(hit == fresh))


def _miss_after_warm(base, variant):
    """``base`` twice (miss, hit), then ``variant``: its memo outcome."""
    base()
    base()
    with obs.recording() as rec:
        variant()
    (root,) = _roots(rec)
    return root.args["memo"]


def _iterate(u=None, offs=STAR, w=STAR_W, steps=1, **kw):
    return stencil_iterate(_u() if u is None else u, offs, w, steps, **kw)


VARIANTS = {
    "shape": lambda: _iterate(_u((24, 128))),
    "dtype": lambda: _iterate(_u(dtype=jnp.bfloat16)),
    "weights": lambda: _iterate(w=[0.5] + STAR_W[1:]),
    "offsets": lambda: _iterate(offs=STAR[::-1].copy(),
                                w=STAR_W[::-1]),
    "time_steps": lambda: _iterate(steps=2),
    "tile": lambda: _iterate(tile=(8, 128)),
    "vmem_budget": lambda: _iterate(vmem_budget=1 << 20),
    "sweep_axis": lambda: _iterate(sweep_axis=1),
    "window_kind": lambda: _iterate(window_kind="trapezoid"),
}


@pytest.mark.parametrize("name", list(VARIANTS))
def test_a_changed_signature_misses(name):
    assert _miss_after_warm(_iterate, VARIANTS[name]) == "miss"


def test_interpret_is_keyed_as_resolved():
    """On the CPU ``interpret=None`` resolves to True: the same launch,
    served from the memo."""
    assert _miss_after_warm(
        _iterate, lambda: _iterate(interpret=True)
    ) == "hit"


def test_an_equal_new_program_misses_and_a_freed_one_is_not_kept():
    """A program is keyed by identity (its weak reference checked on a
    hit): an equal new object is a miss, and the memo keeps no program
    alive.  ``stencil_iterate`` builds its program once per spelling, so
    an equal spelling in new objects is a hit."""
    u = _u()
    prog = ir.stencil_program(STAR, STAR_W, 1, d=2)
    same = ir.stencil_program(STAR, STAR_W, 1, d=2)
    assert prog == same and prog is not same
    assert _miss_after_warm(lambda: run(prog, u),
                            lambda: run(same, u)) == "miss"
    ref = weakref.ref(prog)
    del prog
    gc.collect()
    assert ref() is None
    # A stencil_iterate spelling is keyed by value.
    assert _miss_after_warm(
        lambda: stencil_iterate(u, STAR, STAR_W, 1),
        lambda: stencil_iterate(u, STAR.copy(), list(STAR_W), 1),
    ) == "hit"


def test_a_reused_program_id_misses():
    """A key names its program by ``id``; should a freed program's id be
    reused by another program, the entry's weak reference tells them
    apart and the lookup misses (and drops the stale entry)."""
    prog = ir.stencil_program(STAR, STAR_W, 1, d=2)
    other = ir.stencil_program(STAR, [0.5] + STAR_W[1:], 1, d=2)
    run(prog, _u())
    ((key, entry),) = call_entry._memo.items()
    assert call_entry._memo_get(key, prog) is entry
    assert call_entry._memo_get(key, other) is None
    assert not call_entry._memo


def test_planner_generation_invalidates(monkeypatch):
    """Clearing the default planner's cache, or attaching or writing a
    tuned DB, moves the planner's generation: the next call misses."""
    planner = default_planner()

    def twice():
        with obs.recording() as rec:
            _iterate()
            _iterate()
        return [r.args["memo"] for r in _roots(rec)]

    _iterate()
    gen = planner.generation
    planner.cache.clear()
    assert planner.generation != gen
    assert twice() == ["miss", "hit"]
    db = TunedPlanDB(persistent=False)
    gen = planner.generation
    monkeypatch.setattr(planner, "tuned_db", db)
    assert planner.generation != gen
    assert twice() == ["miss", "hit"]
    gen = planner.generation
    db.clear()
    assert planner.generation != gen
    assert twice() == ["miss", "hit"]


def test_tune_never_hits():
    from repro.plan import PlanCache, Planner
    from repro.plan.tune import AutoTuner

    tuner = AutoTuner(db=TunedPlanDB(persistent=False),
                      planner=Planner(cache=PlanCache(persistent=False)),
                      k=1, reps=1, warmup=0)
    u = _u()
    with obs.recording() as rec:
        a = _iterate(u, tune=tuner)
        b = _iterate(u, tune=tuner)
    roots = _roots(rec)
    # The race measures through the entry too: keep the two outer calls.
    outer = [r for r in roots
             if not any(o is not r and o.ts_us <= r.ts_us
                        and r.ts_us + r.dur_us <= o.ts_us + o.dur_us
                        for o in roots)]
    assert [r.args["memo"] for r in outer] == ["miss", "miss"]
    assert bool(jnp.all(a == b))


@pytest.mark.parametrize("kind", KINDS)
def test_hit_does_not_retrace(kind):
    """A hit dispatches the bound launches the miss compiled: neither
    they nor ``_stencil_call`` trace again."""
    call, arrays = _entry_kinds()[kind]()
    call(*arrays).block_until_ready()
    (entry,) = call_entry._memo.values()
    bound = [launch.call for launch in entry.launches]
    sizes = [b._cache_size() for b in bound]
    plain = _stencil_call._cache_size()
    for _ in range(3):
        call(*arrays).block_until_ready()
    assert [b._cache_size() for b in bound] == sizes
    assert _stencil_call._cache_size() == plain
    (again,) = call_entry._memo.values()
    assert again is entry


def test_memo_keeps_no_caller_array_alive():
    u = _u()
    ref = weakref.ref(u)
    out = _iterate(u)
    out2 = _iterate(u)
    assert call_entry._memo
    del u, out, out2
    gc.collect()
    assert ref() is None


def test_lru_bound_holds(monkeypatch):
    """The memo keeps its newest ``_MEMO_SIZE`` signatures."""
    monkeypatch.setattr(call_entry, "_MEMO_SIZE", 2)
    u = _u()
    budgets = [1 << 20, 2 << 20, 3 << 20]
    for b in budgets:
        _iterate(u, tile=(8, 128), vmem_budget=b)
    assert len(call_entry._memo) == 2
    with obs.recording() as rec:
        _iterate(u, tile=(8, 128), vmem_budget=budgets[-1])
        _iterate(u, tile=(8, 128), vmem_budget=budgets[0])
    assert [r.args["memo"] for r in _roots(rec)] == ["hit", "miss"]
    assert len(call_entry._memo) == 2


def test_report_reconciles_memo_counters(tmp_path):
    """``repro.obs.report --check`` holds the memo counters to the
    ``stencil_call`` roots' ``memo`` outcomes."""
    import json

    from repro.obs.report import main as report_main, reconcile, summarize

    path = str(tmp_path / "t.json")
    with obs.recording(path):
        for _ in range(3):
            _iterate()
    assert report_main([path, "--check"]) == 0
    doc = json.load(open(path))
    summary = summarize(doc)
    assert summary["memo"] == {"hit": 2, "miss": 1}
    summary["counters"]["entry_memo_hit"] = 1
    assert any("entry_memo_hit" in p for p in reconcile(summary))
