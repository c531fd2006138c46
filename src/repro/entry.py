"""The call entry: from a public call to the bound launches of the sweep
kernel.

Every call reaches the sweep engine (:mod:`repro.kernels.stencil`) as one
:class:`repro.ir.Program` (DESIGN.md §13), through one of two spellings:

* ``run(program, arrays, ...)`` runs a program on its inputs; a §5
  multi-RHS sum ``q = Σ_p K_p u_p`` is ``run(ir.rhs_program(offsets_list,
  weights_list, d), [u_0, u_1, ...])``.
* ``stencil_iterate(u, offsets, weights, T, ...)`` (one operator T times)
  and ``stencil_iterate(u, stages=[(offsets, weights), ...], ...)`` (a
  distinct operator per stage) build their program once per spelling —
  the builder cache, so a repeated spelling yields the same ``Program``
  object — and run it.

A call lowers its program (``ir.lower``), plans it — explicit
``tile``/``sweep_axis`` win, then a ``plan``, then the default planner
(``tune=`` swaps that last step for the §11 measured-cost loop) — and
binds each launch: the one-device jitted launcher
(``kernels.stencil.bound_call``) or, with ``num_shards``/``mesh``, the
§10 column-sharded one (:mod:`repro.parallel.shard_columns`).  A
``plan`` is validated against the call and, with it, sets pipelining.

The launches a call resolves to depend only on its static signature, so
they are memoized (DESIGN.md §17): the memo keys on the program's
identity, the arrays' shapes and dtypes and the planning keywords, and a
repeated signature skips lowering and planning and goes straight to the
bound launches.  ``tune=`` calls bypass the memo.

Each call is one ``stencil_call`` span (numbered ``call=<n>``,
``memo=hit|miss``).  A miss holds ``ir_lower`` (lowering, summary), the
planner's ``plan`` and one ``kernel_launch`` per launch; a hit holds the
``kernel_launch`` spans alone.  The spans are recorded by a
:mod:`repro.obs` recorder (``with obs.recording(path):`` records a call
to a file) or, with none, sent to a collecting ``jax.profiler`` session.
"""

from __future__ import annotations

import functools
import threading
import weakref
from collections import OrderedDict
from typing import TYPE_CHECKING, NamedTuple, Sequence

import jax.numpy as jnp
import numpy as np

from repro import ir, obs
from repro.core.tiling import (
    chain_halo,
    dtype_itemsize,
    fused_stage_bytes,
    grid_slack,
    halo_from_offsets,
)
from repro.kernels._backend import resolve_interpret
from repro.kernels.stencil import bound_call, input_buffer
from repro.parallel.shard_columns import column_launch, pick_shard_axis
from repro.plan import default_planner, resolve_tuner, validate_plan_call

if TYPE_CHECKING:
    from repro.plan import StencilPlan

__all__ = ["run", "stencil_iterate"]


def stencil_iterate(
    u: jnp.ndarray,
    offsets: np.ndarray | None = None,
    weights: Sequence[float] | None = None,
    time_steps: int | None = None,
    tile: Sequence[int] | None = None,
    interpret: bool | None = None,
    vmem_budget: int | None = None,
    sweep_axis: int | None = None,
    plan: "StencilPlan | None" = None,
    stages: Sequence[tuple] | None = None,
    num_shards: int | None = None,
    shard_axis: int | None = None,
    mesh=None,
    tune=None,
    dtypes: Sequence | None = None,
    window_kind: str | None = None,
) -> jnp.ndarray:
    """Run a stage-chain stencil program — the iterative-solver workload.

    * ``stencil_iterate(u, offsets, weights, T)`` applies the same
      operator T times (Jacobi sweeps) — equal to iterating
      ``kernels.ref.stencil_ref`` T times, zero boundary fill.
    * ``stencil_iterate(u, stages=[(offsets_1, weights_1), ...])`` runs a
      chain with a *distinct* operator per stage (Runge-Kutta sub-steps,
      damped-Jacobi smoother pairs) — equal to applying the references in
      order.

    The planner chooses how deeply to fuse (``plan.fused_depth``): each
    fused launch advances up to that many consecutive stages in one HBM
    pass via the §8/§9 window with streaming frontiers, and the chain
    runs ``ceil(T / fused_depth)`` launches; an explicit ``tile`` fuses
    all T stages into one launch.

    ``num_shards``/``shard_axis``/``mesh`` shard every launch over
    cross-axis tile columns (DESIGN.md §10), bit-wise equal to the
    single-device launch.  ``tune=True`` (or an ``AutoTuner``) plans
    through the §11 measured-cost loop; it excludes ``plan``/``tile``.

    ``dtypes=[dt_1, ..., dt_T]`` declares each stage's output dtype
    (``None`` entries = the input's): frontiers, inter-launch handoffs
    and the final write-back happen at the stage dtype while every stage
    still accumulates in f32 (DESIGN.md §14).  ``window_kind`` forces the
    frontier layout (``"ring"``/``"trapezoid"``); default: the plan's
    choice, else the ring."""
    with obs.call_span("stencil_call") as root:
        if stages is not None:
            if offsets is not None or weights is not None:
                raise ValueError("pass (offsets, weights) or stages, not both")
            if time_steps is not None and time_steps != len(stages):
                raise ValueError(
                    f"time_steps={time_steps} contradicts {len(stages)} "
                    "stages"
                )
            key = tuple((_offsets_key(o), _weights_key(w)) for o, w in stages)
        else:
            if offsets is None or weights is None or time_steps is None:
                raise ValueError(
                    "stencil_iterate needs (offsets, weights, time_steps) "
                    "or stages"
                )
            if int(time_steps) < 1:
                raise ValueError(f"time_steps must be >= 1, got {time_steps}")
            key = ((_offsets_key(offsets), _weights_key(weights)),) * int(
                time_steps
            )
        program = _chain_of(u.ndim, key,
                            None if dtypes is None else tuple(dtypes))
        return _call(root, program, (u,), tile, interpret, vmem_budget,
                     sweep_axis, plan, num_shards, shard_axis, mesh, tune,
                     window_kind)


def run(
    program: ir.Program,
    arrays,
    *,
    tile: Sequence[int] | None = None,
    interpret: bool | None = None,
    vmem_budget: int | None = None,
    sweep_axis: int | None = None,
    plan: "StencilPlan | None" = None,
    num_shards: int | None = None,
    shard_axis: int | None = None,
    mesh=None,
    tune=None,
    window_kind: str | None = None,
) -> jnp.ndarray:
    """Execute ``program`` on the sweep engine.

    ``arrays`` maps the program's load names to jax arrays (a single
    array, or a list matched to ``program.inputs()`` order, also works).
    Boundary ops lower to in-kernel correction taps, per-stage dtypes
    and quantizations ride on the program's ops.  The keywords are
    :func:`stencil_iterate`'s: tile/sweep resolution, ``plan`` (validated
    against the call; a mismatch raises
    :class:`repro.plan.PlanMismatchError`), ``tune``, sharding and the
    frontier layout."""
    with obs.call_span("stencil_call") as root:
        names = program.inputs()
        if isinstance(arrays, dict):
            missing = [n for n in names if n not in arrays]
            if missing:
                raise KeyError(
                    f"program inputs missing from arrays: {missing}"
                )
            us = tuple(arrays[n] for n in names)
        elif isinstance(arrays, (list, tuple)):
            if len(arrays) != len(names):
                raise ValueError(
                    f"{len(arrays)} arrays for {len(names)} program inputs"
                )
            us = tuple(arrays)
        else:
            us = (arrays,) * len(names)
        return _call(root, program, us, tile, interpret, vmem_budget,
                     sweep_axis, plan, num_shards, shard_axis, mesh, tune,
                     window_kind)


# -- the builder cache: one Program per stencil_iterate spelling --------------


def _offsets_key(offsets):
    a = np.asarray(offsets)
    return a.dtype.str, a.shape, a.tobytes()


def _weights_key(weights):
    return tuple(map(float, weights))


def _offsets_of(key, d):
    dtype, shape, raw = key
    return np.frombuffer(raw, dtype=dtype).reshape(shape).reshape(-1, d)


def _dtype_names(dtypes):
    if dtypes is None:
        return None
    return tuple(
        str(jnp.dtype(dt).name) if dt is not None else None for dt in dtypes
    )


@functools.lru_cache(maxsize=128)
def _chain_of(d, stages, dtypes):
    """The program of a stage chain (T repeats of one operator are the
    same chain), from its builder key: per stage, the offsets and
    weights keys."""
    if not stages:
        raise ValueError("stages must contain at least one stage")
    chain = []
    for o, ws in stages:
        offs = _offsets_of(o, d)
        if len(offs) != len(ws):
            raise ValueError(
                f"stage has {len(offs)} offsets but {len(ws)} weights"
            )
        chain.append((offs, ws))
    return ir.chain_program(chain, d, dtypes=_dtype_names(dtypes))


# -- the entry memo (DESIGN.md §17) -------------------------------------------

_MEMO_SIZE = 128  # resolved signatures kept, least recently used dropped
_memo: OrderedDict = OrderedDict()
_memo_lock = threading.Lock()


class _Launch(NamedTuple):
    """One resolved launch: ``call(arrays)`` runs it with every static
    argument bound; ``args`` and ``counters`` are what a recorder gets
    for it (the ``kernel_launch`` span's arguments, the counter bumps)."""

    call: object
    args: dict
    counters: tuple


class _Entry(NamedTuple):
    """A call signature's resolved launch sequence.  ``order`` permutes a
    multi-RHS call's arrays into the lowered operand order; ``pins`` holds
    the objects the key names by identity (plan, mesh, planner) so their
    ids stay theirs while the entry lives; ``program`` is a weak reference
    to the keyed :class:`repro.ir.Program`.  No caller array is held: the
    arrays are arguments of the launches, never captured."""

    launches: tuple
    order: tuple | None
    pins: tuple
    program: object

    def run(self, us):
        if self.order is not None:
            us = tuple(us[i] for i in self.order)
        recording = obs.enabled()
        for launch in self.launches:
            if recording:
                for name, value in launch.counters:
                    obs.add(name, value)
                span = obs.span("kernel_launch", **launch.args)
            else:
                span = obs.span("kernel_launch")
            with span:
                out = launch.call(us)
            us = (out,)
        return out


def _memo_get(key, program):
    with _memo_lock:
        entry = _memo.get(key)
        if entry is None:
            return None
        if entry.program() is not program:
            del _memo[key]  # a freed program's id, reused
            return None
        _memo.move_to_end(key)
        return entry


def _memo_put(key, entry):
    with _memo_lock:
        _memo[key] = entry
        _memo.move_to_end(key)
        while len(_memo) > _MEMO_SIZE:
            _memo.popitem(last=False)


def _call(root, program, us, tile, interpret, vmem_budget, sweep_axis,
          plan, num_shards, shard_axis, mesh, tune, window_kind):
    """One call's body: on a memo hit, the bound launches; on a miss (and
    on every ``tune=`` call, since the tuner measures), lower, plan and
    bind them first.  ``root`` is the call's ``stencil_call`` span, which
    gets ``memo=hit|miss``.  The key holds everything that decides the
    launches, cheap to hash: the program, ``plan`` and ``mesh`` by
    identity (the program checked against its weak reference on a hit),
    the arrays' shapes and dtypes, every planning keyword, and the
    default planner with its generation."""
    interpret = resolve_interpret(interpret, kernel="stencil")
    key = entry = None
    if not tune:
        planner = default_planner()
        key = (
            id(program), tuple((u.shape, u.dtype) for u in us),
            None if tile is None else tuple(tile), interpret, vmem_budget,
            sweep_axis, num_shards, shard_axis, window_kind, id(mesh),
            id(plan), id(planner), planner.generation,
        )
        entry = _memo_get(key, program)
    outcome = "miss" if entry is None else "hit"
    obs.add("entry_memo_" + outcome)
    if root is not obs.NULL_SPAN:
        root.set(memo=outcome)
    if entry is None:
        entry = _resolve(program, us, tile, interpret, vmem_budget,
                         sweep_axis, plan, num_shards, shard_axis, mesh,
                         tune, window_kind)
        if key is not None:
            _memo_put(key, entry)
    return entry.run(us)


def _plan_call(shape, offsets_list, dtype_bytes, n_arrays, vmem_budget=None,
               time_steps=1, stages=None, num_shards=1, tune=None,
               bcs=None, dtypes=None, window_kind="auto", shard_axis=None):
    """Tile decision for an un-planned call: a thin wrapper over the plan
    compiler (``repro.plan``), whose persistent cache makes repeated shapes
    — the serving case — O(1).

    ``stages`` (per-stage offset arrays, weights deliberately stripped so
    cache keys stay weight-independent) requests a stage-chain plan; a
    homogeneous chain canonicalizes to the same request — and cache key —
    as the ``offsets + time_steps`` spelling.

    ``tune`` (``True`` or an ``AutoTuner``) routes the decision through
    the §11 measured-cost loop instead: a warm TunedPlanDB hit serves the
    measured winner, a miss races the top-k candidates on the live
    backend first, each through :func:`run`.

    ``shard_axis`` is the caller's pinned partition axis of a sharded
    call: the tile is planned for that axis's column slab
    (``Planner.plan_along``), not for the planner's own choice of axis."""
    d = len(shape)
    kw = dict(
        shape=tuple(int(n) for n in shape),
        dtype_bytes=dtype_bytes,
        vmem_budget=vmem_budget,
        n_operands=n_arrays + 1,  # p inputs + the output tile (§5 split)
        num_shards=int(num_shards),
    )
    kw["window_kind"] = window_kind
    if stages is not None:
        kw["stages"] = [np.asarray(o).reshape(-1, d) for o in stages]
        if bcs is not None and any(bc is not None for bc in bcs):
            kw["bcs"] = tuple(bcs)
        if dtypes is not None and any(dt is not None for dt in dtypes):
            kw["dtypes"] = tuple(dtypes)
    else:
        kw["offsets"] = [np.asarray(o).reshape(-1, d) for o in offsets_list]
        kw["time_steps"] = time_steps
    tuner = resolve_tuner(tune)
    if tuner is not None:
        return tuner.plan(runner=run, **kw)
    if shard_axis is not None and num_shards > 1:
        return default_planner().plan_along(shard_axis, **kw)
    return default_planner().plan(**kw)


def _resolve(prog, us, tile, interpret, vmem_budget, sweep_axis, plan,
             num_shards, shard_axis, mesh, tune, window_kind):
    """Lower, plan and bind one call signature: its :class:`_Entry`."""
    if window_kind is not None and window_kind not in ("ring", "trapezoid"):
        raise ValueError(
            f"window_kind must be 'ring' or 'trapezoid', got {window_kind!r}"
        )
    assert len({u.shape for u in us}) == 1, "RHS arrays must share a shape"
    d = us[0].ndim
    shape = tuple(int(n) for n in us[0].shape)
    in_name = str(jnp.dtype(us[0].dtype).name)
    # -- verify + lower the stencil program (§13) ---------------------------
    with obs.span("ir_lower"):
        lowered = ir.lower(prog, shape)
        prog_summary = ir.summarize_program(prog)
    order = None
    if lowered.kind == "chain":
        if len(us) != 1:
            raise ValueError(
                f"program lowers to a stage chain over one input; got "
                f"{len(us)} arrays"
            )
        chain = tuple(
            (np.asarray(o, dtype=np.int64).reshape(-1, d), wts)
            for o, wts in lowered.stages
        )
        bcs = lowered.bcs
        T = len(chain)
        offsets_list = [chain[0][0]]
        # Per-stage output dtypes, resolved once against the chain input:
        # ``eff`` holds concrete names for the kernel/launch handoffs,
        # ``req_dtypes`` the None-normalized form the plan stack keys on
        # (a stage at the input dtype is the same request as no dtype).
        chain_dtypes = tuple(lowered.dtypes) if lowered.dtypes else (None,) * T
        assert len(chain_dtypes) == T, (chain_dtypes, T)
        # §15 per-stage quantizations: execution parameters (not part of
        # plan keys — StageSpec dtypes already differentiate), threaded
        # straight to the launches.
        chain_quants = (
            tuple(lowered.quants) if lowered.quants else (None,) * T
        )
        assert len(chain_quants) == T, (chain_quants, T)
        eff = tuple(
            str(jnp.dtype(dt).name) if dt is not None else in_name
            for dt in chain_dtypes
        )
        req_dtypes = tuple(dt if dt != in_name else None for dt in eff)
        if all(dt is None for dt in req_dtypes):
            eff = None
            req_dtypes = None
    else:  # multi-RHS single application
        if len(us) != len(lowered.inputs):
            raise ValueError(
                f"program loads {len(lowered.inputs)} inputs; got "
                f"{len(us)} arrays"
            )
        # ``us`` arrives in load order; the combine may sum the operands
        # in any order, and stage p applies to lowered.inputs[p].
        load_order = {name: i for i, name in enumerate(prog.inputs())}
        order = tuple(load_order[name] for name in lowered.inputs)
        us = tuple(us[i] for i in order)
        if order == tuple(range(len(us))):
            order = None
        chain = None
        bcs = ()
        T = 1
        eff = req_dtypes = None
        chain_quants = (None,)
        offsets_list = [
            np.asarray(o, dtype=np.int64).reshape(-1, d)
            for o, _ in lowered.stages
        ]
        weights_list = [list(wts) for _, wts in lowered.stages]
    explicit_sweep = sweep_axis is not None
    explicit_shard = shard_axis is not None
    if num_shards is None:
        if mesh is not None:
            num_shards = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        elif plan is not None:
            num_shards = plan.num_shards
    depth = None
    pipelined = True
    if tune and (plan is not None or tile is not None):
        raise ValueError(
            "tune= requests the §11 measured-cost planning loop, but "
            "plan=/tile= pin the decision already — pass one or the other"
        )
    resolved_plan = None
    if plan is not None:
        validate_plan_call(
            plan,
            us[0].shape,
            [np.asarray(o).reshape(-1, d) for o in offsets_list],
            us[0].dtype.itemsize,
            time_steps=T,
            stages=[offs for offs, _ in chain] if chain is not None else None,
            bcs=bcs if chain is not None else None,
            dtypes=req_dtypes if chain is not None else None,
        )
        if tile is None:
            tile = plan.tile
        if sweep_axis is None:
            sweep_axis = plan.sweep_axis
        if shard_axis is None:
            shard_axis = plan.shard_axis
        if window_kind is None:
            window_kind = plan.window_kind
        pipelined = plan.pipelined
        depth = plan.fused_depth
        resolved_plan = plan
    elif tile is None:
        choice = _plan_call(
            us[0].shape, offsets_list, us[0].dtype.itemsize, len(us),
            vmem_budget=vmem_budget, time_steps=T,
            stages=(
                [offs for offs, _ in chain] if chain is not None else None
            ),
            num_shards=num_shards or 1,
            tune=tune,
            bcs=bcs if chain is not None else None,
            dtypes=req_dtypes if chain is not None else None,
            window_kind=window_kind or "auto",
            shard_axis=shard_axis,
        )
        tile = choice.tile
        if sweep_axis is None:
            sweep_axis = choice.sweep_axis
        if shard_axis is None:
            shard_axis = choice.shard_axis
        if window_kind is None:
            window_kind = choice.window_kind
        depth = choice.fused_depth
        resolved_plan = choice
    if sweep_axis is None:
        sweep_axis = 0
    if window_kind is None:
        window_kind = "ring"  # §14 default: strictly smaller resident set
    if depth is None:
        depth = T  # explicit tile: the caller owns the VMEM arithmetic
    tile = tuple(int(t) for t in tile)
    sweep_axis = int(sweep_axis)
    pipelined = bool(pipelined)
    num_shards = 1 if num_shards is None else int(num_shards)
    sharded = num_shards > 1 or mesh is not None

    if (
        sharded
        and shard_axis is not None
        and int(shard_axis) == sweep_axis
        and explicit_shard != explicit_sweep
    ):
        # Exactly one of the two axes was pinned by the caller and the
        # planner's independent choice of the other collided with it: the
        # explicit pin wins — re-derive the free axis instead of refusing
        # a feasible call.
        if explicit_shard:
            ncols = {
                i: -(-us[0].shape[i] // tile[i])
                for i in range(d)
                if i != int(shard_axis)
            }
            if not ncols:  # 1-d grid: let the launcher raise its error
                ncols = {sweep_axis: 1}
            sweep_axis = max(ncols, key=lambda i: (ncols[i], -i))
        else:
            shard_axis = pick_shard_axis(us[0].shape, tile, sweep_axis)

    p = resolved_plan
    if p is not None:
        chain_bytes = (
            p.per_shard_traffic_bytes * p.num_shards + p.halo_exchange_bytes
        )
        plan_key = p.request.cache_key()
    else:
        plan_key = "<explicit-tile>"  # explicit tile: the caller owns the model

    def bind(statics, n_run, x_dtype, run=None, run_dts=None, run_qs=None,
             bcs_w=None, in_q=None):
        """One launch, bound: the sharded path's built program or the
        single-device bound call; and what a recorder gets for it — this
        launch's slice of the plan's whole-chain model (n_run of T
        stages), what it reads its input from (``input_buffer``), how far
        the input stops short of whole (sublane, lane) grains
        (``grid_slack``), and the counters the report CLI reconciles
        against the spans.  The ``kernel_launch`` span times the host's
        side only: the bound call until it returns the unready array."""
        xbytes = None
        if sharded:
            call, xbytes = column_launch(
                shape, x_dtype, len(us), num_shards=num_shards,
                shard_axis=shard_axis, mesh=mesh, **statics,
            )
        else:
            call = bound_call(tuple(sorted(statics.items())))
        if p is not None:
            n_stages = max(len(chain) if chain is not None else 1, 1)
            mb = round(chain_bytes * n_run / n_stages)
            mf = round(p.modeled_flops * n_run / n_stages)
        else:
            mb = mf = 0
        # §14 frontier accounting: the modeled VMEM bytes of this
        # launch's staged buffers under the resolved window kind, at each
        # stage's own dtype — reconciled by ``repro.obs.report --check``.
        rvb = 0
        if run is not None and len(run) > 1:
            run_halos = [halo_from_offsets([o], d) for o, _ in run]
            in_db = us[0].dtype.itemsize
            sdb = [
                dtype_itemsize(dt) if dt is not None else in_db
                for dt in (run_dts or (None,) * len(run))
            ]
            rvb = fused_stage_bytes(
                tile, run_halos[0], in_db, len(run),
                stage_halos=run_halos, window_kind=window_kind,
                sweep_axis=sweep_axis, stage_dtype_bytes=sdb,
            ) * max(num_shards, 1)
        quantized = run_qs is not None and any(
            q is not None for q in run_qs
        )
        x_db = jnp.dtype(x_dtype).itemsize
        halo = (
            chain_halo([halo_from_offsets([o], d) for o, _ in run])
            if run is not None
            else halo_from_offsets(offsets_list, d)
        )
        buf = input_buffer(shape, tile, halo, x_db, bcs_w, in_q, num_shards)
        slack = grid_slack(shape, x_db)
        counters = (
            ("launches", 1),
            ("direct_input_launches", int(buf == "direct")),
            ("offgrain_launches", int(any(slack))),
            ("modeled_bytes", mb),
            ("modeled_flops", mf),
            ("ring_vmem_bytes", rvb),
        )
        if xbytes is not None:
            # The exchange runs inside the jitted SPMD program (its device
            # time is the ``stencil_halo`` scope of a profiler trace), so
            # the counter holds the modeled cross-device bytes.
            counters += (("halo_exchange_bytes", xbytes),)
        args = dict(
            plan_key=plan_key, tile=list(tile), sweep_axis=sweep_axis,
            fused_depth=int(depth), steps=n_run, num_shards=num_shards,
            interpret=interpret, modeled_bytes=mb, modeled_flops=mf,
            program=prog_summary, window_kind=window_kind,
            input_buffer=buf, grid_slack=list(slack),
            stage_dtypes=(list(run_dts) if run_dts is not None else None),
            ring_vmem_bytes=rvb,
            stage_quants=(
                [list(q) if q is not None else None for q in run_qs]
                if quantized else None
            ),
        )
        return _Launch(call, args, counters)

    def static_spec(op):
        offs, wts = op
        return (tuple(map(tuple, np.asarray(offs).tolist())), tuple(wts))

    base = dict(tile=tile, sweep=sweep_axis, pipelined=pipelined,
                interpret=interpret)
    launches = []
    if chain is None:  # multi-RHS single application
        offsets_w = tuple(
            static_spec((o, tuple(float(w) for w in ws)))
            for o, ws in zip(offsets_list, weights_list)
        )
        launches.append(bind(dict(base, offsets_w=offsets_w), 1,
                             str(jnp.dtype(us[0].dtype).name)))
    else:
        pos = 0
        in_q = None
        x_dtype = in_name
        while pos < len(chain):
            run = chain[pos : pos + int(depth)]
            run_bcs = tuple(bcs[pos : pos + len(run)])
            run_dts = (
                tuple(eff[pos : pos + len(run)]) if eff is not None else None
            )
            run_qs = tuple(chain_quants[pos : pos + len(run)])
            pos += len(run)
            bcs_w = run_bcs if any(bc is not None for bc in run_bcs) else None
            statics = dict(base, offsets_w=(static_spec(run[0]),))
            if bcs_w is not None or run_dts is not None:
                # §13 boundary-op / §14 mixed-dtype / §15 quantized
                # launch: always the stage-chain form (even for one
                # stage), with the lowered per-stage bcs as in-kernel
                # correction taps and the per-stage output dtypes on the
                # frontiers/write-back.  A quantized stage anywhere in
                # the chain forces eff non-None (its dtype is int8), so
                # every launch of such a chain takes this branch and the
                # quantized inter-launch handoff (``in_q``) is threaded.
                statics.update(
                    stages_w=tuple(static_spec(op) for op in run),
                    bcs_w=bcs_w,
                    dtypes_w=run_dts,
                    window_kind=window_kind,
                    quants_w=run_qs if any(
                        q is not None for q in run_qs
                    ) else None,
                    in_quant=in_q,
                )
            elif len(run) > 1:
                statics.update(
                    stages_w=tuple(static_spec(op) for op in run),
                    window_kind=window_kind,
                )
            launches.append(bind(statics, len(run), x_dtype, run, run_dts,
                                 run_qs, bcs_w, in_q))
            # The next launch reads this one's output, at its last stage's
            # dtype.
            if run_dts is not None:
                x_dtype = run_dts[-1]
            in_q = run_qs[-1]
    return _Entry(
        launches=tuple(launches),
        order=order,
        pins=(plan, mesh, default_planner()),
        program=weakref.ref(prog),
    )
