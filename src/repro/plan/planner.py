"""The stencil plan compiler — the paper's pipeline as one pass.

``Planner.plan`` runs, in order:

1. **Interference lattice** (§4, Eq. 8/9): build the Eq. 9 basis of the
   grid's interference lattice for the target cache of S words, LLL-reduce
   it, and find the shortest vector.
2. **Unfavorable-grid detection** (§6): the grid is unfavorable when the
   shortest L1 lattice vector is below the stencil diameter divided by the
   associativity — the Fig. 5 miss spikes.
3. **Padding proposal** (§6, Appendix B): minimal padding of the leading
   dims that clears the threshold (``core.padding.pad_grid``), emitted as
   a :class:`~repro.plan.schema.PadPlan`.
4. **Tile enumeration + scoring**: the sweep engine's candidate tiles
   (``core.tiling.candidate_tiles``) *plus* two lattice-informed boxes —
   the bounding box of the reduced-basis parallelepiped (§4's fundamental
   parallelepiped, axis-aligned because DMA engines move rectangles) and
   the surface-to-volume-optimal box (T_i ∝ halo_i at fixed volume) — all
   scored by the §4 traffic model under the per-operand VMEM budget.
   With ``time_steps=T > 1`` the scoring repeats at every fusion depth
   1..T (halos and staged windows grown per DESIGN.md §8) and the depth
   minimizing the whole chain's modeled traffic wins; depth 1 is always a
   candidate, so a fused plan provably never scores worse than the
   planner's own single-pass choice.
5. **Freeze**: the winning (pad, tile, sweep axis) plus predicted traffic,
   VMEM footprint, the isoperimetric lower bound and the legacy-heuristic
   baseline become a frozen, serializable
   :class:`~repro.plan.schema.StencilPlan`.

With ``num_shards=S > 1`` (DESIGN.md §10) step 4 runs on the *worst
shard's column slab* — the per-core cache-fitting problem, with the
sweep constrained off the shard axis — so all traffic/flop fields become
per-shard, and the plan additionally freezes the shard axis and the
modeled halo-exchange bytes.  ``num_shards=1`` is byte-identical to an
unsharded request.

Steps 1–3 only run when the request carries a hardware ``geometry``
(a, z, w); on an explicitly-managed memory (TPU VMEM) conflict misses do
not exist and the pad stage is a documented no-op.

``strategy="legacy"`` reproduces the old ``kernels.stencil._auto_tile``
heuristic exactly (default candidate set only); ``strategy="paper"`` adds
the lattice candidates and asserts it never predicts more traffic than
legacy — the candidate set is a strict superset under the same model, so
the assert is a model-consistency check, not a hope.
"""

from __future__ import annotations

import time
from math import prod
from typing import Sequence

import numpy as np

from repro.core.lattice import (
    CacheGeometry,
    basis_eccentricity,
    interference_basis,
    lll_reduce,
    shortest_vector,
)
from repro.core.padding import hyperbola_index, pad_grid
from repro.core.tiling import (
    LANE,
    TARGET_VMEM_BYTES,
    TileChoice,
    chain_flops,
    chain_halo,
    dtype_itemsize,
    fused_stage_bytes,
    halo_from_offsets,
    kernel_vmem_bytes,
    select_tile,
    sublane_unit,
    tile_traffic_bytes,
    tile_vmem_bytes,
)

from .. import obs
from .cache import PlanCache
from .schema import LatticeReport, PadPlan, PlanRequest, StencilPlan

__all__ = ["Planner", "default_planner", "plan_stencil"]


def _program_stage_halos(request: PlanRequest, d: int):
    """Per-stage operator halos of a chain request, sourced from its
    canonical serialized stencil program (DESIGN.md §13): the IR's
    accessed-offset footprints over the program's ``apply`` ops — which
    are exactly the cut-points the depth scoring fuses between.  Requests
    constructed directly (no derived program) fall back to the stage-list
    arithmetic; the two agree by construction and by test."""
    if request.program:
        from repro.ir import Program, stage_halos as ir_stage_halos

        halos = ir_stage_halos(Program.from_json(request.program))
        if len(halos) == len(request.stages):
            return [tuple(h) for h in halos]
    return [halo_from_offsets([st.offsets], d) for st in request.stages]


def _align_extent(t: int, n: int, unit: int) -> int:
    """Clamp a tile extent to [1, n], snapped down to ``unit`` multiples
    (or up to min(unit, n) when below the grain)."""
    t = max(1, min(int(t), int(n)))
    if n < unit:
        return n
    if t < unit:
        return min(unit, n)
    return (t // unit) * unit


def _fit_to_budget(tile, shape, halo, dtype_bytes, budget, aligned):
    """Shrink a candidate box (halving its largest extent) until the halo'd
    window fits the per-operand budget.  Returns None if even the unit tile
    does not fit."""
    tile = list(tile)
    d = len(tile)
    sub = sublane_unit(dtype_bytes)
    for _ in range(64):
        if tile_vmem_bytes(
            tile, halo, dtype_bytes, None, False, aligned=aligned
        ) <= budget:
            return tuple(tile)
        i = max(range(d), key=lambda j: tile[j])
        if tile[i] <= 1:
            return None
        tile[i] = max(1, tile[i] // 2)
        if aligned:
            unit = LANE if i == d - 1 else sub if i == d - 2 else 1
            tile[i] = _align_extent(tile[i], shape[i], unit)
    return None


class _Survey:
    """One request's scored planning state, shared by ``plan()``'s argmin
    and ``candidates()``'s enumeration: the lattice/pad decisions, the
    (possibly shard-slab) work shape, the legacy baseline, the per-depth
    best tiles with their whole-chain prices, and the ``tiled``/
    ``price_chain`` closures for scoring further (depth, sweep-axis)
    combinations under identical budgets."""

    __slots__ = (
        "request", "d", "T", "db", "halo", "stage_halos", "lattice", "pad",
        "work", "work_full", "num_shards", "shard_axis", "extras", "legacy",
        "legacy_priced", "per_depth", "scored", "tiled", "price_chain",
        "window_kind", "stage_dbs",
    )

    def __init__(self, **kw):
        for name in self.__slots__:
            setattr(self, name, kw.pop(name))
        assert not kw, f"unexpected survey fields: {sorted(kw)}"


class Planner:
    """Compiles :class:`PlanRequest` → :class:`StencilPlan`, memoized by a
    :class:`PlanCache` (content-addressed, persistent)."""

    def __init__(
        self,
        strategy: str = "paper",
        cache: PlanCache | None = None,
        tuned_db=None,
    ):
        assert strategy in ("paper", "legacy"), strategy
        self.strategy = strategy
        self.cache = cache if cache is not None else PlanCache()
        # Optional repro.plan.tunedb.TunedPlanDB: when attached, plan()
        # prefers a measured winner recorded for this exact request on
        # this exact backend (DESIGN.md §11); a DB miss falls back to the
        # analytic choice unchanged.
        self.tuned_db = tuned_db
        self.last_plan_seconds: float | None = None  # cold-vs-warm telemetry
        self.last_plan_tuned: bool = False           # did a tuned entry win?
        self.last_plan_cache: str | None = None      # hit / miss / tuned
        self._along: dict = {}  # (request, pinned shard axis) -> plan

    # -- cheap diagnostics (no tile search) --------------------------------

    def lattice_report(
        self, shape: Sequence[int], S: int, diameter: int, a: int = 1
    ) -> LatticeReport:
        """Steps 1–2 of the pipeline for one grid: basis → LLL → shortest
        vector → §6 unfavorable criterion + Fig. 5 hyperbola fit."""
        shape = tuple(int(n) for n in shape)
        B = interference_basis(shape, S)
        R = lll_reduce(B)
        v = shortest_vector(R, norm="l1")
        l1 = float(np.abs(v).sum())
        l2 = float(np.sqrt((v.astype(np.float64) ** 2).sum()))
        threshold = diameter / a
        k, dist = (
            hyperbola_index(shape, S) if len(shape) >= 2 else (0, float("inf"))
        )
        return LatticeReport(
            S=int(S),
            basis=tuple(tuple(int(x) for x in row) for row in B),
            reduced=tuple(tuple(int(x) for x in row) for row in R),
            shortest=tuple(int(x) for x in v),
            shortest_l1=l1,
            shortest_l2=l2,
            eccentricity=float(basis_eccentricity(R)),
            diameter=int(diameter),
            threshold=float(threshold),
            unfavorable=l1 < threshold,
            hyperbola_k=int(k),
            hyperbola_dist=float(dist),
        )

    def pad_plan(
        self,
        shape: Sequence[int],
        S: int,
        diameter: int,
        a: int = 1,
        max_pad: int = 16,
        lattice: LatticeReport | None = None,
    ) -> PadPlan:
        """Step 3: minimal favorable padding, or an explained zero pad."""
        shape = tuple(int(n) for n in shape)
        rep = lattice or self.lattice_report(shape, S, diameter, a)
        if not rep.unfavorable:
            return PadPlan.zero(
                shape,
                shortest=rep.shortest_l1,
                threshold=rep.threshold,
                reason=(
                    f"favorable: shortest lattice vector |v|_1="
                    f"{rep.shortest_l1:.0f} >= {rep.threshold:.3g}"
                ),
            )
        padded, info = pad_grid(shape, S, diameter, a=a, max_pad=max_pad)
        return PadPlan(
            pad=tuple(p - n for p, n in zip(padded, shape)),
            padded_shape=tuple(int(n) for n in padded),
            extra_words=int(info["extra_words"]),
            shortest_before=float(info["shortest_before"]),
            shortest_after=float(info["shortest_after"]),
            threshold=float(info["threshold"]),
            reason=(
                f"unfavorable: shortest lattice vector {rep.shortest} "
                f"(|v|_1={rep.shortest_l1:.0f}) < {rep.threshold:.3g}; "
                f"near Fig. 5 hyperbola n1*n2 = k*S/2 with k={rep.hyperbola_k} "
                f"(rel. dist {rep.hyperbola_dist:.3f})"
            ),
        )

    # -- lattice-informed tile candidates ----------------------------------

    def _extra_candidates(
        self, shape, halo, request: PlanRequest, lattice: LatticeReport | None
    ) -> list[tuple[int, ...]]:
        d = len(shape)
        budget = request.vmem_budget // max(request.n_operands, 1)
        db = request.dtype_bytes
        sub = sublane_unit(db)
        cands: list[tuple[int, ...]] = []

        def add(tile):
            if tile is None:
                return
            tile = tuple(
                _align_extent(
                    t, n, LANE if i == d - 1 else sub if i == d - 2 else 1
                )
                if request.aligned
                else max(1, min(int(t), int(n)))
                for i, (t, n) in enumerate(zip(tile, shape))
            )
            fit = _fit_to_budget(tile, shape, halo, db, budget, request.aligned)
            if fit is not None and fit not in cands:
                cands.append(fit)

        # (a) Bounding box of the reduced-basis parallelepiped: the paper's
        # §4 fundamental parallelepiped has det = S and near-cubic shape
        # after LLL; DMA engines move rectangles, so we take its box hull.
        if lattice is not None:
            R = np.asarray(lattice.reduced, dtype=np.int64)
            add(np.abs(R).max(axis=0))
        # (b) s2v-optimal box: minimizing Σ_i h_i/T_i at fixed volume V
        # gives T_i ∝ h_i (Lagrange); scale to the budgeted volume.
        w = [max(lo + hi, 1) for lo, hi in halo]
        vol = max(budget // db, 1)
        scale = (vol / prod(w)) ** (1.0 / d)
        add([max(1, round(wi * scale)) for wi in w])
        # (c) the same box with the sweep dim collapsed thin (the scanning
        # face): under sweep reuse the sweep extent stops paying surface.
        for s in range(d):
            thin = [max(1, round(wi * scale)) for wi in w]
            thin[s] = 1
            add(thin)
        return cands

    # -- the full pipeline -------------------------------------------------

    def plan(self, request: PlanRequest | None = None, /, **kw) -> StencilPlan:
        """Compile (or fetch from cache) the plan for one request.  Keyword
        form builds the request via :meth:`PlanRequest.make`, with the
        planner's strategy as default.

        With a ``tuned_db`` attached, a measured winner recorded for this
        request on this backend wins over the analytic choice (§11 autotune
        loop); a DB miss — or no DB — resolves analytically, unchanged.

        The ``plan`` span covers the request build, its sha256 key and the
        resolution, and carries ``cache``: ``hit``, ``miss`` or
        ``tuned`` (:attr:`last_plan_cache`).  With neither a recorder nor
        a profiler it is the shared null span."""
        with obs.span("plan") as sp:
            if request is None:
                kw.setdefault("strategy", self.strategy)
                request = PlanRequest.make(**kw)
            key = request.cache_key()
            plan = self._plan_resolve(request, key)
            if sp is not obs.NULL_SPAN:
                sp.set(cache=self.last_plan_cache)
            if obs.enabled():
                sp.set(
                    key=key,
                    tuned=self.last_plan_tuned,
                    tile=list(plan.tile),
                    sweep_axis=plan.sweep_axis,
                    fused_depth=plan.fused_depth,
                    num_shards=plan.num_shards,
                    traffic_bytes=plan.traffic_bytes,
                )
        return plan

    def _plan_resolve(self, request: PlanRequest, key: str) -> StencilPlan:
        t0 = time.perf_counter()
        self.last_plan_tuned = False
        if self.tuned_db is not None:
            tuned = self._tuned_winner(key)
            if tuned is not None:
                self.last_plan_tuned = True
                self.last_plan_cache = "tuned"
                self.last_plan_seconds = time.perf_counter() - t0
                return tuned
        plan = self._analytic(request, key)
        self.last_plan_seconds = time.perf_counter() - t0
        return plan

    def _analytic(
        self, request: PlanRequest, key: str | None = None
    ) -> StencilPlan:
        """The model-driven plan (PlanCache-memoized), never consulting the
        tuned DB — the autotuner's baseline and candidate source."""
        key = key if key is not None else request.cache_key()
        cached = self.cache.get(key)
        if cached is not None:
            self.last_plan_cache = "hit"
            return cached
        self.last_plan_cache = "miss"
        plan = self._compile(request)
        self.cache.put(key, plan)
        return plan

    def _tuned_winner(self, key: str) -> StencilPlan | None:
        from .tune import backend_fingerprint  # lazy: pulls in jax

        rec = self.tuned_db.get(key, backend_fingerprint())
        return None if rec is None else rec.winner_plan

    # -- candidate enumeration (the §11 autotune surface) ------------------

    def candidates(
        self, request: PlanRequest | None = None, /, k: int = 3, **kw
    ) -> list[StencilPlan]:
        """The top-``k`` candidate plans by modeled chain cost — the scored
        tile/depth/shard enumeration behind :meth:`plan`'s argmin, exposed
        so the §11 autotune loop can *measure* the near-ties instead of
        trusting the model to break them.

        ``candidates()[0]`` is always exactly :meth:`plan`'s analytic
        choice (same object the cache serves); the rest are distinct
        execution signatures — per sweep axis and fusion depth the best
        tile, the legacy-heuristic tile, and (under §10 sharding) every
        alternative shard axis — ranked by modeled whole-chain traffic.
        Fewer than ``k`` plans come back when the request admits fewer
        distinct feasible signatures.  Every returned plan executes this
        request correctly; only their cost fields differ."""
        if request is None:
            kw.setdefault("strategy", self.strategy)
            request = PlanRequest.make(**kw)
        analytic = self._analytic(request)
        k = int(k)
        if k <= 1:
            return [analytic]

        pool: list[tuple] = []
        seen = {
            (analytic.tile, analytic.sweep_axis, analytic.fused_depth,
             analytic.shard_axis)
        }

        def harvest(sv: "_Survey", shard_rank: int) -> None:
            axes: list[int | None] = [None] + [
                i for i, n in enumerate(sv.work) if n > 1
            ]
            if sv.shard_axis is not None:
                # The engine realizes sweep_axis=None as axis-0 grid order,
                # which collides with an axis-0 shard partition (§10).
                axes = [
                    a for a in axes
                    if a != sv.shard_axis
                    and not (a is None and sv.shard_axis == 0)
                ]
            for depth in sorted(sv.scored):
                for rank, axis in enumerate(axes):
                    try:
                        c = sv.tiled(depth, sv.extras, sweep_axis=axis)
                    except ValueError:
                        continue  # no tile fits the budget on this axis
                    priced = sv.price_chain(depth, c)
                    if priced is None:
                        continue
                    s = (c.tile, c.sweep_axis, int(depth), sv.shard_axis)
                    if s in seen:
                        continue
                    seen.add(s)
                    pool.append((priced[0], depth, shard_rank, rank, sv, c,
                                 priced))
            # The legacy heuristic's depth-1 choice is a candidate too:
            # when the analytic model is wrong it is the natural hedge.
            if sv.legacy_priced is not None:
                s = (sv.legacy.tile, sv.legacy.sweep_axis, 1, sv.shard_axis)
                if s not in seen:
                    seen.add(s)
                    pool.append((sv.legacy_priced[0], 1, shard_rank,
                                 len(axes), sv, sv.legacy, sv.legacy_priced))

        sv0 = self._survey(request)
        harvest(sv0, 0)
        if request.num_shards > 1:
            # §10: also enumerate the alternative shard axes — a different
            # column partition changes the per-shard slab, the feasible
            # sweep axes, and the halo-exchange bytes.
            dims = [i for i, n in enumerate(sv0.work_full) if n > 1]
            for j, axis in enumerate(a for a in dims if a != sv0.shard_axis):
                try:
                    sva = self._survey(request, shard_axis_override=axis)
                except (ValueError, AssertionError):
                    continue  # no feasible tiling under this partition
                harvest(sva, j + 1)

        # Rank by modeled whole-chain traffic; ties break shallow-first,
        # then planner-preferred shard/sweep order (stable, like plan()).
        pool.sort(key=lambda t: (t[0], t[1], t[2], t[3]))
        out = [analytic]
        for _traffic, depth, _sr, _ar, sv, c, priced in pool[: k - 1]:
            out.append(self._freeze(sv, int(depth), c, priced))
        return out

    def plan_along(self, shard_axis: int, **kw) -> StencilPlan:
        """:meth:`plan` for a sharded request whose caller pinned the
        partitioned axis (§10): the cached plan when it splits
        ``shard_axis`` already, else the same search over that axis's
        column slab.  The request's cache key stands for the planner's
        own axis, so those plans are memoized in this planner only."""
        plan = self.plan(**kw)
        if plan.shard_axis in (None, int(shard_axis)):
            return plan
        key = (plan.request, int(shard_axis))
        if key not in self._along:
            self._along[key] = self._compile(plan.request, int(shard_axis))
        return self._along[key]

    def _compile(
        self, request: PlanRequest, shard_axis: int | None = None
    ) -> StencilPlan:
        sv = self._survey(request, shard_axis_override=shard_axis)
        single_total = sv.scored[1][0]
        # Shallower wins ties: same modeled traffic, smaller VMEM webs and
        # fewer staged buffers.
        fused_depth = min(sv.scored, key=lambda t: (sv.scored[t][0], t))
        traffic_total = sv.scored[fused_depth][0]
        # Depth 1 is always in the candidate set, so the fused choice can
        # never score worse than the planner's own single-pass plan.
        assert traffic_total <= single_total, (
            f"fused plan regressed vs single-pass: {traffic_total} > "
            f"{single_total} on {sv.work} (T={sv.T}, depth={fused_depth})"
        )
        return self._freeze(
            sv, fused_depth, sv.per_depth[fused_depth], sv.scored[fused_depth]
        )

    def _survey(
        self, request: PlanRequest, shard_axis_override: int | None = None
    ) -> "_Survey":
        shape = request.shape
        d = len(shape)
        stages = request.stages
        if stages:
            # Stage chain (possibly a repeated single operator): per-stage
            # halos drive the launch geometry; the componentwise union is
            # what the lattice/pad stages and the depth-1 tile see (a
            # window sized for the union admits every stage).  The fusion
            # depths scored below are cut-points of the request's stencil
            # program — the halos come from the IR's shape inference over
            # its apply ops (DESIGN.md §13), pinned equal to the legacy
            # stage-list arithmetic by test.
            stage_halos = _program_stage_halos(request, d)
            stage_points = [len(st.offsets) for st in stages]
            halo = halo_from_offsets([st.offsets for st in stages], d)
        else:
            stage_halos = None  # multi-RHS single application
            stage_points = [sum(len(g) for g in request.offsets)]
            halo = halo_from_offsets(request.offsets, d)
        diameter = max(lo + hi + 1 for lo, hi in halo)

        lattice = None
        if request.geometry is not None:
            geom = CacheGeometry(*request.geometry)
            S = geom.size_words
            # a=1: the §6 criterion at direct-mapped worst case — the repo's
            # convention everywhere (a 2-way cache can still thrash when the
            # two images of the scanning face collide with u AND q).
            lattice = self.lattice_report(shape, S, diameter, a=1)
            pad = self.pad_plan(
                shape, S, diameter, a=1, max_pad=request.max_pad,
                lattice=lattice,
            )
        else:
            pad = PadPlan.zero(
                shape,
                reason=(
                    "explicit-memory target (no cache geometry): DMA'd VMEM "
                    "windows have no conflict misses, padding not required"
                ),
            )
        work_full = pad.padded_shape
        T = request.time_steps
        db = request.dtype_bytes
        n_ops = max(request.n_operands, 1)
        per_op_budget = request.vmem_budget // n_ops
        # §14: per-stage frontier element widths (stage output dtypes) and
        # the window-kind candidate set.  "auto" races both frontier
        # layouts under the same model; only chains with T > 1 have
        # frontiers at all, so shallower requests price as trapezoids.
        stage_dbs = (
            [dtype_itemsize(st.dtype) if st.dtype else db for st in stages]
            if stages else None
        )
        wk_req = request.window_kind
        if T <= 1:
            kinds = ("trapezoid",) if wk_req == "auto" else (wk_req,)
        elif wk_req == "auto":
            kinds = ("ring", "trapezoid")
        else:
            kinds = (wk_req,)
        chosen = {"wk": kinds[0]}  # rebound after scoring (closure default)

        # §10 column sharding: a sharded request tiles the *worst shard's
        # column slab* — the per-core cache-fitting problem — with the
        # sweep constrained off the shard axis.  The shard axis is the
        # longest partitionable dim (most columns to split; ties to the
        # lowest index).  With num_shards == 1 nothing changes and the
        # plan is byte-identical to an unsharded one.
        num_shards = request.num_shards
        shard_axis = None
        work = work_full
        if num_shards > 1:
            dims = [i for i, n in enumerate(work_full) if n > 1]
            if not dims:
                dims = list(range(d))
            if shard_axis_override is not None:
                if shard_axis_override not in dims:
                    raise ValueError(
                        f"shard axis {shard_axis_override} not partitionable "
                        f"on padded grid {work_full}"
                    )
                shard_axis = int(shard_axis_override)
            else:
                shard_axis = max(dims, key=lambda i: (work_full[i], -i))
            work = tuple(
                max(-(-n // num_shards), 1) if i == shard_axis else n
                for i, n in enumerate(work_full)
            )

        def tiled(
            depth: int, extras=None, sweep_axis="auto", window_kind=None
        ) -> TileChoice:
            """Tile for one launch: depth 1 scores the per-application
            union halo (a window sized for the union admits every stage of
            a heterogeneous chain); deeper launches score the chain's
            leading ``depth``-stage prefix.  ``sweep_axis`` pins one axis
            (the candidate enumeration); ``"auto"`` is plan()'s argmin.
            ``window_kind=None`` uses the survey's resolved §14 layout."""
            launch = None
            if stage_halos is not None and depth > 1:
                launch = stage_halos[:depth]
            return select_tile(
                work,
                halo,
                dtype_bytes=db,
                vmem_budget=request.vmem_budget,
                n_operands=request.n_operands,
                sweep_axis=sweep_axis,
                aligned=request.aligned,
                prefetch=request.pipelined,
                extra_tiles=extras,
                time_steps=1 if launch is not None else depth,
                stage_halos=launch,
                exclude_sweep_axis=shard_axis,
                window_kind=window_kind or chosen["wk"],
                stage_dtype_bytes=(
                    stage_dbs[:depth] if launch is not None else None
                ),
            )

        def price_chain(depth: int, c: TileChoice, window_kind=None):
            """Modeled (traffic, lower bound, streaming flops, recompute
            flops) of the whole T-step chain as ceil(T/depth) launches of
            c's one tile — launch i fuses the stage run [i·d, (i+1)·d).
            The remainder launch reuses the same tile, so it is priced at
            its own (shorter) run, not with the tile a standalone plan
            would pick.  Returns None when some launch's window + staged
            buffers outgrow VMEM with this tile (heterogeneous chains can
            put their largest halos in a later run).  (Under §10 sharding
            ``work`` is already the shard's column slab, so every figure
            here is per-shard.)"""
            if stage_halos is None:
                fl = chain_flops(
                    work, c.tile, stage_points, [halo], c.sweep_axis,
                )
                return c.traffic_bytes, c.lower_bound_bytes, fl, fl
            traffic = flops_s = flops_r = 0
            lb = 0.0
            for i in range(0, T, depth):
                launch = stage_halos[i : i + depth]
                vmem = tile_vmem_bytes(
                    c.tile, halo, db, c.sweep_axis, request.pipelined,
                    stage_halos=launch, aligned=request.aligned,
                )
                if vmem > per_op_budget:
                    return None
                if len(launch) > 1:
                    staged = fused_stage_bytes(
                        c.tile, halo, db, len(launch), stage_halos=launch,
                        window_kind=window_kind or chosen["wk"],
                        sweep_axis=c.sweep_axis,
                        stage_dtype_bytes=(
                            stage_dbs[i : i + depth] if stage_dbs else None
                        ),
                        aligned=request.aligned,
                    )
                    if vmem * n_ops + staged > request.vmem_budget:
                        return None
                if request.aligned and kernel_vmem_bytes(
                    c.tile, halo, db, c.sweep_axis, request.pipelined,
                    max(n_ops - 1, 1), stage_halos=launch,
                    window_kind=window_kind or chosen["wk"],
                    stage_dtype_bytes=(
                        stage_dbs[i : i + depth] if stage_dbs else None
                    ),
                ) > TARGET_VMEM_BYTES:
                    return None
                traffic += tile_traffic_bytes(
                    work, c.tile, halo, db, c.sweep_axis, stage_halos=launch,
                )
                pts = stage_points[i : i + depth]
                flops_s += chain_flops(
                    work, c.tile, pts, launch, c.sweep_axis, streaming=True,
                )
                flops_r += chain_flops(
                    work, c.tile, pts, launch, c.sweep_axis, streaming=False,
                )
                lb += c.lower_bound_bytes  # per-launch bound: shape + budget
            return traffic, lb, flops_s, flops_r

        legacy = tiled(1)  # the old heuristic: per-step, never fused
        legacy_priced = price_chain(1, legacy)
        if request.strategy == "legacy":
            extras = None
            by_kind = {kinds[0]: {1: legacy}}
        else:
            extras = self._extra_candidates(work, halo, request, lattice)
            by_kind = {}
            for wk in kinds:
                per_depth_k = {}
                for depth in range(1, T + 1):
                    try:
                        per_depth_k[depth] = tiled(
                            depth, extras, window_kind=wk
                        )
                    except ValueError:
                        # The depth-d window + staged intermediates outgrew
                        # the VMEM budget; deeper ones only grow.
                        break
                by_kind[wk] = per_depth_k
            # Superset of candidates under the same model: can never lose.
            first = by_kind[kinds[0]]
            assert first[1].traffic_bytes <= legacy.traffic_bytes, (
                f"planner regressed vs legacy heuristic: "
                f"{first[1].traffic_bytes} > {legacy.traffic_bytes} "
                f"on {work}"
            )

        scored_by_kind = {}
        for wk, per_depth_k in by_kind.items():
            sc = {}
            for depth, c in per_depth_k.items():
                priced = price_chain(depth, c, window_kind=wk)
                if priced is not None:
                    sc[depth] = priced
            # Depth 1 is always feasible (every stage's halo is
            # componentwise <= the union the tile was sized for)...
            assert 1 in sc, f"depth-1 chain infeasible on {work}"
            scored_by_kind[wk] = sc
        # §14 window-kind race: keep the modeled-cheapest layout (ties go
        # to the first listed — ring under "auto").  The ring can never
        # lose this race: its bands are subsets of the trapezoid's cones,
        # so every trapezoid-feasible depth is ring-feasible at identical
        # modeled traffic — the assert pins that dominance.
        window_kind = min(
            scored_by_kind,
            key=lambda wk: (
                min(t[0] for t in scored_by_kind[wk].values()),
                kinds.index(wk),
            ),
        )
        if wk_req == "auto" and len(scored_by_kind) > 1:
            assert window_kind == "ring", (
                f"trapezoid out-scored the ring on {work}: "
                f"{scored_by_kind}"
            )
        per_depth = by_kind[window_kind]
        scored = scored_by_kind[window_kind]
        chosen["wk"] = window_kind  # rebind the closures' default
        # ...but a heterogeneous chain prices launches with their own
        # halos, where the union-scored tile is not provably best — take
        # the legacy tile instead whenever it chains cheaper, preserving
        # planned <= legacy for every input.
        if legacy_priced is not None and (
            legacy_priced[0] < scored[1][0]
        ):
            per_depth[1] = legacy
            scored[1] = legacy_priced
        return _Survey(
            request=request,
            d=d,
            T=T,
            db=db,
            halo=halo,
            stage_halos=stage_halos,
            lattice=lattice,
            pad=pad,
            work=work,
            work_full=work_full,
            num_shards=num_shards,
            shard_axis=shard_axis,
            extras=extras,
            legacy=legacy,
            legacy_priced=legacy_priced,
            per_depth=per_depth,
            scored=scored,
            tiled=tiled,
            price_chain=price_chain,
            window_kind=window_kind,
            stage_dbs=stage_dbs,
        )

    def _freeze(
        self, sv: "_Survey", fused_depth: int, choice: TileChoice, priced
    ) -> StencilPlan:
        """Freeze one scored (tile, depth) candidate of a survey into a
        full :class:`StencilPlan`.  ``plan()`` freezes the modeled argmin;
        :meth:`candidates` freezes the runners-up too, so a frozen
        candidate's chain fields honestly describe *its own* cost (its
        ``traffic_vs_single_pass`` may exceed 1 — that is exactly the
        information the autotuner measures against)."""
        request, T, d, db = sv.request, sv.T, sv.d, sv.db
        halo, stage_halos = sv.halo, sv.stage_halos
        num_shards, shard_axis = sv.num_shards, sv.shard_axis
        traffic_total, lb_total, flops_total, rflops_total = priced
        single_total = sv.scored[1][0]
        depth_scores = tuple(
            (int(depth), int(tr), int(fs))
            for depth, (tr, _lb, fs, _fr) in sorted(sv.scored.items())
        )

        sweep = choice.sweep_axis
        h_s = 0 if sweep is None else halo[sweep][0] + halo[sweep][1]
        n_sweep = 1 if sweep is None else choice.grid[sweep]
        legacy_total = (
            sv.legacy_priced[0] if sv.legacy_priced is not None
            else T * sv.legacy.traffic_bytes
        )

        # -- §10 shard accounting: the scoring already ran on the worst
        # shard's column slab, so traffic_total IS the per-shard figure;
        # what remains is the cross-device boundary exchange.
        grid_full = tuple(
            -(-n // t) for n, t in zip(sv.work_full, choice.tile)
        )
        halo_exchange = 0
        if num_shards > 1:
            a = shard_axis
            # Each of the S-1 interior boundaries moves the launch's
            # shard-axis cone over the halo'd cross extents of the global
            # padded grid, once per launch of the chain and once per RHS
            # operand (the launcher exchanges every input block).
            if stage_halos is not None:
                launch_halos = [
                    chain_halo(stage_halos[i : i + fused_depth])
                    for i in range(0, T, fused_depth)
                ]
            else:
                launch_halos = [halo]
            p_rhs = max(len(request.offsets), 1)
            for li, cone in enumerate(launch_halos):
                ext = prod(
                    grid_full[i] * choice.tile[i] + cone[i][0] + cone[i][1]
                    for i in range(d)
                    if i != a
                )
                # §14: launch li > 0 exchanges the previous launch's output
                # — a stage-dtype array, not the request's input dtype.
                in_db = (
                    sv.stage_dbs[li * fused_depth - 1]
                    if sv.stage_dbs and li > 0 else db
                )
                halo_exchange += (
                    p_rhs * (num_shards - 1)
                    * (cone[a][0] + cone[a][1]) * ext * in_db
                )
        return StencilPlan(
            request=request,
            lattice=sv.lattice,
            pad=sv.pad,
            tile=choice.tile,
            sweep_axis=sweep,
            grid=grid_full,
            pipelined=bool(
                request.pipelined and sweep is not None
                and h_s > 0 and n_sweep > 1
            ),
            traffic_bytes=int(traffic_total),
            vmem_bytes=int(choice.vmem_bytes),
            surface_to_volume=float(choice.surface_to_volume),
            lower_bound_bytes=float(lb_total),
            efficiency=float(min(lb_total / max(traffic_total, 1), 1.0)),
            legacy_tile=sv.legacy.tile,
            legacy_sweep_axis=sv.legacy.sweep_axis,
            legacy_traffic_bytes=int(legacy_total),
            time_steps=T,
            fused_depth=int(fused_depth),
            single_pass_traffic_bytes=int(single_total),
            modeled_flops=int(flops_total),
            recompute_flops=int(rflops_total),
            depth_scores=depth_scores,
            num_shards=int(num_shards),
            shard_axis=shard_axis,
            window_kind=sv.window_kind,
            per_shard_traffic_bytes=int(traffic_total),
            halo_exchange_bytes=int(halo_exchange),
        )

    # -- optional exact validation ----------------------------------------

    def validate(self, plan: StencilPlan, max_points: int = 400_000) -> dict:
        """Cache-simulate the padded vs. original grid (natural order) on
        the request's hardware geometry — the §2 exact model as a check on
        the pad decision.  Only meaningful when the request has a geometry;
        large grids are truncated to a thin slab along the last dim."""
        if plan.request.geometry is None:
            return {"validated": False, "reason": "no cache geometry"}
        from repro.core.cache_fitting import access_stream, natural_order, star_stencil
        from repro.core.cache_sim import simulate_misses

        geom = CacheGeometry(*plan.request.geometry)
        halo = halo_from_offsets(plan.request.offsets, len(plan.request.shape))
        r = max(max(lo, hi) for lo, hi in halo)
        r = max(r, 1)
        K = star_stencil(len(plan.request.shape), r)

        def slab(dims):
            dims = tuple(dims)
            while prod(dims) > max_points and dims[-1] > 4 * r + 4:
                dims = dims[:-1] + (max(dims[-1] // 2, 4 * r + 4),)
            return dims

        out = {"validated": True, "geometry": plan.request.geometry}
        for name, dims in (
            ("original", plan.request.shape),
            ("padded", plan.pad.padded_shape),
        ):
            dims = slab(dims)
            pts = prod(max(n - 2 * r, 1) for n in dims)
            order = natural_order(dims, r)
            if len(order) == 0:
                out[name] = {"dims": dims, "miss_per_point": float("nan")}
                continue
            m = simulate_misses(access_stream(dims, order, K), geom)
            out[name] = {"dims": dims, "miss_per_point": m / pts}
        if plan.pad.nonzero:
            o = out["original"]["miss_per_point"]
            p = out["padded"]["miss_per_point"]
            out["miss_reduction_x"] = o / p if p else float("inf")
        return out


_DEFAULT: Planner | None = None


def default_planner() -> Planner:
    """Process-wide planner with the persistent default cache — what the
    kernel layer consults when no explicit plan is passed."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = Planner()
    return _DEFAULT


def plan_stencil(shape, offsets, **kw) -> StencilPlan:
    """Convenience: plan one stencil with the default planner.  ``offsets``
    may be a single (s, d) array or a per-RHS sequence."""
    return default_planner().plan(shape=shape, offsets=offsets, **kw)
