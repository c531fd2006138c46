"""Measured-cost autotune loop (command line: ``python -m repro.autotune``).

The paper validates its miss model by *measuring* (Fig. 5: predicted vs
observed misses on R10000); the planner so far trusts the §4 analytic
model alone.  This module closes the loop (DESIGN.md §11):

1. ask the :class:`~repro.plan.planner.Planner` for the top-``k``
   candidate plans by modeled cost (``Planner.candidates`` — the scored
   tile/depth/shard enumeration behind ``plan()``'s argmin);
2. time every candidate on the live backend with the
   :mod:`repro.runtime.timing` harness (jit warm-up excluded,
   ``block_until_ready``, median-of-n with IQR);
3. record wall-clock, achieved bandwidth, and the model-vs-measured
   ratio per candidate into the persistent
   :class:`~repro.plan.tunedb.TunedPlanDB` (same sha256 request keys as
   the PlanCache, additionally keyed by backend fingerprint);
4. keep the measured winner.  The analytic choice is always candidate 0
   and always raced, so the ``never_slower`` gate — measured winner time
   ≤ measured analytic time — holds by construction and is asserted at
   tune time.

Beyond the geometry candidates, the race covers the §14/§15 execution
variants (DESIGN.md §15): the *window flip* (the other ring/trapezoid
frontier layout, bit-wise neutral, eligible to win outright) and the
*storage-dtype variants* (intermediate stages stored bf16 or
int8-quantized).  Dtype variants change the computed values, so their
rows are **advisory** — recorded in the TuneDB for the planner's §14
pricing to learn from, never served as the winner of the request they
did not answer.

A Planner constructed with ``tuned_db=`` (or an :class:`AutoTuner` used
directly, or ``stencil_iterate(..., tune=True)``) then *prefers* the
measured winner on a warm DB hit — sub-ms, no re-measurement — and falls
back to the analytic choice unchanged on a miss.

The tuner generates its own input arrays (the timing depends on shapes
and dtypes, never on values) and launches each candidate as a program
through the *runner* its caller passes to :meth:`AutoTuner.tune` or
:meth:`AutoTuner.plan` (the call entry passes its own
:func:`repro.entry.run`), with ``plan=candidate`` explicitly, so tuning
never recurses into tuning and the plan layer never imports the entry.
The command line lives above the entry.

jax is imported lazily: importing ``repro.plan`` must never fix the
process's device topology before a caller (conftest, benchmarks, the
CLI) has set ``XLA_FLAGS``.
"""

from __future__ import annotations

from datetime import datetime, timezone

import numpy as np

from .. import obs
from .planner import Planner, default_planner
from .schema import PlanRequest, StencilPlan
from .tunedb import CandidateTiming, TunedPlanDB, TuneRecord

__all__ = [
    "AutoTuner",
    "backend_fingerprint",
    "default_tuner",
    "format_record",
    "resolve_tuner",
]


def backend_fingerprint(interpret: bool | None = None) -> str:
    """Identity of what a measurement means here: the device fingerprint
    (backend:kind:xN:jax-version) plus whether Pallas kernels compile or
    interpret — interpret-mode CPU numbers must never be served to a
    compiled-TPU process, even on the same host."""
    from repro.kernels._backend import resolve_interpret
    from repro.runtime.timing import device_fingerprint

    return (
        f"{device_fingerprint()}|interpret="
        f"{bool(resolve_interpret(interpret))}"
    )


def _spearman(xs, ys) -> float:
    """Spearman rank correlation (average ranks on ties): how well the
    modeled-bytes *ordering* predicts the measured-time ordering — the
    per-request analogue of the paper's Fig. 5 model validation."""
    n = len(xs)
    if n < 2:
        return 0.0

    def ranks(v):
        v = np.asarray(v, dtype=float)
        order = np.argsort(v, kind="mergesort")
        r = np.empty(n, dtype=float)
        r[order] = np.arange(n, dtype=float)
        for val in np.unique(v):
            m = v == val
            r[m] = r[m].mean()
        return r

    rx, ry = ranks(xs), ranks(ys)
    sx, sy = rx - rx.mean(), ry - ry.mean()
    denom = float(np.sqrt((sx**2).sum() * (sy**2).sum()))
    if denom == 0.0:
        return 0.0
    return float((sx * sy).sum() / denom)


def _modeled_bytes(plan: StencilPlan) -> int:
    """A candidate's total modeled HBM traffic: the (per-shard) chain
    bytes across all shards plus the cross-device halo exchange."""
    return (
        plan.per_shard_traffic_bytes * plan.num_shards
        + plan.halo_exchange_bytes
    )


class AutoTuner:
    """Races candidate plans on the live backend, keeps measured winners.

    ``tune()`` measures one request and records a :class:`TuneRecord`;
    ``plan()`` is the drop-in planning entry point the call entry's
    ``tune=`` knob routes through — warm DB hit returns the measured
    winner without re-measurement, miss tunes first.  ``force=True``
    re-measures even on a warm hit (fresh numbers after a driver or
    clock change).  Both take ``runner``, which runs one candidate as
    ``runner(program, arrays, plan=, interpret=)``: :func:`repro.entry.run`.
    """

    def __init__(
        self,
        db: TunedPlanDB | None = None,
        planner: Planner | None = None,
        k: int = 4,
        reps: int = 5,
        warmup: int = 1,
        interpret: bool | None = None,
        force: bool = False,
    ):
        self.db = db if db is not None else TunedPlanDB()
        self.planner = planner if planner is not None else default_planner()
        self.k = int(k)
        self.reps = int(reps)
        self.warmup = int(warmup)
        self.interpret = interpret
        self.force = bool(force)
        self.last_plan_tuned: bool = False  # warm hit (vs fresh measurement)?
        self.last_record: TuneRecord | None = None

    # -- launching one candidate ------------------------------------------

    def _launch_fn(self, request: PlanRequest, plan: StencilPlan, run,
                   quants=None):
        """A zero-arg closure running the request's whole computation under
        ``plan`` through the runner ``run`` — the thing
        :func:`repro.runtime.timing.measure` times.
        Inputs are synthesized here (timing depends on shape/dtype, not
        values); weights default to uniform 1/s so deep chains stay
        bounded.  ``plan=plan`` pins tile/sweep/depth/shard explicitly, so
        the launch never consults a planner (and never re-tunes).

        Stage chains launch as explicit §13 programs so the request's
        boundary conditions and per-stage storage dtypes survive into the
        launch (a plan for the bf16/robin chain must race the bf16/robin
        chain — ``validate_plan_call`` rejects anything else); ``quants``
        attaches per-stage §15 ``(scale, zero_point)`` int8 quantization
        for the dtype-variant rows (execution params, not plan keys)."""
        import jax.numpy as jnp

        from repro import ir

        dtype = {2: jnp.bfloat16, 4: jnp.float32, 8: jnp.float64}.get(
            request.dtype_bytes, jnp.float32
        )
        rng = np.random.default_rng(0)

        def mk():
            return jnp.asarray(
                rng.standard_normal(request.shape), dtype=dtype
            )

        interpret = self.interpret
        if request.stages:
            stage_list = [
                (
                    np.asarray(st.offsets, dtype=np.int64),
                    st.weights if st.weights is not None
                    else (1.0 / len(st.offsets),) * len(st.offsets),
                )
                for st in request.stages
            ]
            dts = tuple(st.dtype for st in request.stages)
            prog = ir.chain_program(
                stage_list, len(request.shape),
                boundary=(
                    list(request.bcs)
                    if any(bc is not None for bc in request.bcs) else None
                ),
                dtypes=dts if any(dt is not None for dt in dts) else None,
                quants=quants,
            )
            us = (mk(),)
            return lambda: run(prog, us, plan=plan, interpret=interpret)
        offsets_list = [
            np.asarray(g, dtype=np.int64) for g in request.offsets
        ]
        weights_list = [(1.0 / len(g),) * len(g) for g in offsets_list]
        d = len(request.shape)
        if len(offsets_list) == 1:
            prog = ir.stencil_program(offsets_list[0], weights_list[0],
                                      request.time_steps, d=d)
        else:
            prog = ir.rhs_program(offsets_list, weights_list, d=d)
        us = tuple(mk() for _ in offsets_list)
        return lambda: run(prog, us, plan=plan, interpret=interpret)

    # -- the §15 variant survey --------------------------------------------

    def _remake(self, request: PlanRequest, dtypes=None,
                window_kind=None) -> PlanRequest:
        """The same planning problem with the stage dtypes or the frontier
        window rewritten — the variant rows' launch requests."""
        return PlanRequest.make(
            shape=request.shape,
            stages=request.stages,
            dtypes=dtypes,
            bcs=request.bcs or None,
            dtype_bytes=request.dtype_bytes,
            vmem_budget=request.vmem_budget,
            n_operands=request.n_operands,
            geometry=request.geometry,
            aligned=request.aligned,
            pipelined=request.pipelined,
            strategy=request.strategy,
            max_pad=request.max_pad,
            num_shards=request.num_shards,
            mesh_axis=request.mesh_axis,
            window_kind=(
                window_kind if window_kind is not None
                else request.window_kind
            ),
        )

    # Intermediate-stage int8 scale for the advisory race: inputs are unit
    # normals and weights uniform 1/s, so stage values sit well inside
    # ±128·0.05.  Values never change the timing; any fixed scale does.
    _RACE_QUANT = (0.05, 0)

    def _variants(self, request: PlanRequest, plan0: StencilPlan):
        """Entries beyond the geometry candidates (DESIGN.md §15):

        * the **window flip** — the same request re-planned under the
          other §14 frontier layout.  Ring and trapezoid launches are
          bit-wise identical, so the flip races *for the win*
          (``advisory=False``); the served plan keeps the original
          request (same cache key), only ``window_kind`` differs.
        * **storage-dtype variants** — the chain with its intermediate
          stages stored bf16 / int8-quantized.  These change the computed
          values, so they race **advisory-only**: their rows record what
          narrower frontiers would buy, but they can never be served as
          the winner of the f32 request they did not answer.

        Returns ``(plan, launch_request, quants, advisory)`` tuples.
        """
        from dataclasses import replace

        out = []
        T = len(request.stages)
        if T >= 2 and request.window_kind == "auto" \
                and plan0.fused_depth >= 2:
            other = (
                "ring" if plan0.window_kind == "trapezoid" else "trapezoid"
            )
            try:
                wk_plan = self.planner._analytic(
                    self._remake(request, window_kind=other)
                )
            except ValueError:
                wk_plan = None  # no tile fits this layout's frontier cost
            if wk_plan is not None and wk_plan.window_kind != \
                    plan0.window_kind:
                out.append(
                    (replace(wk_plan, request=request), request, None, False)
                )
        if T >= 2 and all(st.dtype is None for st in request.stages):
            for name in ("bfloat16", "int8"):
                dts = (name,) * (T - 1) + (None,)
                qns = (
                    (self._RACE_QUANT,) * (T - 1) + (None,)
                    if name == "int8" else None
                )
                try:
                    var_req = self._remake(request, dtypes=dts)
                    var_plan = self.planner._analytic(var_req)
                except ValueError:
                    continue  # e.g. unsupported dtype for this engine
                out.append((var_plan, var_req, qns, True))
        return out

    # -- the tune pass -----------------------------------------------------

    def tune(
        self, request: PlanRequest | None = None, /, *, runner, **kw
    ) -> TuneRecord:
        """Measure the top-k candidates of one request and persist the
        result.  Candidate 0 is the planner's analytic argmin; the winner
        is the measured argmin (ties break toward the analytic choice),
        so ``never_slower`` holds by construction."""
        from repro.runtime.timing import measure

        if request is None:
            kw.setdefault("strategy", self.planner.strategy)
            request = PlanRequest.make(**kw)
        key = request.cache_key()
        race_sp = None
        if obs.enabled():
            # Rank = candidate index: the planner returns them ordered by
            # modeled cost, so rank 0 is the analytic argmin.
            race_sp = obs.span("tune_race", plan_key=key).__enter__()
        try:
            cands = self.planner.candidates(request, k=self.k)
            entries = [(plan, request, None, False) for plan in cands]
            entries += self._variants(request, cands[0])
            timed = []
            for rank, (plan, lreq, qns, advisory) in enumerate(entries):
                fn = self._launch_fn(lreq, plan, runner, quants=qns)
                if obs.enabled():
                    with obs.span(
                        "tune_candidate", plan_key=key, rank=rank,
                        tile=list(plan.tile), fused_depth=plan.fused_depth,
                        window_kind=plan.window_kind, advisory=advisory,
                        modeled_bytes=_modeled_bytes(plan),
                    ) as csp:
                        t = measure(fn, reps=self.reps, warmup=self.warmup)
                        csp.set(median_ms=t.median_s * 1e3)
                else:
                    t = measure(fn, reps=self.reps, warmup=self.warmup)
                timed.append((plan, t))
        except BaseException:
            if race_sp is not None:
                race_sp.set(outcome="error")
                race_sp.__exit__(None, None, None)
            raise
        base_t = max(timed[0][1].median_s, 1e-12)
        base_m = max(_modeled_bytes(entries[0][0]), 1)
        rows = []
        for (plan, lreq, _, advisory), (_, t) in zip(entries, timed):
            m = _modeled_bytes(plan)
            med = max(t.median_s, 1e-12)
            row_dts = tuple(st.dtype for st in lreq.stages)
            rows.append(CandidateTiming(
                tile=plan.tile,
                sweep_axis=plan.sweep_axis,
                fused_depth=plan.fused_depth,
                shard_axis=plan.shard_axis,
                modeled_bytes=m,
                median_s=t.median_s,
                iqr_s=t.iqr_s,
                reps=t.reps,
                achieved_gbps=m / med / 1e9,
                model_measured_ratio=(m / base_m) / (med / base_t),
                window_kind=plan.window_kind,
                stage_dtypes=(
                    row_dts if any(dt is not None for dt in row_dts)
                    else None
                ),
                advisory=advisory,
            ))
        # Winner eligibility (§15): only semantics-preserving rows — the
        # geometry candidates and the bit-wise-neutral window flip — may
        # win; dtype-variant rows are information, not answers.
        winner = min(
            (i for i in range(len(rows)) if not rows[i].advisory),
            key=lambda i: (rows[i].median_s, i),
        )
        never_slower = rows[winner].median_s <= rows[0].median_s
        # The analytic plan is in the raced set, so the measured argmin
        # cannot lose to it — this gate failing means the harness itself
        # is broken (e.g. a non-blocking launch), not a bad model.
        assert never_slower, (
            f"tuned winner slower than analytic: "
            f"{rows[winner].median_s} > {rows[0].median_s}"
        )
        if race_sp is not None:
            race_sp.set(
                candidates=len(rows), winner_rank=winner,
                source="measured", never_slower=never_slower,
            )
            race_sp.__exit__(None, None, None)
        rec = TuneRecord(
            key=key,
            fingerprint=backend_fingerprint(self.interpret),
            candidates=tuple(rows),
            winner=winner,
            analytic=0,
            never_slower=never_slower,
            speedup_vs_analytic=base_t / max(rows[winner].median_s, 1e-12),
            rank_correlation=_spearman(
                [r.modeled_bytes for r in rows],
                [r.median_s for r in rows],
            ),
            winner_plan=timed[winner][0],
            tuned_at=datetime.now(timezone.utc).isoformat(
                timespec="seconds"
            ),
        )
        self.db.put(rec)
        self.last_record = rec
        return rec

    def plan(
        self, request: PlanRequest | None = None, /, *, runner, **kw
    ) -> StencilPlan:
        """Planning entry point with measured preference: warm DB hit →
        the measured winner (no re-measurement); miss → tune through
        ``runner``, then the winner.  Otherwise signature-compatible with
        ``Planner.plan``, which is what lets a call's ``tune=`` swap it
        in."""
        if request is None:
            kw.setdefault("strategy", self.planner.strategy)
            request = PlanRequest.make(**kw)
        if obs.enabled():
            with obs.span("plan", key=request.cache_key(),
                          source="autotuner") as sp:
                plan = self._plan_resolve(request, runner)
                sp.set(
                    tuned=self.last_plan_tuned,
                    tile=list(plan.tile),
                    fused_depth=plan.fused_depth,
                    num_shards=plan.num_shards,
                )
            return plan
        return self._plan_resolve(request, runner)

    def _plan_resolve(self, request: PlanRequest, runner) -> StencilPlan:
        rec = None
        if not self.force:
            rec = self.db.get(
                request.cache_key(), backend_fingerprint(self.interpret)
            )
        self.last_plan_tuned = rec is not None
        if rec is None:
            rec = self.tune(request, runner=runner)
        self.last_record = rec
        return rec.winner_plan


_DEFAULT: AutoTuner | None = None


def default_tuner() -> AutoTuner:
    """Process-wide tuner over the default planner and persistent DB —
    what a call's ``tune=True`` resolves to."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = AutoTuner()
    return _DEFAULT


def resolve_tuner(tune) -> AutoTuner | None:
    """The call entry's ``tune=`` knob: ``None``/``False`` → no tuning,
    ``True`` → the default tuner, an :class:`AutoTuner` → itself."""
    if tune is None or tune is False:
        return None
    if tune is True:
        return default_tuner()
    return tune


# -- reporting -------------------------------------------------------------


def _fmt_t(s: float) -> str:
    if s >= 1.0:
        return f"{s:.3f} s"
    if s >= 1e-3:
        return f"{s * 1e3:.3f} ms"
    return f"{s * 1e6:.1f} us"


def format_record(rec: TuneRecord) -> str:
    """The measured-vs-modeled table of one tune record (also what
    ``repro.plan.explain --tuned`` prints for a warm entry)."""
    lines = [
        f"tuned entry {rec.key[:16]}…  backend {rec.fingerprint}",
        f"  tuned at {rec.tuned_at}  (schema v{rec.schema}, "
        f"planner v{rec.planner_version})",
        "  candidates (measured on the live backend):",
        "    #  tile              sweep depth shard window     "
        "dtypes   modeled MiB  measured      iqr        GB/s  model/meas",
    ]
    for i, c in enumerate(rec.candidates):
        mark = (
            "  <-- winner" if i == rec.winner else
            "  (analytic)" if i == rec.analytic else
            "  (advisory)" if c.advisory else ""
        )
        dts = "-"
        if c.stage_dtypes:
            named = {dt for dt in c.stage_dtypes if dt is not None}
            dts = "/".join(sorted(named)) or "-"
        lines.append(
            f"    {i}  {str(c.tile):<17} {str(c.sweep_axis):>5} "
            f"{c.fused_depth:>5} {str(c.shard_axis):>5} "
            f"{str(c.window_kind):>9} {dts:>8} "
            f"{c.modeled_bytes / (1 << 20):>12.2f}  "
            f"{_fmt_t(c.median_s):>9}  {_fmt_t(c.iqr_s):>9}  "
            f"{c.achieved_gbps:>9.3f}  {c.model_measured_ratio:>9.3f}"
            f"{mark}"
        )
    lines += [
        f"  winner: candidate {rec.winner} "
        f"({rec.speedup_vs_analytic:.3f}x vs analytic; never_slower="
        f"{rec.never_slower})",
        f"  rank correlation (modeled bytes vs measured time): "
        f"{rec.rank_correlation:+.3f} over {len(rec.candidates)} candidates",
    ]
    return "\n".join(lines)
