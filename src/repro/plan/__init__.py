"""Stencil plan compiler: lattice → padding → tiling, compiled once,
cached forever.

The paper's pipeline (interference lattice → LLL → unfavorable-grid
detection → padding → surface-to-volume tiling) lives here as a single
``Planner.plan()`` call producing a frozen :class:`StencilPlan`, memoized
by a content-addressed persistent :class:`PlanCache`.  Consumers —
``kernels.stencil``, ``kernels.conv1d``, ``models.ssm``, the benchmark
harness — treat the plan as the single source of truth for padding, tile
shape, sweep axis and pipelining.

``python -m repro.plan.explain SHAPE`` prints a human-readable plan
report (see :mod:`repro.plan.explain`); ``python -m repro.autotune
SHAPE`` races the top-k candidate plans on the live backend and persists
the measured winner in the :class:`TunedPlanDB` (DESIGN.md §11 — a
Planner built with ``tuned_db=`` then prefers measured winners).
"""

from .cache import PlanCache, default_cache_dir  # noqa: F401
from .planner import Planner, default_planner, plan_stencil  # noqa: F401
from .tune import AutoTuner, default_tuner, resolve_tuner  # noqa: F401
from .tunedb import (  # noqa: F401
    TUNEDB_SCHEMA,
    CandidateTiming,
    TunedPlanDB,
    TuneRecord,
)
from .schema import (  # noqa: F401
    PLANNER_VERSION,
    LatticeReport,
    PadPlan,
    PlanMismatchError,
    PlanRequest,
    StageSpec,
    StencilPlan,
    validate_plan_call,
)

__all__ = [
    "PLANNER_VERSION",
    "TUNEDB_SCHEMA",
    "AutoTuner",
    "CandidateTiming",
    "LatticeReport",
    "PadPlan",
    "PlanCache",
    "PlanMismatchError",
    "PlanRequest",
    "Planner",
    "StageSpec",
    "StencilPlan",
    "TunedPlanDB",
    "TuneRecord",
    "default_cache_dir",
    "default_planner",
    "default_tuner",
    "plan_stencil",
    "resolve_tuner",
    "validate_plan_call",
]
