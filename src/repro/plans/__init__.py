"""Phase-aware parallel plans: search -> save -> load -> execute.

This package owns the strategy->execution seam.  A
:class:`~repro.plans.parallel_plan.ParallelPlan` carries one
:class:`~repro.models.plan.ModelPlan` per phase (``train`` / ``prefill``
/ ``decode``), the mesh it was searched for and provenance metadata, and
round-trips through a versioned JSON schema.  The sharding realization
(:func:`param_pspecs` & friends, formerly ``repro.train.shardings``)
lives here too, so ``make_train_step``, ``make_serve_fns`` and the
``ServeEngine`` all consume the same artifact through one code path.
"""

from .parallel_plan import (
    PHASES,
    SCHEMA_VERSION,
    ParallelPlan,
    PlanArchMismatchError,
    PlanError,
    PlanFormatError,
    arch_fingerprint,
    as_model_plan,
    model_plan_from_json,
    model_plan_to_json,
)
from .search import (
    STRATEGIES,
    baseline_phase_plan,
    build_parallel_plan,
    resolve_plan,
    search_phase_plan,
)
from .shardings import (
    adamw_shardings,
    batch_pspecs,
    cache_pspecs,
    dominant_unit_plan,
    param_pspecs,
    to_shardings,
)

__all__ = [
    "PHASES", "SCHEMA_VERSION", "STRATEGIES", "ParallelPlan",
    "PlanArchMismatchError", "PlanError", "PlanFormatError",
    "adamw_shardings", "arch_fingerprint", "as_model_plan", "baseline_phase_plan",
    "batch_pspecs", "build_parallel_plan", "cache_pspecs",
    "dominant_unit_plan", "model_plan_from_json", "model_plan_to_json",
    "param_pspecs", "resolve_plan", "search_phase_plan", "to_shardings",
]
