"""Unified matrix -> ExecutionPlan compiler (the paper's synthesis step).

One offline lowering of a :class:`repro.core.sparse.FixedMatrix` produces
every static artifact the kernels, the serve engine and the cost reports
consume: gathered nonzero tiles, per-column reduction term lists (with
block- and plane-level culling), whole-plane masks, VMEM-banded rollout
layouts, the sorted BCSR tile list, and the FPGA cost model attached to
the exact decomposed structure.  :mod:`repro.plan.schedule` picks the
lowering a plan runs in from the plan and the platform.
"""

from repro.plan.plan import (
    DEFAULT_VMEM_BUDGET,
    BandedRollout,
    BcsrLayout,
    ExecutionPlan,
    PlanStats,
    RolloutBand,
    plan_cache_stats,
    plan_for,
)
from repro.plan.schedule import Schedule, choose_schedule
from repro.plan.specialize import (
    DEFAULT_BATCH_TILE,
    RolloutProgram,
    specialize_rollout,
    specialize_summary,
)

__all__ = [
    "DEFAULT_BATCH_TILE",
    "DEFAULT_VMEM_BUDGET",
    "BandedRollout",
    "BcsrLayout",
    "ExecutionPlan",
    "PlanStats",
    "RolloutBand",
    "RolloutProgram",
    "Schedule",
    "choose_schedule",
    "plan_cache_stats",
    "plan_for",
    "specialize_rollout",
    "specialize_summary",
]
