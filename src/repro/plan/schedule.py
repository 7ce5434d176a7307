"""Which lowering runs the recurrence: one rule on the plan and the platform.

Where Pallas runs compiled (the TPU) and the folded tiles of the whole
program fit a kernel's VMEM budget, the Pallas kernel holds them resident;
int8 programs that do not fit take XLA's dense fold, fp32 ones the Pallas
kernel's pipelined bands.  Where Pallas would run interpreted (the CPU)
the rollout is XLA's.  int8 folds every digit plane into the quantized
tile (crossover 0); fp32 keeps the block's default crossover.  Every
schedule is bit-identical to every other (int8 accumulates in exact
int32, fp32 keeps ascending-row order), so the rule decides speed only.
"""

from __future__ import annotations

import dataclasses

from repro.kernels.platform import interpret_for
from repro.plan.plan import DEFAULT_VMEM_BUDGET, ExecutionPlan
from repro.plan.specialize import default_crossover, specialize_summary

__all__ = ["BATCH_TILE", "Schedule", "choose_schedule"]

# Batch tile of every chosen schedule: the tile the chip's cells run (the
# Pallas kernel tiles esn1024's pool by it; XLA's scan takes the pool whole)
BATCH_TILE = 8


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One rollout schedule: the engine knobs the rule fills in."""

    mode: str                  # "fp32" | "int8" (kernel mode)
    backend: str               # "xla" | "pallas"
    vmem_budget: int | None    # None holds every folded tile resident
    crossover: int
    batch_tile_max: int

    def describe(self) -> str:
        budget = "none" if self.vmem_budget is None else str(self.vmem_budget)
        return (f"{self.backend} budget={budget} "
                f"crossover={self.crossover} tile={self.batch_tile_max}")


def choose_schedule(plan: ExecutionPlan, mode: str,
                    platform: str) -> Schedule:
    """The schedule ``backend="auto"`` engines run ``plan`` in ``mode`` with
    on ``platform`` (``jax.default_backend()``)."""
    assert mode in ("fp32", "int8"), mode
    crossover = 0 if mode == "int8" else default_crossover(plan.block)
    if interpret_for(platform):
        return Schedule(mode, "xla", None, crossover, BATCH_TILE)
    resident = specialize_summary(plan, mode, vmem_budget=None,
                                  crossover=crossover,
                                  batch_tile_max=BATCH_TILE)["resident_bytes"]
    fits = resident <= DEFAULT_VMEM_BUDGET
    if mode == "int8":
        return Schedule(mode, "pallas" if fits else "xla", None, crossover,
                        BATCH_TILE)
    return Schedule(mode, "pallas", None if fits else DEFAULT_VMEM_BUDGET,
                    crossover, BATCH_TILE)
