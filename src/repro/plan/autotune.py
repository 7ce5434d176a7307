"""Measured-cost plan autotuning: close the loop on the cost model.

PR 5's specialization pass picks its schedule — weight-residency regime,
shift-add crossover, VMEM band budget, batch tile — from fixed heuristics,
and ``backend="auto"`` silently always means XLA.  The paper's actual
contribution is an *extensible cost model driving the implementation*:
predicted cost picks the design point, measurement calibrates the
predictor.  This module is that loop for the rollout schedule space:

  predict  — enumerate every valid candidate schedule (budgets x
             crossovers x batch tiles x backends; regime falls out of the
             budget) and price each one with the calibrated linear model
             in :mod:`repro.core.costmodel`, using counts-only
             ``specialize_summary`` analysis — no tile data, no compile.
  prune    — keep the top-K predicted schedules (the default-heuristic
             schedule is ALWAYS kept, so the measured winner can never
             lose to the default on the tuner's own trials).
  measure  — build real engines through the ``specialize_rollout`` ->
             ``RolloutProgram`` path and time the actual rollout,
             best-of-reps.
  cache    — the winner lands on the plan (``plan.describe()`` reports
             it), in the process-wide :class:`ScheduleCache`, and — via
             ``autotune_cache_save`` — in a JSON file keyed on plan
             fingerprint + hardware fingerprint, so serve startup after
             ``autotune_cache_load`` pays zero re-tuning.

Every candidate schedule is bit-identical to every other (the programs
differ only in term grouping and residency; int8 accumulates in exact
int32, fp32 keeps ascending-row order — property-tested), so tuning is
purely a throughput decision and can never change served results.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import time

import numpy as np

from repro import obs
from repro.core import costmodel
from repro.plan.plan import DEFAULT_VMEM_BUDGET, ExecutionPlan
from repro.plan.specialize import DEFAULT_BATCH_TILE, default_crossover, \
    specialize_summary

__all__ = [
    "BACKENDS",
    "Schedule",
    "TunedSchedule",
    "ScheduleCache",
    "default_schedule",
    "candidate_schedules",
    "predict_cost",
    "plan_fingerprint",
    "hardware_fingerprint",
    "resolve_schedule",
    "resolve_backend",
    "autotune_rollout",
    "autotune_cache",
    "autotune_cache_load",
    "autotune_cache_save",
]

BACKENDS = ("xla", "pallas")

# VMEM the Pallas kernel's shift-add unroll holds per output lane and batch
# row: Mosaic keeps each lane's summed (b_tile, 1) column live, 140-150 B
# per lane-row on a v5e (compiled for the chip: degree-3 programs of 1280,
# 1920 and 5000 nodes at 8- and 16-row tiles)
UNROLL_LANE_ROW_BYTES = 144

# Default tuning shape: small enough to measure in milliseconds, big
# enough that the regime/backend choice it makes transfers to serve-sized
# batches (the cache key buckets the batch axis, so other shapes re-tune).
TUNE_BATCH = 8
TUNE_STEPS = 8


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One point in the rollout schedule space.

    The regime (resident vs pipelined) is not a free axis: it falls out of
    ``vmem_budget`` deterministically (``None`` forces resident; a finite
    budget pipelines iff the folded tiles overflow it), so enumerating
    budgets enumerates regimes.
    """

    mode: str                  # "fp32" | "int8" (kernel mode)
    backend: str               # "xla" | "pallas"
    vmem_budget: int | None
    crossover: int
    batch_tile_max: int

    def key(self) -> tuple:
        return (self.mode, self.backend, self.vmem_budget, self.crossover,
                self.batch_tile_max)

    def sort_key(self) -> tuple:
        """Total order for deterministic tie-breaking (``None`` budget —
        forced resident — sorts as -1, below every finite budget)."""
        return (self.mode, self.backend,
                -1 if self.vmem_budget is None else self.vmem_budget,
                self.crossover, self.batch_tile_max)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Schedule":
        return cls(mode=d["mode"], backend=d["backend"],
                   vmem_budget=d["vmem_budget"],
                   crossover=int(d["crossover"]),
                   batch_tile_max=int(d["batch_tile_max"]))

    def describe(self) -> str:
        budget = "none" if self.vmem_budget is None else str(self.vmem_budget)
        return (f"{self.backend} budget={budget} "
                f"crossover={self.crossover} tile={self.batch_tile_max}")


def default_schedule(plan: ExecutionPlan, mode: str,
                     backend: str = "xla") -> Schedule:
    """The PR-5 fixed-heuristic schedule — the tuner's reference point and
    the fallback when tuning is disabled or impossible."""
    return Schedule(mode=mode, backend=backend,
                    vmem_budget=DEFAULT_VMEM_BUDGET,
                    crossover=default_crossover(plan.block),
                    batch_tile_max=DEFAULT_BATCH_TILE)


@dataclasses.dataclass(frozen=True)
class TunedSchedule:
    """A tuning decision: the chosen schedule plus the evidence for it.

    ``source`` is ``"measured"`` (full predict -> prune -> measure loop),
    ``"predicted"`` (analytic model only — what engine construction does
    on a cache miss, so startup never blocks on wall-clock measurement),
    or ``"cache"`` (replayed from the persisted JSON cache).  ``trials``
    records every measured candidate as ``(schedule_dict, predicted_s,
    measured_s)`` — the calibration rows ``fit_rollout_cost`` consumes.
    """

    schedule: Schedule
    batch: int
    steps: int
    predicted_s: float
    measured_s: float | None = None
    default_predicted_s: float | None = None
    default_measured_s: float | None = None
    source: str = "predicted"
    n_candidates: int = 0
    trials: tuple = ()

    def as_dict(self) -> dict:
        return {
            "schedule": self.schedule.as_dict(),
            "batch": self.batch, "steps": self.steps,
            "predicted_s": self.predicted_s,
            "measured_s": self.measured_s,
            "default_predicted_s": self.default_predicted_s,
            "default_measured_s": self.default_measured_s,
            "source": self.source, "n_candidates": self.n_candidates,
            "trials": [{"schedule": s, "predicted_s": p, "measured_s": m}
                       for s, p, m in self.trials],
        }

    @classmethod
    def from_dict(cls, d: dict) -> "TunedSchedule":
        return cls(
            schedule=Schedule.from_dict(d["schedule"]),
            batch=int(d["batch"]), steps=int(d["steps"]),
            predicted_s=float(d["predicted_s"]),
            measured_s=d.get("measured_s"),
            default_predicted_s=d.get("default_predicted_s"),
            default_measured_s=d.get("default_measured_s"),
            source=d.get("source", "cache"),
            n_candidates=int(d.get("n_candidates", 0)),
            trials=tuple((t["schedule"], t["predicted_s"], t["measured_s"])
                         for t in d.get("trials", ())))

    def describe(self) -> str:
        meas = (f"{self.measured_s * 1e3:.3f} ms measured"
                if self.measured_s is not None else "predict-only")
        return (f"{self.schedule.describe()} "
                f"({self.predicted_s * 1e3:.3f} ms predicted, {meas}, "
                f"{self.source} over {self.n_candidates} candidates)")


# -- fingerprints ------------------------------------------------------------
def plan_fingerprint(plan: ExecutionPlan) -> str:
    """Stable digest of the structure the schedule space depends on.

    Two matrices with the same block sparsity pattern, digit mode and
    set-digit count have identical schedule spaces and near-identical
    costs, so they share a cache entry — a registry republishing a
    same-shaped matrix reuses the tuning.  Uses ``fm.ones`` (already
    computed at matrix compile) rather than ``plan.stats`` so fp32-only
    consumers never pay for the integer lowering just to be fingerprinted.
    """
    h = hashlib.sha1()
    for part in (plan.shape, plan.block, plan.mode, plan.weight_bits,
                 plan.blocks_nnz, plan._fm.ones):
        h.update(repr(part).encode())
    h.update(np.ascontiguousarray(plan.block_rows).tobytes())
    h.update(np.ascontiguousarray(plan.block_cols).tobytes())
    return h.hexdigest()[:16]


def hardware_fingerprint() -> str:
    """Device identity the measurements are valid for — a persisted cache
    recorded on one machine never silently serves another."""
    import jax
    dev = jax.devices()[0]
    kind = str(getattr(dev, "device_kind", dev.platform)).replace(" ", "_")
    return f"{jax.default_backend()}:{kind}x{jax.device_count()}"


def _batch_bucket(batch: int) -> int:
    """Round the batch up to a power of two: one cache entry per regime of
    batch sizes, not per exact batch."""
    return 1 << max(0, int(batch) - 1).bit_length()


# -- candidate enumeration + prediction --------------------------------------
def candidate_schedules(plan: ExecutionPlan, mode: str,
                        backends=BACKENDS) -> list:
    """Every *valid* schedule in the search grid.

    Budgets sweep the regime axis (``None`` = forced resident, then
    halvings of the default that push big matrices into pipelined bands);
    crossovers sweep the matmul/shift-add split (int8 only — fp32 has no
    digit planes to strength-reduce, so its crossover is pinned to the
    default and the axis collapses); batch tiles sweep grid parallelism.
    Candidates whose band packing is infeasible (a single column's folded
    tiles overflow half the budget — ``specialize_rollout`` would raise)
    are dropped here, so everything returned can actually build; so are
    Pallas candidates whose folded tiles and shift-add unroll hold more
    VMEM than the default budget (the rest of the chip's VMEM is left to
    the state and the pipeline's blocks).  On the XLA backend the
    crossover axis moves blocks between folded tiles and the scattered
    table (:class:`~repro.plan.specialize.ScatteredTable`); on Pallas,
    between folded tiles and unrolled shift-adds.
    """
    block = plan.block
    budgets = [None, DEFAULT_VMEM_BUDGET, DEFAULT_VMEM_BUDGET // 2,
               DEFAULT_VMEM_BUDGET // 4]
    if mode == "fp32":
        crossovers = [default_crossover(block)]
    else:
        crossovers = sorted({0, block // 4, default_crossover(block),
                             block, 2 * block})
    tiles = sorted({8, DEFAULT_BATCH_TILE, 32})
    out, seen = [], set()
    for backend in backends:
        for budget in budgets:
            for crossover in crossovers:
                for tile in tiles:
                    try:
                        summary = specialize_summary(
                            plan, mode, vmem_budget=budget,
                            crossover=crossover, batch_tile_max=tile)
                    except ValueError:
                        continue  # infeasible double-buffer packing
                    if (backend == "pallas" and _pallas_vmem(summary, tile)
                            > DEFAULT_VMEM_BUDGET):
                        continue  # more than a kernel's VMEM holds
                    s = Schedule(mode, backend, budget, crossover, tile)
                    if s.key() not in seen:
                        seen.add(s.key())
                        out.append(s)
    return out


def _pallas_vmem(summary: dict, batch_tile_max: int) -> int:
    """VMEM a Pallas program holds whatever the batch: its folded tiles
    (twice over where they stream) and its shift-add unroll."""
    return (summary["resident_bytes"] + summary["shiftadd_lanes"]
            * batch_tile_max * UNROLL_LANE_ROW_BYTES)


def predict_cost(plan: ExecutionPlan, schedule: Schedule, batch: int,
                 steps: int,
                 model: costmodel.RolloutCostModel | None = None) -> float:
    """Analytic seconds for one rollout under ``schedule`` — counts-only
    summary in, calibrated linear model out.  Never compiles anything."""
    if model is None:
        model = _default_model()
    summary = specialize_summary(
        plan, schedule.mode, vmem_budget=schedule.vmem_budget,
        crossover=schedule.crossover,
        batch_tile_max=schedule.batch_tile_max)
    feats = costmodel.rollout_cost_features(summary, plan.block, batch,
                                            steps, schedule.backend)
    return model.predict(schedule.backend, feats)


_MODEL_CACHE: dict = {}


def _default_model() -> costmodel.RolloutCostModel:
    import jax
    platform = jax.default_backend()
    model = _MODEL_CACHE.get(platform)
    if model is None:
        model = _MODEL_CACHE[platform] = \
            costmodel.default_rollout_cost_model(platform)
    return model


def set_cost_model(model: costmodel.RolloutCostModel) -> None:
    """Install a calibrated model as the default predictor (e.g. one
    refit from measured bench rows)."""
    _MODEL_CACHE[model.platform] = model


# -- measurement -------------------------------------------------------------
def _probe_params(plan: ExecutionPlan, mode: str):
    """Synthetic ESNParams over the plan's own matrix, for measuring when
    the caller has no trained params at hand (the matrix is what matters;
    w_in only sets the projection gemm's inner dim)."""
    from repro.core.esn import ESNConfig, ESNParams
    fm = plan._fm
    dim = plan.shape[0]
    digit = fm.mode if fm.mode in ("pn", "csd") else "csd"
    esn_mode = f"int8-{digit}" if mode == "int8" else "fp32"
    cfg = ESNConfig(reservoir_dim=dim, input_dim=4, mode=esn_mode)
    rng = np.random.default_rng(0)
    w_in = np.asarray(rng.standard_normal((4, dim)) * 0.1, np.float32)
    return ESNParams(config=cfg, w=fm, w_in=w_in)


def _measure_schedule(plan: ExecutionPlan, schedule: Schedule, params,
                      batch: int, steps: int, reps: int = 2) -> float:
    """Wall-clock one candidate through the real engine path (compile
    excluded; best-of-reps, matching the bench harness convention)."""
    import jax
    import jax.numpy as jnp

    from repro.serve.engine import ReservoirEngine  # deferred: serve imports plan

    eng = ReservoirEngine(
        params, backend=schedule.backend,
        vmem_budget=schedule.vmem_budget, crossover=schedule.crossover,
        batch_tile_max=schedule.batch_tile_max, specialize=True)
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.standard_normal(
        (batch, steps, params.config.input_dim)), jnp.float32)
    jax.block_until_ready(eng.rollout(u))          # compile outside the clock
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(eng.rollout(u))
        best = min(best, time.perf_counter() - t0)
    return best


# -- persisted schedule cache ------------------------------------------------
class ScheduleCache:
    """``(plan fingerprint, mode, batch bucket, hardware) -> TunedSchedule``
    with JSON persistence, so a serve process can load the winners a bench
    run measured and never re-tune at startup."""

    VERSION = 1

    def __init__(self):
        self._entries: dict = {}
        self.hits = 0
        self.misses = 0

    @staticmethod
    def entry_key(fingerprint: str, mode: str, batch: int,
                  hardware: str) -> str:
        return f"{fingerprint}|{mode}|b{_batch_bucket(batch)}|{hardware}"

    def get(self, key: str):
        tuned = self._entries.get(key)
        if tuned is None:
            self.misses += 1
        else:
            self.hits += 1
        return tuned

    def put(self, key: str, tuned: TunedSchedule) -> None:
        self._entries[key] = tuned

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        self._entries.clear()
        self.hits = self.misses = 0

    def stats(self) -> dict:
        return {"size": len(self._entries), "hits": self.hits,
                "misses": self.misses}

    def as_dict(self) -> dict:
        return {"version": self.VERSION,
                "entries": {k: t.as_dict()
                            for k, t in sorted(self._entries.items())}}

    def save(self, path) -> None:
        with open(path, "w") as f:
            json.dump(self.as_dict(), f, indent=1, sort_keys=True)

    def load(self, path, merge: bool = True) -> int:
        """Merge (or replace) entries from ``path``; returns the number of
        entries loaded.  Entries replay as ``source="cache"``."""
        with open(path) as f:
            data = json.load(f)
        if data.get("version") != self.VERSION:
            raise ValueError(
                f"schedule cache version {data.get('version')} != "
                f"{self.VERSION}: re-tune rather than trust stale entries")
        if not merge:
            self._entries.clear()
        n = 0
        for key, d in data.get("entries", {}).items():
            self._entries[key] = dataclasses.replace(
                TunedSchedule.from_dict(d), source="cache")
            n += 1
        return n


_CACHE = ScheduleCache()


def autotune_cache() -> ScheduleCache:
    """The process-wide tuning cache (engine construction resolves
    through it)."""
    return _CACHE


def autotune_cache_save(path) -> None:
    _CACHE.save(path)


def autotune_cache_load(path, merge: bool = True) -> int:
    return _CACHE.load(path, merge=merge)


# -- resolution: the one entry point engines call ----------------------------
def resolve_schedule(plan: ExecutionPlan, mode: str, *,
                     backend: str = "auto", batch: int = TUNE_BATCH,
                     steps: int = TUNE_STEPS, measure: bool = False,
                     params=None, top_k: int = 3, reps: int = 2,
                     model: costmodel.RolloutCostModel | None = None,
                     cache: ScheduleCache | None = None,
                     refresh: bool = False) -> TunedSchedule:
    """The tuner's front door: cache -> predict [-> prune -> measure].

    ``measure=False`` (engine construction) never compiles or times
    anything: a cache hit replays the persisted winner, a miss falls back
    to the analytic model's pick.  ``measure=True`` (benchmarks, explicit
    ``autotune_rollout``) runs the full loop and caches the measured
    winner, which subsequent engine constructions then inherit.  An
    explicit ``backend`` restricts the search to that backend.
    """
    assert mode in ("fp32", "int8"), mode
    cache = _CACHE if cache is None else cache
    backends = BACKENDS if backend == "auto" else (backend,)
    hw = hardware_fingerprint()
    key = "|".join((ScheduleCache.entry_key(
        plan_fingerprint(plan), mode, batch, hw),) + backends)
    if not refresh:
        tuned = cache.get(key)
        if tuned is not None and (tuned.source == "measured"
                                  or tuned.measured_s is not None
                                  or not measure):
            _pin_to_plan(plan, mode, batch, hw, tuned)
            obs.event("schedule_resolve", source=tuned.source, mode=mode,
                      schedule=tuned.schedule.describe())
            obs.inc("schedule_cache_requests_total", outcome="hit")
            return tuned
    model = _default_model() if model is None else model
    cands = candidate_schedules(plan, mode, backends)
    if not cands:
        cands = [default_schedule(plan, mode, backends[0])]
    scored = sorted(
        ((predict_cost(plan, s, batch, steps, model), s) for s in cands),
        key=lambda t: (t[0], t[1].sort_key()))
    default = default_schedule(plan, mode,
                               "xla" if "xla" in backends else backends[0])
    default_pred = predict_cost(plan, default, batch, steps, model)

    if not measure:
        pred, best = scored[0]
        tuned = TunedSchedule(
            schedule=best, batch=batch, steps=steps, predicted_s=pred,
            default_predicted_s=default_pred, source="predicted",
            n_candidates=len(cands))
    else:
        chosen = scored[:max(1, top_k)]
        if not any(s.key() == default.key() for _p, s in chosen):
            chosen.append((default_pred, default))
        trials = []
        for pred, s in chosen:
            t0 = time.perf_counter()
            meas = _measure_schedule(plan, s, params, batch, steps, reps)
            obs.span("autotune.trial", t0, time.perf_counter(),
                     clock="wall", schedule=s.describe(),
                     predicted_s=pred, measured_s=meas)
            trials.append((s, pred, meas))
        win_sched, win_pred, win_meas = min(
            trials, key=lambda t: (t[2], t[0].sort_key()))
        default_meas = next(m for s, _p, m in trials
                            if s.key() == default.key())
        tuned = TunedSchedule(
            schedule=win_sched, batch=batch, steps=steps,
            predicted_s=win_pred, measured_s=win_meas,
            default_predicted_s=default_pred,
            default_measured_s=default_meas, source="measured",
            n_candidates=len(cands),
            trials=tuple((s.as_dict(), p, m) for s, p, m in trials))
    cache.put(key, tuned)
    _pin_to_plan(plan, mode, batch, hw, tuned)
    obs.event("schedule_resolve", source=tuned.source, mode=mode,
              schedule=tuned.schedule.describe())
    obs.inc("schedule_cache_requests_total", outcome="miss")
    return tuned


def _pin_to_plan(plan: ExecutionPlan, mode: str, batch: int, hw: str,
                 tuned: TunedSchedule) -> None:
    pinned = getattr(plan, "_tuned", None)
    if pinned is None:
        pinned = plan._tuned = {}
    pinned[(mode, _batch_bucket(batch), hw)] = tuned


def autotune_rollout(plan: ExecutionPlan, mode: str, *,
                     batch: int = TUNE_BATCH, steps: int = TUNE_STEPS,
                     params=None, backends=BACKENDS, top_k: int = 3,
                     reps: int = 2,
                     model: costmodel.RolloutCostModel | None = None,
                     cache: ScheduleCache | None = None,
                     refresh: bool = False) -> TunedSchedule:
    """Run the full predict -> prune -> measure -> cache loop for one plan.

    The measured winner can never lose to the default-heuristic schedule
    on its own trials: the default is always among the measured candidates
    and the winner is the measured argmin.
    """
    backend = "auto" if tuple(backends) == BACKENDS else backends[0]
    return resolve_schedule(
        plan, mode, backend=backend, batch=batch, steps=steps,
        measure=True, params=params, top_k=top_k, reps=reps, model=model,
        cache=cache, refresh=refresh)


def resolve_backend(params, backend: str = "auto",
                    batch: int = TUNE_BATCH) -> str:
    """The backend ``backend="auto"`` resolves to for these params — the
    one function ``engine_for``'s cache key AND ``ReservoirEngine``'s
    constructor both route through, so they can never disagree."""
    if backend != "auto":
        return backend
    from repro.plan.plan import plan_for
    plan = plan_for(params.w)
    mode = "int8" if params.config.mode.startswith("int8") else "fp32"
    return resolve_schedule(plan, mode, batch=batch).schedule.backend
