"""The rollout schedule rule: its picks, bit-identity, and the engines.

The load-bearing property mirrors the specialized-vs-banded test in
test_specialize.py: EVERY schedule in the grid of band budget x crossover
x batch tile stays bit-identical to the default-heuristic program across
{fp32, int8-pn, int8-csd} x {one-shot, chunked}.  The schedule is a
throughput decision only; it can never change served bits.  On top: the
rule's pick for each plan of the table it was read from (both sides of the
VMEM line, both platforms), the full-schedule summary-cache key (the
batch-tile collision bugfix), and the engines that take the rule.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.esn_paper import LARGE_1024
from repro.core.esn import ESNConfig, ESNParams, init_esn
from repro.core.sparse import FixedMatrix, random_sparse_matrix
from repro.kernels.reservoir_rollout.ops import FusedRollout
from repro.kernels.reservoir_rollout.specialized import SpecializedRollout
from repro.plan import (DEFAULT_BATCH_TILE, DEFAULT_VMEM_BUDGET, Schedule,
                        choose_schedule, plan_for, specialize_rollout,
                        specialize_summary)
from repro.plan.specialize import default_crossover
from repro.serve.engine import (ReservoirEngine, engine_cache_clear,
                                engine_cache_stats, engine_for)

DIM, BLOCK = 256, 64
TILE = BLOCK * BLOCK
# budgets that force the pipelined regime at DIM/BLOCK (see test_specialize)
PIPELINE_BUDGET = {"fp32": TILE * 4 * 10, "int8": TILE * 10}


def _fixed_matrix(digit_mode="csd", es=0.9, seed=0, dim=DIM, block=BLOCK):
    rng = np.random.default_rng(seed)
    w = random_sparse_matrix(dim, dim, es, rng) * 0.05
    return FixedMatrix.compile(w, weight_bits=8, mode=digit_mode,
                               block=block, rng=rng)


def _params(fm, esn_mode, seed=0, w_out=True):
    dim = fm.shape[0]
    rng = np.random.default_rng(seed + 100)
    cfg = ESNConfig(reservoir_dim=dim, input_dim=4, mode=esn_mode,
                    block=fm.blocks.block, seed=seed)
    return ESNParams(
        w=fm,
        w_in=jnp.asarray(rng.uniform(-0.5, 0.5, (4, dim)), jnp.float32),
        w_out=jnp.asarray(rng.uniform(-0.1, 0.1, (dim, 4)), jnp.float32)
        if w_out else None,
        config=cfg)


_FMS = {}


def _fm_for(esn_mode):
    if esn_mode not in _FMS:
        digit = "csd" if esn_mode != "int8-pn" else "pn"
        _FMS[esn_mode] = _fixed_matrix(digit)
    return _FMS[esn_mode]


MODES = ("fp32", "int8-pn", "int8-csd")


def _kmode(esn_mode):
    return "fp32" if esn_mode == "fp32" else "int8"


# One generic banded reference kernel per mode (the default-heuristic
# program's own reference), plus specialized kernels memoized per
# schedule so hypothesis examples reuse compiles.
_BASE = {}
_SPEC = {}


def _base_for(esn_mode):
    if esn_mode not in _BASE:
        rng = np.random.default_rng(7)
        w_in = rng.uniform(-0.5, 0.5, (4, DIM)).astype(np.float32)
        w_out = rng.uniform(-0.1, 0.1, (DIM, 4)).astype(np.float32)
        _BASE[esn_mode] = FusedRollout(
            plan_for(_fm_for(esn_mode)), w_in, leak=0.7,
            mode=_kmode(esn_mode), w_out=w_out)
    return _BASE[esn_mode]


def _spec_for(esn_mode, sched: Schedule):
    key = (esn_mode, sched.vmem_budget, sched.crossover,
           sched.batch_tile_max)
    if key not in _SPEC:
        rng = np.random.default_rng(7)
        w_in = rng.uniform(-0.5, 0.5, (4, DIM)).astype(np.float32)
        w_out = rng.uniform(-0.1, 0.1, (DIM, 4)).astype(np.float32)
        _SPEC[key] = SpecializedRollout(
            plan_for(_fm_for(esn_mode)), w_in, leak=0.7,
            mode=_kmode(esn_mode), w_out=w_out,
            vmem_budget=sched.vmem_budget, crossover=sched.crossover,
            batch_tile_max=sched.batch_tile_max)
    return _SPEC[key]


_GRIDS = {}


def _schedule_grid(esn_mode):
    """Every buildable Pallas schedule of budget x crossover x tile (the
    resident budget and halvings of the default; crossovers across the
    digit planes, int8 only, since fp32 has no planes to strength-reduce),
    plus the default knobs at a budget that forces the pipelined regime at
    test dims."""
    if esn_mode not in _GRIDS:
        km = _kmode(esn_mode)
        plan = plan_for(_fm_for(esn_mode))
        budgets = (None, DEFAULT_VMEM_BUDGET, DEFAULT_VMEM_BUDGET // 2,
                   DEFAULT_VMEM_BUDGET // 4)
        crossovers = ((default_crossover(BLOCK),) if km == "fp32" else
                      (0, BLOCK // 4, default_crossover(BLOCK), BLOCK,
                       2 * BLOCK))
        grid = [Schedule(km, "pallas", PIPELINE_BUDGET[km],
                         default_crossover(BLOCK), DEFAULT_BATCH_TILE)]
        for budget in budgets:
            for crossover in crossovers:
                for tile in (8, 16, 32):
                    try:
                        specialize_summary(plan, km, vmem_budget=budget,
                                           crossover=crossover,
                                           batch_tile_max=tile)
                    except ValueError:
                        continue  # infeasible double-buffer packing
                    grid.append(Schedule(km, "pallas", budget, crossover,
                                         tile))
        _GRIDS[esn_mode] = grid
    return _GRIDS[esn_mode]


class TestScheduleParity:
    # batch >= 2: at a single row XLA lowers the readout matmul as a gemv
    # whose accumulation order differs by an ulp (the caveat pinned in the
    # dist engine docstring) — that holds for the default-heuristic
    # program too, so it is not a property of the schedules.
    @given(st.sampled_from(MODES), st.booleans(), st.integers(2, 20),
           st.integers(0, 10**6), st.integers(0, 10**6))
    @settings(max_examples=15, deadline=None)
    def test_every_candidate_bit_identical_to_heuristic(
            self, mode, chunked, batch, seed, pick):
        """Any schedule of the grid == the default-heuristic program, bit
        for bit, one-shot and chunked."""
        grid = _schedule_grid(mode)
        sched = grid[pick % len(grid)]
        base, spec = _base_for(mode), _spec_for(mode, sched)
        rng = np.random.default_rng(seed)
        t = 8
        u = jnp.asarray(rng.standard_normal((t, batch, 4)), jnp.float32)
        ref_s, ref_f = base(u, want_states=True, want_final=True)
        ref_p = base(u, want_states=False, want_preds=True)
        if chunked:
            s1, f1 = spec(u[: t // 2], want_states=True, want_final=True)
            s2, got_f = spec(u[t // 2:], x0=f1, want_states=True,
                             want_final=True)
            got_s = jnp.concatenate([s1, s2], axis=0)
            p1, g1 = spec(u[: t // 2], want_states=False,
                          want_preds=True, want_final=True)
            p2 = spec(u[t // 2:], x0=g1, want_states=False,
                      want_preds=True)
            got_p = jnp.concatenate([p1, p2], axis=0)
        else:
            got_s, got_f = spec(u, want_states=True, want_final=True)
            got_p = spec(u, want_states=False, want_preds=True)
        assert (np.asarray(ref_s) == np.asarray(got_s)).all()
        assert (np.asarray(ref_f) == np.asarray(got_f)).all()
        assert (np.asarray(ref_p) == np.asarray(got_p)).all()


# The plans the rule was read from, at block 128: the benchmark's two
# matrices ("esn1024": the paper's dim-1024 95%-sparse reservoir;
# "deg3"/5000: ks5000's degree-3 reservoir), and 5%-dense and degree-3
# matrices on both sides of the 8 MiB VMEM line.
_TABLE_FMS: dict = {}


def _table_fm(kind: str, dim: int) -> FixedMatrix:
    if (kind, dim) not in _TABLE_FMS:
        if kind == "esn1024":
            fm = init_esn(LARGE_1024).w
        else:
            rng = np.random.default_rng(dim)
            m = rng.uniform(-1.0, 1.0, (dim, dim))
            m *= rng.random((dim, dim)) < (0.05 if kind == "dense5"
                                           else 3.0 / dim)
            fm = FixedMatrix.compile(0.6 * m, weight_bits=8, mode="csd",
                                     block=128)
        _TABLE_FMS[kind, dim] = fm
    return _TABLE_FMS[kind, dim]


XLA, PALLAS = "xla", "pallas"
MiB8 = DEFAULT_VMEM_BUDGET

# (kind, dim, mode, platform) -> (backend, vmem_budget, crossover, tile)
RULE_TABLE = [
    # int8 folded tiles that fit VMEM: the Pallas resident kernel
    (("esn1024", 1024, "int8", "tpu"), (PALLAS, None, 0, 8)),
    (("dense5", 256, "int8", "tpu"), (PALLAS, None, 0, 8)),
    (("dense5", 1024, "int8", "tpu"), (PALLAS, None, 0, 8)),
    (("dense5", 2048, "int8", "tpu"), (PALLAS, None, 0, 8)),
    (("deg3", 640, "int8", "tpu"), (PALLAS, None, 0, 8)),
    (("deg3", 2048, "int8", "tpu"), (PALLAS, None, 0, 8)),
    # int8 folded tiles past the line: XLA's dense fold
    (("dense5", 3072, "int8", "tpu"), (XLA, None, 0, 8)),
    (("deg3", 3072, "int8", "tpu"), (XLA, None, 0, 8)),
    (("deg3", 5000, "int8", "tpu"), (XLA, None, 0, 8)),
    # fp32: resident where it fits, else pipelined bands
    (("dense5", 512, "fp32", "tpu"), (PALLAS, None, 64, 8)),
    (("deg3", 640, "fp32", "tpu"), (PALLAS, None, 64, 8)),
    (("dense5", 2048, "fp32", "tpu"), (PALLAS, MiB8, 64, 8)),
    (("deg3", 3072, "fp32", "tpu"), (PALLAS, MiB8, 64, 8)),
    # Pallas would run interpreted: XLA, whatever the size
    (("esn1024", 1024, "int8", "cpu"), (XLA, None, 0, 8)),
    (("deg3", 5000, "int8", "cpu"), (XLA, None, 0, 8)),
    (("dense5", 2048, "fp32", "cpu"), (XLA, None, 64, 8)),
]


class TestRule:
    @pytest.mark.parametrize(
        "case,want", RULE_TABLE,
        ids=["-".join(map(str, case)) for case, _want in RULE_TABLE])
    def test_rule_picks_the_table(self, case, want):
        """The pick for each plan and mode; on the TPU, Pallas exactly where
        the whole program's folded tiles fit the VMEM budget."""
        kind, dim, mode, platform = case
        plan = plan_for(_table_fm(kind, dim))
        got = choose_schedule(plan, mode, platform)
        assert (got.mode, got.backend, got.vmem_budget, got.crossover,
                got.batch_tile_max) == (mode,) + want
        resident = specialize_summary(
            plan, mode, vmem_budget=None, crossover=want[2],
            batch_tile_max=8)["resident_bytes"]
        if platform == "tpu":
            fits = want[0] == PALLAS and want[1] is None
            assert (resident <= DEFAULT_VMEM_BUDGET) == fits
        if kind == "esn1024" and mode == "int8":
            assert resident == 1_048_576      # 64 folded int8 tiles
            if platform == "tpu":
                program = specialize_rollout(plan, mode,
                                             vmem_budget=got.vmem_budget,
                                             crossover=got.crossover,
                                             batch_tile_max=8)
                assert program.regime == "resident"
                assert program.n_matmul_terms == 64
                assert program.n_shiftadd_terms == 0

    def test_resolution_is_deterministic_and_xla_on_cpu(self):
        plan = plan_for(_fm_for("int8-csd"))
        a = choose_schedule(plan, "int8", jax.default_backend())
        b = choose_schedule(plan, "int8", jax.default_backend())
        assert a == b
        assert choose_schedule(plan, "int8", "cpu").backend == "xla"
        if jax.default_backend() == "cpu":
            assert a.backend == "xla"


class TestSummaryCacheKey:
    def test_summary_keyed_on_batch_tile(self):
        """Regression: the summary cache used to omit the batch tile, so
        schedules differing only in tiling collided."""
        plan = plan_for(_fm_for("int8-csd"))
        s8 = specialize_summary(plan, "int8", batch_tile_max=8)
        s32 = specialize_summary(plan, "int8", batch_tile_max=32)
        assert s8["batch_tile_max"] == 8
        assert s32["batch_tile_max"] == 32
        # a cached program for one tile size must not answer for another
        specialize_rollout(plan, "int8", batch_tile_max=8)
        assert specialize_summary(plan, "int8",
                                  batch_tile_max=32)["batch_tile_max"] == 32

    def test_summary_still_matches_program(self):
        plan = plan_for(_fm_for("int8-csd"))
        prog = specialize_rollout(plan, "int8", batch_tile_max=8)
        s = specialize_summary(plan, "int8", batch_tile_max=8)
        assert s["batch_tile_max"] == prog.batch_tile_max == 8
        assert s["n_matmul_terms"] == prog.n_matmul_terms


def _chosen(p):
    mode = "int8" if p.config.mode.startswith("int8") else "fp32"
    return choose_schedule(plan_for(p.w), mode, jax.default_backend())


class TestEngineIntegration:
    def test_engine_for_key_and_backend_agree(self):
        """Regression: engine_for used to key "auto" as "xla" while the
        constructor got the raw string; both now take the rule."""
        engine_cache_clear()
        engine_cache_stats(reset=True)
        p = _params(_fm_for("int8-csd"), "int8-csd", seed=8)
        eng = engine_for(p)
        assert eng.backend == _chosen(p).backend
        # asking for the chosen backend explicitly hits the same entry
        assert engine_for(p, eng.backend) is eng
        assert engine_for(p) is eng
        assert engine_cache_stats()["hits"] >= 2

    def test_auto_engine_adopts_chosen_schedule(self):
        p = _params(_fm_for("int8-csd"), "int8-csd", seed=9)
        chosen = _chosen(p)
        eng = ReservoirEngine(p)
        assert eng.schedule == chosen
        assert eng.backend == chosen.backend
        assert eng.vmem_budget == chosen.vmem_budget
        assert eng.crossover == chosen.crossover
        assert eng.batch_tile_max == chosen.batch_tile_max

    def test_explicit_kwargs_beat_chosen_schedule(self):
        p = _params(_fm_for("int8-csd"), "int8-csd", seed=9)
        eng = ReservoirEngine(p, vmem_budget=12345, crossover=7,
                              batch_tile_max=4)
        assert eng.vmem_budget == 12345
        assert eng.crossover == 7 and eng.batch_tile_max == 4

    def test_unspecialized_auto_stays_xla(self):
        p = _params(_fm_for("fp32"), "fp32", seed=10)
        eng = ReservoirEngine(p, specialize=False)
        assert eng.backend == "xla" and eng.schedule is None

    def test_sharded_engine_inherits_chosen_schedule(self):
        from repro.dist.engine import ShardedReservoirEngine
        p = _params(_fm_for("int8-csd"), "int8-csd", seed=11)
        chosen = _chosen(p)
        eng = ShardedReservoirEngine(p, n_shards=1)
        assert eng.schedule == chosen
        assert eng.backend == chosen.backend
        sib = eng.like()
        assert sib.schedule == eng.schedule
        assert sib.backend == eng.backend
        assert sib.vmem_budget == eng.vmem_budget
        # new params take the rule for their own plan
        q = _params(_fm_for("fp32"), "fp32", seed=12)
        other = eng.like(q)
        assert other.schedule == _chosen(q)
