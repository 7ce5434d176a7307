"""Plan specialization: regimes, CSD folding, shift-add, parity, caches.

The contract is the tentpole's: whatever regime the plan selects
(resident / double-buffered pipeline), whatever the schedule folds or
strength-reduces, the specialized rollout is *bit-identical* to the
generic banded kernel — property-tested across
{fp32, int8-pn, int8-csd} x {resident, pipelined} x {one-shot, chunked}.
On top: regime selection against the VMEM budget, the constant-propagated
fold collapsing digit planes into the quantized block, shift-add emission
below the crossover, the specialized XLA schedules, and the bounded
plan/engine caches.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.esn import ESNConfig, ESNParams
from repro.core.sparse import FixedMatrix, random_sparse_matrix
from repro.kernels.reservoir_rollout.ops import FusedRollout
from repro.kernels.reservoir_rollout.specialized import SpecializedRollout
from repro.plan import plan_for, specialize_rollout, specialize_summary
from repro.plan.plan import plan_cache_stats
from repro.plan.specialize import MM, SA, int8_recur_reference
from repro.serve.engine import (ENGINE_CACHE_MAX, ReservoirEngine,
                                engine_cache_clear, engine_cache_stats,
                                engine_for)

DIM, BLOCK = 256, 64
TILE = BLOCK * BLOCK
# budgets that force the pipelined regime at DIM/BLOCK (cap = budget // 2
# still fits each single column's tiles, total does not fit)
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


_PAIRS = {}


def _kernel_pair(esn_mode, regime):
    """(generic banded, specialized) rollout ops for one mode/regime."""
    key = (esn_mode, regime)
    if key not in _PAIRS:
        fm = _fm_for(esn_mode)
        kmode = "fp32" if esn_mode == "fp32" else "int8"
        budget = None if regime == "resident" else PIPELINE_BUDGET[kmode]
        rng = np.random.default_rng(7)
        w_in = rng.uniform(-0.5, 0.5, (4, DIM)).astype(np.float32)
        w_out = rng.uniform(-0.1, 0.1, (DIM, 4)).astype(np.float32)
        base = FusedRollout(plan_for(fm), w_in, leak=0.7, mode=kmode,
                            w_out=w_out)
        spec = SpecializedRollout(plan_for(fm), w_in, leak=0.7, mode=kmode,
                                  w_out=w_out, vmem_budget=budget,
                                  batch_tile_max=8)
        assert spec.regime == regime, (key, spec.regime)
        _PAIRS[key] = (base, spec)
    return _PAIRS[key]


class TestRegimeSelection:
    def test_resident_when_tiles_fit(self):
        plan = plan_for(_fm_for("fp32"))
        prog = specialize_rollout(plan, "fp32", vmem_budget=None)
        assert prog.regime == "resident" and prog.n_bands == 1

    def test_pipelined_when_budget_exceeded(self):
        plan = plan_for(_fm_for("fp32"))
        prog = specialize_rollout(plan, "fp32",
                                  vmem_budget=PIPELINE_BUDGET["fp32"])
        assert prog.regime == "pipelined" and prog.n_bands > 1
        # every band's tiles fit half the budget (double buffering)
        itemsize = 4
        for band in prog.schedules:
            terms = sum(1 for _ci, ts in band for t in ts if t[0] == MM)
            assert terms * TILE * itemsize <= PIPELINE_BUDGET["fp32"] // 2

    def test_column_larger_than_half_budget_raises(self):
        plan = plan_for(_fm_for("fp32"))
        with pytest.raises(ValueError, match="double buffering"):
            specialize_rollout(plan, "fp32", vmem_budget=TILE * 4 * 3)

    def test_program_cached_per_plan(self):
        plan = plan_for(_fm_for("fp32"))
        assert (specialize_rollout(plan, "fp32")
                is specialize_rollout(plan, "fp32"))

    def test_batch_tiling_balanced(self):
        prog = specialize_rollout(plan_for(_fm_for("fp32")), "fp32")
        assert prog.batch_tiling(64) == (16, 4, 64)
        assert prog.batch_tiling(5) == (5, 1, 5)
        # a tile is a multiple of the 8-row sublane tiling or the whole
        # batch: 20 rows run as three 8-row tiles (Mosaic refuses 10)
        assert prog.batch_tiling(20) == (8, 3, 24)
        for batch in range(1, 80):
            b_tile, n, b_pad = prog.batch_tiling(batch)
            assert b_tile * n == b_pad >= batch and b_pad - batch < b_tile
            assert (n == 1 and b_tile == batch) or b_tile % 8 == 0
            assert b_tile <= prog.batch_tile_max

    def test_summary_matches_program(self):
        plan = plan_for(_fm_for("int8-csd"))
        s = specialize_summary(plan, "int8",
                               vmem_budget=PIPELINE_BUDGET["int8"])
        prog = specialize_rollout(plan, "int8",
                                  vmem_budget=PIPELINE_BUDGET["int8"])
        assert s["regime"] == prog.regime
        assert s["n_bands"] == prog.n_bands
        assert s["n_matmul_terms"] == prog.n_matmul_terms
        assert s["n_shiftadd_terms"] == prog.n_shiftadd_terms
        assert s["resident_bytes"] == prog.resident_bytes

    def test_describe_reports_regime(self):
        plan = plan_for(_fm_for("int8-csd"))
        text = plan.describe()
        assert "specialized: fp32" in text and "specialized: int8" in text
        assert "matmul terms" in text and "shift-add" in text
        prog = specialize_rollout(plan, "int8")
        assert prog.regime in prog.describe()


class TestConstantPropagation:
    def test_full_fold_is_quantized_block(self):
        """With the crossover at 0 nothing is strength-reduced, so every
        block folds ALL its planes — and the fold must be exactly the
        quantized block: sum_w 2^w d_w == q."""
        fm = _fm_for("int8-csd")
        plan = plan_for(fm)
        prog = specialize_rollout(plan, "int8", vmem_budget=None, crossover=0)
        assert prog.n_shiftadd_terms == 0
        q = np.asarray(fm.q, np.int64)
        qpad = np.zeros((plan.rows_pad, plan.cols_pad), np.int64)
        qpad[: q.shape[0], : q.shape[1]] = q
        data = np.asarray(prog.data)
        for ci, terms in prog.schedules[0]:
            for tag, slot, shift, ri in terms:
                assert tag == MM and shift == 0
                tile = qpad[ri * BLOCK:(ri + 1) * BLOCK,
                            ci * BLOCK:(ci + 1) * BLOCK]
                assert (data[0, slot].astype(np.int64) == tile).all()

    def test_shiftadd_emitted_below_crossover(self):
        """A huge crossover strength-reduces every plane: no matmul terms
        survive, the digit count equals the matrix's set-digit count, and
        the schedule is still exact."""
        fm = _fixed_matrix("csd", es=0.995, seed=3, dim=128, block=32)
        plan = plan_for(fm)
        prog = specialize_rollout(plan, "int8", vmem_budget=None,
                                  crossover=10**9)
        assert prog.n_matmul_terms == 0 and prog.n_shiftadd_terms > 0
        assert prog.shiftadd_digits == int(
            np.count_nonzero(plan.int8_tiles))
        rng = np.random.default_rng(0)
        xq = jnp.asarray(rng.integers(-128, 128, (3, 128)), jnp.int32)
        ref = fm.matvec_int_exact(xq)
        got = int8_recur_reference(prog, xq, plan.rows_pad, 128)
        assert (np.asarray(ref) == np.asarray(got)).all()

    def test_mixed_schedule_is_exact(self):
        """Default crossover on a sparse matrix mixes folded matmuls and
        shift-adds; the int32 total must still equal the exact plane sum."""
        fm = _fixed_matrix("csd", es=0.97, seed=4, dim=128, block=32)
        plan = plan_for(fm)
        prog = specialize_rollout(plan, "int8", vmem_budget=None)
        assert prog.n_matmul_terms > 0 and prog.n_shiftadd_terms > 0
        rng = np.random.default_rng(1)
        xq = jnp.asarray(rng.integers(-128, 128, (4, 128)), jnp.int32)
        ref = fm.matvec_int_exact(xq)
        got = int8_recur_reference(prog, xq, plan.rows_pad, 128)
        assert (np.asarray(ref) == np.asarray(got)).all()

    def test_sa_terms_reference_real_digits(self):
        fm = _fixed_matrix("csd", es=0.97, seed=4, dim=128, block=32)
        plan = plan_for(fm)
        prog = specialize_rollout(plan, "int8", vmem_budget=None)
        tiles = plan.int8_tiles
        rows, cols = plan.block_rows, plan.block_cols
        for band in prog.schedules:
            for ci, terms in band:
                for term in terms:
                    if term[0] != SA:
                        continue
                    _tag, ri, digits = term
                    # locate the source block and check each digit
                    (di,) = [int(d) for d in np.flatnonzero(
                        (cols == ci) & (rows == ri))]
                    for i, j, s, w in digits:
                        assert int(tiles[w, di][i, j]) == s != 0


MODES = ("fp32", "int8-pn", "int8-csd")
REGIMES = ("resident", "pipelined")


class TestSpecializedParity:
    # batch >= 2: a one-row fp32 batch may lower the recurrent matmul as a
    # gemv whose accumulation order differs by an ulp between the two
    # kernels (the documented one-row caveat, as in test_schedule.py)
    @given(st.sampled_from(MODES), st.sampled_from(REGIMES),
           st.booleans(), st.integers(2, 20), st.integers(0, 10**6))
    @settings(max_examples=15, deadline=None)
    def test_bitwise_parity_with_banded_kernel(self, mode, regime, chunked,
                                               batch, seed):
        """Specialized == generic banded kernel, bit for bit, across
        modes x regimes x chunked/one-shot (states, preds, final state)."""
        base, spec = _kernel_pair(mode, regime)
        rng = np.random.default_rng(seed)
        t = 8
        u = jnp.asarray(rng.standard_normal((t, batch, 4)), jnp.float32)
        ref_s, ref_f = base(u, want_states=True, want_final=True)
        ref_p = base(u, want_states=False, want_preds=True)
        if chunked:
            # two chunks resuming from the carried final state
            s1, f1 = spec(u[: t // 2], want_states=True, want_final=True)
            s2, f2 = spec(u[t // 2:], x0=f1, want_states=True,
                          want_final=True)
            got_s = jnp.concatenate([s1, s2], axis=0)
            got_f = f2
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


class TestSpecializedEpilogues:
    def test_readout_every_k_matches_generic(self):
        fm = _fm_for("fp32")
        rng = np.random.default_rng(9)
        w_in = rng.uniform(-0.5, 0.5, (4, DIM)).astype(np.float32)
        w_out = rng.uniform(-0.1, 0.1, (DIM, 4)).astype(np.float32)
        base = FusedRollout(plan_for(fm), w_in, leak=0.6, mode="fp32",
                            w_out=w_out, readout_every=4)
        spec = SpecializedRollout(plan_for(fm), w_in, leak=0.6, mode="fp32",
                                  w_out=w_out, readout_every=4,
                                  batch_tile_max=4)
        u = jnp.asarray(rng.standard_normal((8, 6, 4)), jnp.float32)
        ref = base(u, want_states=False, want_preds=True)
        got = spec(u, want_states=False, want_preds=True)
        assert ref.shape == got.shape == (2, 6, 4)
        assert (np.asarray(ref) == np.asarray(got)).all()


class TestSpecializedXla:
    @pytest.mark.parametrize("esn_mode", ["int8-csd", "int8-pn"])
    def test_folded_dense_matches_plane_exact(self, esn_mode):
        p = _params(_fm_for(esn_mode), esn_mode, w_out=True)
        base = ReservoirEngine(p, specialize=False)
        spec = ReservoirEngine(p)
        assert spec.xla_schedule == "int8-folded-dense"
        rng = np.random.default_rng(2)
        u = jnp.asarray(rng.standard_normal((5, 7, 4)), jnp.float32)
        z = jnp.zeros((5, DIM), jnp.float32)
        for want_states in (True, False):
            a, fa = base.run_segment(u, z, want_states=want_states)
            b, fb = spec.run_segment(u, z, want_states=want_states)
            assert (np.asarray(a) == np.asarray(b)).all()
            assert (np.asarray(fa) == np.asarray(fb)).all()

    def test_folded_culled_matches_plane_exact(self):
        fm = _fixed_matrix("csd", es=0.97, seed=4, dim=128, block=32)
        p = _params(fm, "int8-csd")
        # force the culled schedule regardless of block density
        base = ReservoirEngine(p, specialize=False)
        spec = ReservoirEngine(p, dense_dispatch_density=2.0)
        assert spec.xla_schedule == "int8-folded-culled"
        rng = np.random.default_rng(3)
        u = jnp.asarray(rng.standard_normal((6, 3, 4)), jnp.float32)
        a = base.rollout(u)
        b = spec.rollout(u)
        assert (np.asarray(a) == np.asarray(b)).all()

    def test_fp32_unchanged_by_specialization(self):
        p = _params(_fm_for("fp32"), "fp32")
        base = ReservoirEngine(p, specialize=False)
        spec = ReservoirEngine(p)
        rng = np.random.default_rng(4)
        u = jnp.asarray(rng.standard_normal((4, 5, 4)), jnp.float32)
        assert (np.asarray(base.rollout(u))
                == np.asarray(spec.rollout(u))).all()


class TestBoundedCaches:
    def test_plan_cache_counts_hits_and_misses(self):
        before = plan_cache_stats()
        fm = _fixed_matrix("csd", seed=11, dim=128, block=64)
        plan_for(fm)
        plan_for(fm)
        after = plan_cache_stats()
        assert after["misses"] == before["misses"] + 1
        assert after["hits"] >= before["hits"] + 1

    def test_engine_cache_is_bounded_lru(self):
        engine_cache_clear()
        engine_cache_stats(reset=True)
        fm = _fixed_matrix("csd", seed=12, dim=128, block=64)
        keep = []
        for i in range(ENGINE_CACHE_MAX + 4):
            p = _params(fm, "fp32", seed=i, w_out=False)
            keep.append(p)                    # keep params alive: evictions
            engine_for(p)                     # must come from the LRU bound
        s = engine_cache_stats()
        assert s["size"] <= ENGINE_CACHE_MAX
        assert s["evictions"] >= 4
        assert s["misses"] == ENGINE_CACHE_MAX + 4

    def test_engine_cache_hit_and_readout_invalidation(self):
        engine_cache_clear()
        engine_cache_stats(reset=True)
        fm = _fixed_matrix("csd", seed=13, dim=128, block=64)
        p = _params(fm, "fp32", w_out=False)
        e1 = engine_for(p)
        assert engine_for(p) is e1
        assert engine_cache_stats()["hits"] == 1
        # replacing the readout must invalidate the compiled engine
        p.w_out = jnp.zeros((128, 4), jnp.float32)
        e2 = engine_for(p)
        assert e2 is not e1 and e2.has_readout

    def test_lru_evicts_oldest_and_rebuilds_on_return(self):
        engine_cache_clear()
        engine_cache_stats(reset=True)
        fm = _fixed_matrix("csd", seed=14, dim=128, block=64)
        first = _params(fm, "fp32", seed=0, w_out=False)
        e_first = engine_for(first)
        for i in range(1, ENGINE_CACHE_MAX + 1):   # pushes `first` out
            engine_for(_params(fm, "fp32", seed=i, w_out=False))
        assert engine_cache_stats()["evictions"] >= 1
        e_again = engine_for(first)                 # miss: was evicted
        assert e_again is not e_first
        assert engine_cache_stats()["misses"] == ENGINE_CACHE_MAX + 2
