"""The scattered table: the int8 lowering of reservoirs whose blocks all
hold a nonzero but few of them (degree-3 reservoirs, as in Pathak et al.,
PRL 120, 024102, 2018).

Every block of such a matrix lies below the shift-add crossover, so the
program has no folded tile.  The XLA backend's culled schedule reads the
whole matrix from ELL tables in one gather-multiply-accumulate: exact in
int32, and a program whose size is fixed by the table's shape, not by
the matrix's nonzeros or digits.  Where the gather is dear (the TPU), the
schedule rule picks the folded dense product of the same integers instead.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import obs
from repro.configs.esn_paper import LARGE_1024
from repro.core.esn import ESNConfig, ESNParams, init_esn
from repro.core.sparse import FixedMatrix
from repro.plan import (choose_schedule, plan_for, specialize_rollout,
                        specialize_summary)
from repro.plan.specialize import (default_crossover, int8_recur_reference,
                                   scattered_table, table_product)
from repro.serve import AsyncReservoirServer, ReservoirEngine, SubmitSpec

IN, OUT = 20, 8           # 8 grid points plus 6 on each side in, 8 out
DIMS = (640, 1280)


def _degree3(dim: int, seed: int = 0) -> FixedMatrix:
    """An Erdos-Renyi reservoir of average degree 3, entries uniform in
    [-1, 1], compiled to 8-bit CSD digits at block 128."""
    rng = np.random.default_rng(seed)
    m = rng.uniform(-1.0, 1.0, (dim, dim))
    m *= rng.random((dim, dim)) < 3.0 / dim
    return FixedMatrix.compile(0.6 * m, weight_bits=8, mode="csd",
                               block=128)


_FMS: dict = {}


def _fm(dim: int) -> FixedMatrix:
    if dim not in _FMS:
        _FMS[dim] = _degree3(dim, seed=dim)
    return _FMS[dim]


def _params(fm: FixedMatrix, seed: int = 0) -> ESNParams:
    dim = fm.shape[0]
    rng = np.random.default_rng(seed + 1)
    cfg = ESNConfig(reservoir_dim=dim, input_dim=IN, output_dim=OUT,
                    element_sparsity=1 - 3 / dim, spectral_radius=0.6,
                    input_scale=1.0, mode="int8-csd")
    return ESNParams(
        w=fm,
        w_in=jnp.asarray(rng.uniform(-1.0, 1.0, (IN, dim)), jnp.float32),
        w_out=jnp.asarray(rng.uniform(-0.1, 0.1, (dim, OUT)), jnp.float32),
        config=cfg)


def _eqns(jaxpr) -> int:
    """Equations of a jaxpr and of every jaxpr nested in it."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    n += _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    n += _eqns(sub)
    return n


class TestTable:
    @pytest.mark.parametrize("dim", DIMS)
    def test_table_holds_the_quantized_matrix(self, dim):
        fm = _fm(dim)
        plan = plan_for(fm)
        program = specialize_rollout(plan, "int8")
        table = scattered_table(plan)
        assert plan.block_density > 0.9          # a nonzero in most blocks
        assert program.kind == "scattered"
        assert program.n_matmul_terms == 0 and program.table is table
        q = np.asarray(fm.q, np.int64)
        assert table.entries == np.count_nonzero(q)
        rebuilt = np.zeros((plan.rows_pad, plan.cols_pad), np.int64)
        np.add.at(rebuilt, (table.idx, np.arange(plan.cols_pad)),
                  table.val)
        assert (rebuilt[:dim, :dim] == q).all()
        assert table.degree == np.count_nonzero(q, axis=0).max()

    @pytest.mark.parametrize("dim", DIMS)
    def test_table_product_is_exact(self, dim):
        fm = _fm(dim)
        plan = plan_for(fm)
        program = specialize_rollout(plan, "int8")
        rng = np.random.default_rng(dim)
        xq = jnp.asarray(rng.integers(-128, 128, (7, dim)), jnp.int32)
        want = np.asarray(fm.matvec_int_exact(xq))
        got = np.asarray(table_product(scattered_table(plan), xq))[:, :dim]
        ref = np.asarray(int8_recur_reference(program, xq, plan.rows_pad,
                                              dim))
        assert (got == want).all() and (ref == want).all()

    # batch >= 2, as in the banded-kernel parity property
    @given(st.sampled_from(DIMS), st.booleans(), st.integers(2, 12),
           st.integers(0, 10**6))
    @settings(max_examples=8, deadline=None)
    def test_bitwise_parity_with_plane_engine(self, dim, chunked, batch,
                                              seed):
        """The table engine == the exact digit-plane engine, bit for bit:
        states, predictions and final state, one-shot and chunked."""
        p = _params(_fm(dim))
        table = ReservoirEngine(p, backend="xla")
        planes = ReservoirEngine(p, backend="xla", specialize=False)
        assert table.xla_schedule == "int8-folded-culled"
        assert table.program is None and \
            table._int8_summary()["kind"] == "scattered"
        assert planes.xla_schedule == "int8-planes"
        rng = np.random.default_rng(seed)
        t = 8
        u = jnp.asarray(rng.standard_normal((batch, t, IN)), jnp.float32)
        z = jnp.zeros((batch, dim), jnp.float32)
        for want_states in (True, False):
            a, fa = planes.run_segment(u, z, want_states=want_states)
            if chunked:
                b1, f1 = table.run_segment(u[:, :t // 2], z,
                                           want_states=want_states)
                b2, fb = table.run_segment(u[:, t // 2:], f1,
                                           want_states=want_states)
                b = jnp.concatenate([b1, b2], axis=1)
            else:
                b, fb = table.run_segment(u, z, want_states=want_states)
            assert (np.asarray(a) == np.asarray(b)).all()
            assert (np.asarray(fa) == np.asarray(fb)).all()

    def test_program_size_does_not_grow_with_nonzeros(self):
        """The traced rollout is as long at dim 1280 as at 640, though the
        matrix has twice the nonzeros (and the Pallas unroll twice the
        digits)."""
        counts, digits = [], []
        for dim in DIMS:
            eng = ReservoirEngine(_params(_fm(dim)), backend="xla")
            u = jnp.zeros((4, 8, IN), jnp.float32)
            z = jnp.zeros((4, dim), jnp.float32)
            counts.append(_eqns(jax.make_jaxpr(eng._xla(True, True))(
                u, z).jaxpr))
            digits.append(specialize_summary(plan_for(_fm(dim)),
                                             "int8")["shiftadd_digits"])
        assert counts[0] == counts[1]
        assert digits[1] > 1.5 * digits[0]

    def test_served_path_matches_plain_reference(self):
        """Through ``AsyncReservoirServer`` on an engine of the default
        backend at the block's default crossover (so the table serves),
        against a plain ``jax.numpy`` rollout at the highest matmul
        precision.

        Tolerances: the recurrent product is exact integers on both sides
        (|x_q| <= 128, |q| <= 127, at most a dozen terms per column, far
        inside float32's 2**24), so what differs is float32 summation
        order in the 20-deep input projection and the 640-deep readout,
        a few ulps of values below 10; a state that lands within an ulp
        of a quantization boundary may round to the neighbouring level,
        moving one pre-activation by ``scale / 127`` (about 4e-5 here) and
        its node by at most that.  So 1e-4 on any prediction and 1e-5
        RMS, far below the 8-bit state's own step."""
        dim = 640
        fm = _fm(dim)
        p = _params(fm, seed=3)
        eng = ReservoirEngine(p, crossover=default_crossover(128))
        assert eng.backend == "xla" and eng.xla_schedule == "int8-folded-culled"
        assert eng._int8_summary()["kind"] == "scattered"
        rng = np.random.default_rng(4)
        lengths = [5, 17, 31, 9, 24, 12]
        inputs = [rng.uniform(-1, 1, (n, IN)).astype(np.float32)
                  for n in lengths]
        srv = AsyncReservoirServer(eng, n_slots=4, chunk_steps=8)
        for k, u in enumerate(inputs):
            srv.submit(SubmitSpec(u, uid=k))
        results = srv.run()
        smax = 127
        q = jnp.asarray(np.asarray(fm.q), jnp.float32)
        with jax.default_matmul_precision("highest"):
            for k, u in enumerate(inputs):
                x = jnp.zeros((dim,), jnp.float32)
                want = []
                for step in jnp.asarray(u):
                    xq = jnp.clip(jnp.round(x * smax), -smax - 1, smax)
                    x = jnp.tanh(step @ p.w_in
                                 + (xq @ q) * (fm.scale / smax))
                    want.append(x @ p.w_out)
                got = np.asarray(results[k].preds)
                gap = got - np.asarray(jnp.stack(want))
                assert got.shape == (lengths[k], OUT)
                assert np.abs(gap).max() <= 1e-4
                assert np.sqrt(np.mean(gap ** 2)) <= 1e-5

    def test_dispatch_counts_its_multiply_adds(self):
        """``engine.dispatch`` carries the launch's multiply-adds (the
        padded table's for the table lowering) and the launch counter its
        lowering."""
        dim = 640
        eng = ReservoirEngine(_params(_fm(dim)), backend="xla")
        table = scattered_table(eng.plan)
        u = jnp.zeros((3, 8, IN), jnp.float32)
        obs.configure()
        try:
            eng.run_segment(u, jnp.zeros((3, dim), jnp.float32),
                            defer_sync=True)
            (span,) = obs.tracer().spans(name="engine.dispatch")
            text = obs.metrics().prometheus_text()
        finally:
            obs.disable()
        assert span.attrs["recur_ops"] == table.slots * 3 * 8
        assert 'lowering="int8-folded-culled"' in text


class TestSelection:
    def test_existing_cell_keeps_its_program(self):
        """The benchmark's dim-1024 95%-sparse matrix: 64 folded tiles,
        resident, no shift-add term, no table; the TPU pick stays the
        Pallas kernel at crossover 0."""
        plan = plan_for(init_esn(LARGE_1024).w)
        program = specialize_rollout(plan, "int8")
        assert program.regime == "resident" and program.kind == "tiles"
        assert program.n_matmul_terms == 64
        assert program.n_shiftadd_terms == 0 and program.table is None
        pick = choose_schedule(plan, "int8", "tpu")
        assert (pick.backend, pick.crossover) == ("pallas", 0)

    def test_pallas_unroll_beyond_vmem_is_no_candidate(self):
        """The Pallas kernel keeps one (b_tile, 1) column per output lane
        of its shift-add unroll in VMEM, which at 3072 nodes and degree 3
        no batch tile keeps under the budget.  The TPU pick never unrolls:
        at 3072 nodes the folded tiles overflow the budget too, so it is
        XLA's fold; at 640 it is the Pallas kernel at crossover 0, all
        folded tiles, where the default crossover would unroll digits."""
        def pick_and_digits(dim):
            plan = plan_for(_degree3(dim, seed=1))
            pick = choose_schedule(plan, "int8", "tpu")
            return pick, [specialize_summary(
                plan, "int8", vmem_budget=None, crossover=c,
                batch_tile_max=pick.batch_tile_max)["shiftadd_digits"]
                for c in (pick.crossover, default_crossover(plan.block))]

        big, (big_digits, _) = pick_and_digits(3072)
        assert big.backend == "xla" and big_digits == 0
        small, (small_digits, unrolled) = pick_and_digits(640)
        assert (small.backend, small.crossover) == ("pallas", 0)
        assert small_digits == 0 and unrolled > 0

    def test_cold_pick_is_the_fastest_measured(self):
        """A degree-3 reservoir whose folded tiles overflow a kernel's
        VMEM.  On the TPU the pick is XLA's dense fold, the fastest
        lowering a v5e chip has measured for such a matrix: the 5000-node
        cell's fold took 0.64 / 0.67 / 0.80 ms per 16-step launch of 8 /
        64 / 256 rows, its scattered table 1.77 / 1.80 / 3.26 ms, and the
        Pallas kernel multiplies folded tiles slower than XLA.  On the
        CPU, where Pallas runs interpreted, the pick is XLA's fold too;
        the table serves only where a caller asks for a crossover."""
        plan = plan_for(_degree3(3072, seed=1))
        for platform in ("tpu", "cpu"):
            pick = choose_schedule(plan, "int8", platform)
            assert (pick.backend, pick.crossover) == ("xla", 0)
        assert specialize_summary(plan, "int8", crossover=0)["kind"] \
            == "tiles"
        assert specialize_summary(
            plan, "int8", crossover=default_crossover(plan.block)
        )["kind"] == "scattered"
