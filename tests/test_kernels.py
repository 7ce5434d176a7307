"""Pallas kernels vs pure-jnp oracles (interpreted on the CPU).

Each kernel sweeps shapes/dtypes and asserts allclose (exact for integer
paths) against its ref.py oracle, per the kernel-layout convention.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.bitplanes import decompose
from repro.core.sparse import BlockSparse, FixedMatrix, random_sparse_matrix
from repro.kernels.bitplane_gemv.bitplane_gemv import bitplane_gemv
from repro.kernels.bitplane_gemv.ops import BitplaneGemv, digits_from_fixed
from repro.kernels.bitplane_gemv.ref import bitplane_gemv_ref, dense_gemv_ref
from repro.kernels.bcsr_matmul.ops import BcsrMatmul
from repro.kernels.bcsr_matmul.ref import bcsr_matmul_ref
from repro.kernels.reservoir_step.ops import FusedReservoir
from repro.kernels.reservoir_step.ref import reservoir_step_ref


class TestBitplaneGemv:
    @pytest.mark.parametrize("r,c,br,bc", [
        (128, 128, 128, 128),
        (256, 128, 128, 128),
        (128, 256, 64, 128),
        (256, 256, 64, 64),
    ])
    @pytest.mark.parametrize("mode", ["pn", "csd"])
    def test_exact_vs_dense(self, r, c, br, bc, mode):
        rng = np.random.default_rng(r + c)
        v = rng.integers(-128, 128, size=(r, c))
        v[rng.random(v.shape) < 0.9] = 0
        dp = decompose(v, 8, mode=mode, rng=rng)
        digits = jnp.asarray(
            dp.pos.astype(np.int8) - dp.neg.astype(np.int8))
        x = jnp.asarray(rng.integers(-128, 128, size=(4, r)), jnp.int32)
        got = bitplane_gemv(x, digits, block_r=br, block_c=bc)
        want = dense_gemv_ref(x, jnp.asarray(v))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        ref = bitplane_gemv_ref(x, digits)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    @pytest.mark.parametrize("x_dtype", [jnp.int8, jnp.int32])
    def test_input_dtypes(self, x_dtype):
        rng = np.random.default_rng(0)
        v = rng.integers(-8, 8, size=(128, 128))
        dp = decompose(v, 4, mode="pn")
        digits = jnp.asarray(dp.pos.astype(np.int8) - dp.neg.astype(np.int8))
        x = jnp.asarray(rng.integers(-100, 100, size=(2, 128)), x_dtype)
        got = bitplane_gemv(x, digits)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(dense_gemv_ref(x, jnp.asarray(v))))

    def test_plane_mask_culls_safely(self):
        """Culling empty planes (trace-time constant prop) changes nothing."""
        rng = np.random.default_rng(1)
        v = rng.integers(0, 4, size=(128, 128))  # only low planes populated
        dp = decompose(v, 8, mode="pn")
        digits = np.asarray(dp.pos.astype(np.int8) - dp.neg.astype(np.int8))
        x = jnp.asarray(rng.integers(-128, 128, size=(2, 128)), jnp.int32)
        mask = tuple(bool(np.any(digits[w])) for w in range(digits.shape[0]))
        assert not all(mask)  # some planes really are empty
        got = bitplane_gemv(x, jnp.asarray(digits), plane_mask=mask)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(dense_gemv_ref(x, jnp.asarray(v))))

    def test_ops_wrapper_unaligned(self):
        """Wrapper pads ragged shapes to MXU-aligned blocks."""
        rng = np.random.default_rng(2)
        d = random_sparse_matrix(200, 150, 0.9, rng)
        fm = FixedMatrix.compile(d, mode="csd", block=64, rng=rng)
        op = BitplaneGemv(fm, block_r=128, block_c=128)
        x = jnp.asarray(rng.integers(-128, 128, size=(3, 200)), jnp.int32)
        got = op(x)
        want = fm.matvec_int_dense_ref(x)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @given(st.integers(1, 6), st.sampled_from(["pn", "csd"]))
    @settings(max_examples=10, deadline=None)
    def test_property_random_bits(self, weight_bits, mode):
        rng = np.random.default_rng(weight_bits * 17)
        lo, hi = -(1 << (weight_bits - 1)), (1 << (weight_bits - 1))
        v = rng.integers(lo, hi, size=(128, 128))
        dp = decompose(v, weight_bits, mode=mode, rng=rng)
        digits = jnp.asarray(dp.pos.astype(np.int8) - dp.neg.astype(np.int8))
        x = jnp.asarray(rng.integers(-64, 64, size=(2, 128)), jnp.int32)
        got = bitplane_gemv(x, digits)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(dense_gemv_ref(x, jnp.asarray(v))))


class TestBcsrMatmul:
    @pytest.mark.parametrize("r,c,block,sparsity", [
        (256, 256, 128, 0.95),
        (512, 256, 128, 0.99),
        (256, 512, 128, 0.999),   # many empty column blocks
        (384, 384, 128, 0.98),
    ])
    def test_vs_dense(self, r, c, block, sparsity):
        rng = np.random.default_rng(r * 7 + c)
        d = random_sparse_matrix(r, c, sparsity, rng).astype(np.float32)
        bs = BlockSparse.from_dense(d, block=block)
        op = BcsrMatmul(bs)
        x = jnp.asarray(rng.standard_normal((4, r)), jnp.float32)
        got = op(x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(x) @ d,
                                   rtol=1e-5, atol=1e-4)

    def test_vs_ref_oracle(self):
        rng = np.random.default_rng(5)
        d = random_sparse_matrix(256, 256, 0.97, rng).astype(np.float32)
        bs = BlockSparse.from_dense(d, block=128)
        op = BcsrMatmul(bs)
        x = jnp.asarray(rng.standard_normal((2, 256)), jnp.float32)
        want = bcsr_matmul_ref(x, op.data, np.asarray(op.cols),
                               np.asarray(op.rows), op.cols_pad, block=128)
        # rtol-only is too strict for near-zero sums whose accumulation
        # order differs between the kernel and the oracle.
        np.testing.assert_allclose(np.asarray(op(x)), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_bf16_inputs(self):
        rng = np.random.default_rng(6)
        d = random_sparse_matrix(256, 256, 0.95, rng).astype(np.float32)
        bs = BlockSparse.from_dense(d, block=128)
        op = BcsrMatmul(bs)
        x = jnp.asarray(rng.standard_normal((2, 256)), jnp.bfloat16)
        got = np.asarray(op(x), np.float32)
        want = np.asarray(x, np.float32) @ d
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)

    def test_all_zero(self):
        bs = BlockSparse.from_dense(np.zeros((256, 256), np.float32), 128)
        op = BcsrMatmul(bs)
        out = op(jnp.ones((2, 256)))
        np.testing.assert_array_equal(np.asarray(out), 0.0)

    def test_culling_reduces_tiles(self):
        d = np.zeros((512, 512), np.float32)
        d[:128, :128] = 1.0
        bs = BlockSparse.from_dense(d, block=128)
        op = BcsrMatmul(bs)
        # 1 data tile + 3 zero-padding tiles for empty output columns
        assert op.n_tiles == 4
        assert bs.n_blocks_nnz == 1


class TestReservoirStep:
    @pytest.mark.parametrize("dim,batch,block", [
        (128, 4, 128),
        (256, 2, 128),
        (256, 8, 64),
        (384, 1, 128),
    ])
    @pytest.mark.parametrize("leak", [1.0, 0.3])
    def test_vs_ref(self, dim, batch, block, leak):
        rng = np.random.default_rng(dim + batch)
        w = (rng.standard_normal((dim, dim)) * 0.05).astype(np.float32)
        w_in = rng.standard_normal((8, dim)).astype(np.float32) * 0.3
        fr = FusedReservoir(w, w_in, leak=leak, block=block)
        x = jnp.asarray(rng.standard_normal((batch, dim)), jnp.float32)
        u = jnp.asarray(rng.standard_normal((batch, 8)), jnp.float32)
        got = fr.step(x, u)
        want = reservoir_step_ref(x, jnp.asarray(w), u, jnp.asarray(w_in),
                                  leak=leak)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_sequence_scan_matches_esn(self):
        """Fused kernel trajectory == core ESN reference trajectory."""
        from repro.core.esn import ESNConfig, init_esn, run_reservoir
        cfg = ESNConfig(reservoir_dim=128, element_sparsity=0.8, seed=9,
                        block=64)
        p = init_esn(cfg)
        w = np.asarray(p.w.dense_f32())
        fr = FusedReservoir(w, np.asarray(p.w_in), leak=cfg.leak, block=128)
        t, b = 20, 2
        rng = np.random.default_rng(0)
        u = jnp.asarray(rng.standard_normal((t, b, 1)), jnp.float32)
        states_kernel = fr.run(u)
        states_ref = run_reservoir(p, u.transpose(1, 0, 2))
        np.testing.assert_allclose(np.asarray(states_kernel),
                                   np.asarray(states_ref).transpose(1, 0, 2),
                                   rtol=1e-4, atol=1e-4)


class TestPlatformRule:
    """How a kernel runs is derived from the platform, never passed in."""

    def test_cpu_interprets_and_tpu_compiles(self):
        from repro.kernels.platform import interpret_for
        assert interpret_for("cpu") is True
        assert interpret_for("tpu") is False

    def test_platform_without_rule_is_refused(self):
        from repro.kernels.platform import interpret_for
        with pytest.raises(RuntimeError, match="'gpu'"):
            interpret_for("gpu")

    def test_this_process_interprets(self):
        import jax
        from repro.kernels.platform import pallas_interpret
        assert jax.default_backend() == "cpu"
        assert pallas_interpret() is True
