"""Work per step comes from the matrix alone; peaks only for known chips."""

import numpy as np
import pytest

import model
import work
from conftest import small_cfg


def test_work_counts_the_matrix():
    cfg = small_cfg(dim=256)
    q = np.zeros((256, 256), np.int8)
    q[0, :10] = 3
    q[5, 7] = -1
    w = work.work_of(q, cfg)
    assert w.nnz == 11
    assert w.ops_per_row_step == 2 * 11 + 2 * 256 * (1 + 1)
    assert w.ops(4, 16) == 4 * 16 * w.ops_per_row_step
    # weights once (int8 nonzeros, f32 W_in and W_out), state in and out,
    # each input and prediction once
    assert w.bytes(4, 16) == 11 + 4 * 256 * 2 + 2 * 4 * 4 * 256 + 4 * 4 * 16 * 2


@pytest.mark.parametrize("engine_kw", [
    {"backend": "xla"},
    {"backend": "pallas"},
    {"backend": "xla", "specialize": False},
    {"backend": "pallas", "crossover": 0},
    {"backend": "pallas", "batch_tile_max": 8},
], ids=["xla", "pallas", "xla-planes", "pallas-all-shiftadd", "pallas-tile8"])
def test_work_is_the_same_whatever_runs_it(engine_kw):
    """The program quantizes the benchmark's matrix to the same integers
    under every backend and schedule, so the work the roofline charges is
    one number per matrix."""
    import jax.numpy as jnp
    from repro.core.esn import ESNConfig, ESNParams
    from repro.core.sparse import FixedMatrix
    from repro.serve import ReservoirEngine

    cfg = small_cfg(dim=256)
    weights = model.make_weights(cfg)
    config = ESNConfig(reservoir_dim=256, element_sparsity=0.98,
                       mode="int8-csd")
    params = ESNParams(w=FixedMatrix.compile(weights.dense, mode="csd"),
                       w_in=jnp.asarray(weights.w_in),
                       w_out=jnp.asarray(weights.w_out), config=config)
    engine = ReservoirEngine(params, **engine_kw)
    served_q = np.asarray(engine.params.w.q)
    assert np.array_equal(served_q, weights.q)
    assert work.work_of(served_q, cfg) == work.work_of(weights.q, cfg)


def test_known_chip_peaks():
    p = work.peaks("TPU v5 lite")
    assert p == {"bf16_ops_per_s": 197e12, "int8_ops_per_s": 393e12,
                 "hbm_bytes_per_s": 819e9}
    assert work.compute_peak("TPU v5 lite", small_cfg()) == 393e12


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks(kind)


def test_bound_is_the_larger_of_compute_and_bandwidth():
    cfg = small_cfg(dim=256)
    w = work.Work(nnz=1000, reservoir_dim=256, input_dim=1, output_dim=1,
                  weight_bytes=1)
    b = work.bound_s(w, 16, 16, "TPU v5 lite", cfg)
    assert b == max(w.ops(16, 16) / 393e12, w.bytes(16, 16) / 819e9)
