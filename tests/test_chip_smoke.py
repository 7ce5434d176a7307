"""``chip_smoke.py`` at a tiny size on the CPU, and the compile cache.

The smoke's phases are plain functions, so the CPU runs them end to end
(Pallas interpreted, one device) — wrong paths, arguments and checks show
up here instead of on the chip.  ``main()`` itself must refuse the CPU.
"""

import importlib.util
import pathlib

import jax
import numpy as np
import pytest

from repro.core.esn import ESNConfig
from repro.kernels.reservoir_rollout import specialized
from repro.launch import cache

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


smoke = _load_smoke()

TINY = ESNConfig(reservoir_dim=128, element_sparsity=0.9, mode="int8-csd")
# the smallest reservoir whose int8 program has a shift-add term
SPARSE = ESNConfig(reservoir_dim=256, element_sparsity=0.98, mode="int8-csd")


@pytest.fixture(scope="module")
def tiny():
    params = smoke.build_params(TINY, seed=3, train_steps=64)
    inputs = smoke.make_requests(3, n=6, min_len=4, max_len=13)
    return params, inputs, smoke.reference(params, inputs)


@pytest.fixture(scope="module")
def sparse():
    params = smoke.build_params(SPARSE, seed=3, train_steps=64)
    inputs = smoke.make_requests(3, n=6, min_len=4, max_len=13)
    return params, inputs, smoke.reference(params, inputs)


class TestPhases:
    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_serve_phase(self, tiny, backend):
        params, inputs, refs = tiny
        report = smoke.serve_phase(
            f"tiny {backend}", params, inputs, refs, backend=backend,
            tol=smoke.INT8_TOL, expect_backend=backend, n_slots=4,
            chunk_steps=4)
        assert report["served"] == 6
        assert report["retraces_after_warmup"] == 0
        assert report["interpreted"] == (backend == "pallas")
        # on the CPU every path is the reference's arithmetic
        assert report["max_abs_diff"] < 1e-5

    # a shift of 1.0 breaks the max bound; 0.03 stays under it (0.05) but
    # breaks the RMS bound (0.01)
    @pytest.mark.parametrize("shift, broken", [(1.0, "max \\|diff\\|"),
                                               (0.03, "RMS diff")])
    def test_serve_phase_fails_over_bound(self, tiny, shift, broken):
        params, inputs, refs = tiny
        off = [r + shift for r in refs]
        with pytest.raises(smoke.SmokeFailure, match=broken):
            smoke.serve_phase("tiny off", params, inputs, off,
                              backend="xla", tol=smoke.INT8_TOL,
                              n_slots=4, chunk_steps=4)

    def test_serve_phase_shift_add(self, sparse):
        params, inputs, refs = sparse
        report = smoke.serve_phase("tiny shift-add", params, inputs, refs,
                                   backend="pallas", tol=smoke.INT8_TOL,
                                   n_slots=4, chunk_steps=4)
        assert report["shiftadd_terms"] > 0
        assert report["max_abs_diff"] < 1e-5

    # a kernel fault on the served path, planted in the specialized
    # kernel's shift-add terms: dropped, or added one bit too high
    @pytest.mark.parametrize("fault", ["drop", "wrong_shift"])
    def test_serve_phase_catches_shift_add_fault(self, sparse, monkeypatch,
                                                 fault):
        params, inputs, refs = sparse
        right = specialized._shift_add

        def planted(acc_ref, xi, base, digits):
            if fault == "wrong_shift":
                right(acc_ref, xi, base,
                      [(i, j, s, w + 1) for i, j, s, w in digits])

        monkeypatch.setattr(specialized, "_shift_add", planted)
        with pytest.raises(smoke.SmokeFailure,
                           match="max \\|diff\\| .*; RMS diff"):
            smoke.serve_phase("tiny fault", params, inputs, refs,
                              backend="pallas", tol=smoke.INT8_TOL,
                              n_slots=4, chunk_steps=4)

    def test_sharded_phase_one_device(self, tiny):
        params, inputs, _refs = tiny
        report = smoke.sharded_phase(params, inputs, n_shards=1,
                                     slots_per_shard=2, chunk_steps=4)
        assert report["rows_mismatched_vs_single_device"] == 0
        assert all(len(ids) == 1 for ids in report["device_sets"].values())

    def test_requests_are_seeded(self):
        a = smoke.make_requests(5, n=4, min_len=3, max_len=9)
        b = smoke.make_requests(5, n=4, min_len=3, max_len=9)
        assert all(np.array_equal(x, y) for x, y in zip(a, b))
        assert all(3 <= x.shape[0] <= 9 for x in a)


def test_main_refuses_the_cpu(monkeypatch, capsys):
    monkeypatch.setattr(smoke, "enable_compile_cache", lambda: "unused")
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err
    assert '"ok"' not in out


class TestCompileCache:
    def test_env_dir_is_left_to_jax(self, monkeypatch):
        calls = []
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        assert cache.enable_compile_cache() == "/elsewhere/cache"
        assert calls == []

    def test_fixed_checkout_dir_otherwise(self, monkeypatch):
        calls = []
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        path = cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert calls == [("jax_compilation_cache_dir", path)]
