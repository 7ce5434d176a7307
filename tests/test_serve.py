"""Serving layer: engine dispatch, padding buckets, readout, telemetry."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.esn import (ESNConfig, fit_readout, init_esn, predict,
                            run_reservoir)
from repro.serve import (PaddingBucketer, ReservoirEngine, RolloutRequest,
                         ServeStats, engine_for)


def _params(mode="fp32", dim=96, leak=1.0, seed=1, block=32):
    cfg = ESNConfig(reservoir_dim=dim, element_sparsity=0.8, mode=mode,
                    leak=leak, seed=seed, block=block)
    return init_esn(cfg)


def _trained_params(mode="fp32", dim=96, seed=1, block=32, out=2):
    cfg = ESNConfig(reservoir_dim=dim, element_sparsity=0.8, mode=mode,
                    leak=0.7, seed=seed, block=block, output_dim=out)
    p = init_esn(cfg)
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((50, 1)), jnp.float32)
    states = run_reservoir(p, u, engine="scan")
    y = jnp.concatenate([u, jnp.roll(u, 1)], axis=-1)
    return fit_readout(p, states, y, lam=1e-2)


class TestPaddingBucketer:
    def test_pad_len_picks_next_bucket(self):
        b = PaddingBucketer(len_buckets=(16, 32, 64), batch_buckets=(1, 2, 4))
        assert b.pad_len(3) == 16
        assert b.pad_len(16) == 16
        assert b.pad_len(17) == 32
        # beyond the top bucket: round up to a multiple of it
        assert b.pad_len(100) == 128

    def test_pad_batch(self):
        b = PaddingBucketer(len_buckets=(16,), batch_buckets=(1, 2, 4, 8))
        assert b.pad_batch(1) == 1
        assert b.pad_batch(3) == 4
        assert b.pad_batch(8) == 8

    def test_pad_batch_rounds_up_past_top_bucket(self):
        """Regression: beyond the top bucket the batch must round *up* to
        a multiple of it — never hand back a buffer smaller than the
        batch."""
        b = PaddingBucketer(len_buckets=(16,), batch_buckets=(1, 2, 4, 8))
        assert b.pad_batch(9) == 16
        assert b.pad_batch(16) == 16
        assert b.pad_batch(17) == 24
        assert all(b.pad_batch(n) >= n for n in range(1, 40))

    def test_group_shapes_and_padding(self):
        b = PaddingBucketer(len_buckets=(8, 16), batch_buckets=(1, 2, 4))
        rng = np.random.default_rng(0)
        reqs = [RolloutRequest(uid=i,
                               inputs=rng.standard_normal((t, 3)).astype(
                                   np.float32))
                for i, t in enumerate([5, 7, 12, 8, 3])]
        mbs = b.group(reqs)
        # lengths {5,7,8,3} -> bucket 8 (4 reqs, batch 4); {12} -> bucket 16
        assert sorted(mb.inputs.shape for mb in mbs) == [(1, 16, 3),
                                                         (4, 8, 3)]
        assert sum(mb.real_steps for mb in mbs) == 5 + 7 + 12 + 8 + 3
        assert sum(len(mb.requests) for mb in mbs) == 5
        # padded region is zeros; real region is the request data
        big = next(mb for mb in mbs if mb.inputs.shape[0] == 4)
        for j, req in enumerate(big.requests):
            np.testing.assert_array_equal(big.inputs[j, :req.length],
                                          req.inputs)
            assert not big.inputs[j, req.length:].any()

    def test_chunking_respects_max_batch(self):
        b = PaddingBucketer(len_buckets=(8,), batch_buckets=(1, 2))
        reqs = [RolloutRequest(uid=i, inputs=np.ones((4, 1), np.float32))
                for i in range(5)]
        mbs = b.group(reqs)
        assert [mb.inputs.shape[0] for mb in mbs] == [2, 2, 1]


class TestServeStats:
    def test_counters_and_efficiency(self):
        s = ServeStats()
        s.record_call(batch=4, steps=8, seconds=0.5, real_steps=20)
        s.record_call(batch=2, steps=8, seconds=0.5)
        assert s.calls == 2 and s.sequences == 6
        assert s.steps_padded == 48 and s.steps_real == 36
        assert s.padding_efficiency == pytest.approx(36 / 48)
        assert s.steps_per_sec == pytest.approx(48.0)
        assert s.goodput_steps_per_sec == pytest.approx(36.0)
        assert "steps/s" in s.render()

    def test_latency_ewma_tracks(self):
        s = ServeStats()
        s.record_call(batch=1, steps=1, seconds=1.0)
        assert s.latency_ewma_s == pytest.approx(1.0)
        s.record_call(batch=1, steps=1, seconds=0.0)
        assert 0.0 < s.latency_ewma_s < 1.0


class TestEngineParity:
    @pytest.mark.parametrize("mode", ["fp32", "int8-csd"])
    @pytest.mark.parametrize("leak", [1.0, 0.4])
    def test_xla_engine_matches_scan(self, mode, leak):
        p = _params(mode=mode, leak=leak)
        rng = np.random.default_rng(0)
        u = jnp.asarray(rng.standard_normal((4, 30, 1)), jnp.float32)
        want = np.asarray(run_reservoir(p, u, engine="scan"))
        got = np.asarray(ReservoirEngine(p).rollout(u))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_pallas_engine_matches_scan(self):
        p = _params(mode="int8-csd", leak=0.4)
        rng = np.random.default_rng(1)
        u = jnp.asarray(rng.standard_normal((2, 12, 1)), jnp.float32)
        want = np.asarray(run_reservoir(p, u, engine="scan"))
        got = np.asarray(ReservoirEngine(p, backend="pallas").rollout(u))
        np.testing.assert_array_equal(got, want)  # int8: bit-exact

    def test_single_sequence_shape_contract(self):
        p = _params()
        u = jnp.ones((20, 1), jnp.float32)
        got = ReservoirEngine(p).rollout(u)
        assert got.shape == (20, 96)

    def test_x0_vector_broadcasts(self):
        p = _params()
        rng = np.random.default_rng(2)
        u = jnp.asarray(rng.standard_normal((3, 10, 1)), jnp.float32)
        x0 = jnp.asarray(rng.uniform(-0.3, 0.3, (96,)), jnp.float32)
        want = np.asarray(run_reservoir(p, u, x0=x0, engine="scan"))
        got = np.asarray(ReservoirEngine(p).rollout(u, x0=x0))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_composes_under_jit_and_grad(self):
        """Engine dispatch must stay traceable (and not poison the
        per-params engine cache with tracers when first built under a
        trace)."""
        import jax
        p = _params()
        u = jnp.ones((2, 8, 1), jnp.float32)
        want = np.asarray(run_reservoir(p, u, engine="scan"))
        got = np.asarray(jax.jit(lambda x: run_reservoir(p, x))(u))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        # eager call after the traced one: cached engine still usable
        again = np.asarray(run_reservoir(p, u))
        np.testing.assert_allclose(again, want, rtol=1e-4, atol=1e-5)
        g = jax.grad(lambda x: run_reservoir(p, x).sum())(u)
        assert np.isfinite(np.asarray(g)).all()

    def test_run_reservoir_default_dispatches_to_engine(self):
        p = _params()
        u = jnp.ones((3, 10, 1), jnp.float32)
        got = np.asarray(run_reservoir(p, u))
        want = np.asarray(run_reservoir(p, u, engine="scan"))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        # the dispatching engine is cached on the params object
        assert engine_for(p) is engine_for(p)
        assert engine_for(p).stats.calls >= 1


class TestServeRequests:
    def test_ragged_requests_roundtrip(self):
        p = _params(dim=64, block=32, seed=3)
        eng = ReservoirEngine(p)
        rng = np.random.default_rng(3)
        reqs = [RolloutRequest(
                    uid=f"r{i}",
                    inputs=rng.standard_normal((t, 1)).astype(np.float32))
                for i, t in enumerate([5, 17, 17, 30, 9])]
        res = eng.serve(reqs, bucketer=PaddingBucketer(
            len_buckets=(8, 16, 32), batch_buckets=(1, 2, 4)))
        assert set(res) == {f"r{i}" for i in range(5)}
        for r in reqs:
            want = np.asarray(run_reservoir(p, jnp.asarray(r.inputs),
                                            engine="scan"))
            got = np.asarray(res[r.uid])
            assert got.shape == (r.length, 64)
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_serve_returns_predictions_with_trained_readout(self):
        """Acceptance: serve() answers with W_out applied in the fused
        epilogue, matching predict() over the scan-baseline states."""
        p = _trained_params(dim=64, block=32, seed=5)
        eng = ReservoirEngine(p)
        rng = np.random.default_rng(5)
        reqs = [RolloutRequest(
                    uid=i,
                    inputs=rng.standard_normal((t, 1)).astype(np.float32))
                for i, t in enumerate([6, 14, 9])]
        res = eng.serve(reqs, bucketer=PaddingBucketer(
            len_buckets=(8, 16), batch_buckets=(1, 2, 4)))
        for r in reqs:
            states = run_reservoir(p, jnp.asarray(r.inputs), engine="scan")
            want = np.asarray(predict(p, states))
            got = np.asarray(res[r.uid])
            assert got.shape == (r.length, 2)
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_serve_return_states_preserves_old_contract(self):
        p = _trained_params(dim=64, block=32, seed=6)
        eng = ReservoirEngine(p)
        req = RolloutRequest(uid="a", inputs=np.ones((7, 1), np.float32))
        with pytest.warns(DeprecationWarning, match="want_states"):
            res = eng.serve([req], return_states=True)
        assert res["a"].shape == (7, 64)
        want = np.asarray(run_reservoir(p, jnp.asarray(req.inputs),
                                        engine="scan"))
        np.testing.assert_allclose(np.asarray(res["a"]), want,
                                   rtol=1e-4, atol=1e-5)

    def test_serve_without_readout_falls_back_to_states(self):
        p = _params(dim=64, block=32)
        eng = ReservoirEngine(p)
        res = eng.serve([RolloutRequest(uid=0,
                                        inputs=np.ones((5, 1), np.float32))])
        assert res[0].shape == (5, 64)
        with pytest.raises(ValueError, match="readout not trained"):
            eng.predictions(jnp.ones((1, 5, 1), jnp.float32))

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_pallas_and_xla_serve_predictions_agree(self, backend):
        p = _trained_params(mode="int8-csd", dim=64, block=32, seed=7)
        eng = ReservoirEngine(p, backend=backend)
        rng = np.random.default_rng(7)
        u = jnp.asarray(rng.standard_normal((2, 8, 1)), jnp.float32)
        got = np.asarray(eng.predictions(u))
        want = np.asarray(predict(p, run_reservoir(p, u, engine="scan")))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


    def test_serve_honors_request_x0(self):
        """Regression: serve() used to drop initial state — a request's
        x0 must seed its row of the padded batch."""
        p = _params(dim=64, block=32, seed=11)
        eng = ReservoirEngine(p)
        rng = np.random.default_rng(11)
        u0 = rng.standard_normal((8, 1)).astype(np.float32)
        u1 = rng.standard_normal((8, 1)).astype(np.float32)
        x0 = rng.uniform(-0.4, 0.4, (64,)).astype(np.float32)
        bucketer = PaddingBucketer(len_buckets=(8,), batch_buckets=(2,))
        res = eng.serve([RolloutRequest(uid=0, inputs=u0),
                         RolloutRequest(uid=1, inputs=u1, x0=x0)],
                        bucketer=bucketer)
        # bit-identical to the same batched rollout with the x0 row seeded
        x0b = np.zeros((2, 64), np.float32)
        x0b[1] = x0
        want = np.asarray(eng.rollout(jnp.asarray(np.stack([u0, u1])),
                                      x0=jnp.asarray(x0b)))
        np.testing.assert_array_equal(np.asarray(res[0]), want[0])
        np.testing.assert_array_equal(np.asarray(res[1]), want[1])
        # requests without x0 still start from zero
        plain = eng.serve([RolloutRequest(uid=0, inputs=u0)],
                          bucketer=PaddingBucketer(len_buckets=(8,),
                                                   batch_buckets=(1,)))
        np.testing.assert_allclose(
            np.asarray(plain[0]),
            np.asarray(eng.rollout(jnp.asarray(u0))),
            rtol=1e-5, atol=1e-6)

    def test_padding_overhead_lands_in_stats(self):
        p = _params(dim=64, block=32)
        eng = ReservoirEngine(p)
        reqs = [RolloutRequest(uid=0,
                               inputs=np.ones((5, 1), np.float32))]
        eng.serve(reqs, bucketer=PaddingBucketer(len_buckets=(16,),
                                                 batch_buckets=(2,)))
        assert eng.stats.steps_real == 5
        assert eng.stats.steps_padded == 32
        assert eng.stats.padding_efficiency == pytest.approx(5 / 32)


class TestEngineCache:
    def test_engine_cache_reused_for_same_readout(self):
        p = _trained_params(dim=64, block=32, seed=8)
        assert engine_for(p) is engine_for(p)

    def test_engine_cache_invalidated_when_readout_replaced(self):
        """Satellite: engine_for must not serve a stale compiled rollout
        after the readout is swapped on the same params object."""
        p = _trained_params(dim=64, block=32, seed=9)
        eng_old = engine_for(p)
        u = jnp.asarray(np.random.default_rng(9).standard_normal((2, 6, 1)),
                        jnp.float32)
        old = np.asarray(eng_old.predictions(u))
        p.w_out = p.w_out * 2.0              # in-place readout replacement
        eng_new = engine_for(p)
        assert eng_new is not eng_old
        got = np.asarray(eng_new.predictions(u))
        np.testing.assert_allclose(got, 2.0 * old, rtol=1e-5, atol=1e-6)

    def test_fit_readout_produces_freshly_keyed_engine(self):
        p = _params(dim=64, block=32, seed=10)
        eng0 = engine_for(p)
        rng = np.random.default_rng(10)
        u = jnp.asarray(rng.standard_normal((30, 1)), jnp.float32)
        states = run_reservoir(p, u, engine="scan")
        p2 = fit_readout(p, states, jnp.concatenate([u, u], axis=-1),
                         lam=1e-2)
        eng1 = engine_for(p2)
        assert eng1 is not eng0
        assert eng1._w_out is p2.w_out


class TestEngineGuards:
    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_int8_state_wider_than_int8_is_refused(self, backend):
        """Every int8 recurrence multiplies the requantized state as an
        int8 operand, so a wider state is refused, never clipped."""
        cfg = ESNConfig(reservoir_dim=64, element_sparsity=0.8,
                        mode="int8-csd", state_bits=12, block=32)
        with pytest.raises(ValueError, match="state_bits"):
            ReservoirEngine(init_esn(cfg), backend=backend)

    def test_batch_tile_padding_lands_in_stats(self):
        """A 20-row batch runs as three 8-row tiles: 24 rows executed."""
        eng = ReservoirEngine(_params(dim=64), backend="pallas",
                              stats=ServeStats())
        assert eng.program.batch_tiling(20) == (8, 3, 24)
        eng.rollout(jnp.ones((20, 4, 1), jnp.float32))
        assert eng.stats.sequences == 24
        assert eng.stats.steps_real == 80
        assert eng.stats.padding_efficiency == pytest.approx(20 / 24)
