"""Continuous-batching scheduler: slot pool, admission, chunked parity.

The load-bearing contract is the acceptance criterion: a chunked
scheduler rollout — slot pool, ``chunk_steps`` segments, reservoir state
carried between chunks — must be *bit-identical* to the one-shot engine
rollout of the same inputs, for states and for fused-readout
predictions, on both backends.  Bit-identity holds when the batch shapes
match (the pool rolls a fixed ``(n_slots, chunk_steps, I)`` shape and
rows never mix), so those tests pin ``n_slots`` to the request count.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.esn import (ESNConfig, fit_readout, init_esn, run_reservoir)
from repro.serve import (AsyncReservoirServer, ContinuousBatcher,
                         ReservoirEngine, RolloutRequest, ServeStats,
                         SubmitSpec)


def _params(mode="fp32", dim=96, leak=0.7, seed=1, block=32, trained=True):
    cfg = ESNConfig(reservoir_dim=dim, element_sparsity=0.8, mode=mode,
                    leak=leak, seed=seed, block=block, output_dim=2)
    p = init_esn(cfg)
    if trained:
        rng = np.random.default_rng(seed)
        u = jnp.asarray(rng.standard_normal((50, 1)), jnp.float32)
        states = run_reservoir(p, u, engine="scan")
        y = jnp.concatenate([u, jnp.roll(u, 1)], axis=-1)
        p = fit_readout(p, states, y, lam=1e-2)
    return p


def _requests(lengths, seed=0, in_dim=1):
    rng = np.random.default_rng(seed)
    return [SubmitSpec(rng.standard_normal((t, in_dim)).astype(np.float32),
                       uid=i)
            for i, t in enumerate(lengths)]


def _server(p, backend="xla", **kw):
    eng = ReservoirEngine(p, backend=backend, stats=ServeStats())
    kw.setdefault("chunk_time", 1.0)        # deterministic virtual clock
    return eng, AsyncReservoirServer(eng, **kw)


class TestEngineChunkAPI:
    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_final_state_is_last_state(self, backend):
        p = _params(trained=False)
        rng = np.random.default_rng(0)
        u = jnp.asarray(rng.standard_normal((3, 8, 1)), jnp.float32)
        res = ReservoirEngine(p, backend=backend).submit(
            SubmitSpec(u, want_states=True))
        np.testing.assert_array_equal(np.asarray(res.final_state),
                                      np.asarray(res.states)[:, -1])

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_chunk_resume_bit_identical(self, backend):
        p = _params()
        eng = ReservoirEngine(p, backend=backend)
        rng = np.random.default_rng(1)
        u = jnp.asarray(rng.standard_normal((2, 16, 1)), jnp.float32)
        z = jnp.zeros((2, 96), jnp.float32)
        full = np.asarray(eng.rollout(u))
        s1, xf = eng.run_segment(u[:, :8], z, want_states=True)
        s2, _ = eng.run_segment(u[:, 8:], xf, want_states=True)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(s1), np.asarray(s2)], axis=1), full)
        pfull = np.asarray(eng.predictions(u))
        p1, xf = eng.run_segment(u[:, :8], z)
        p2, _ = eng.run_segment(u[:, 8:], xf)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(p1), np.asarray(p2)], axis=1), pfull)

    def test_single_sequence_final_state_shape(self):
        p = _params(trained=False)
        res = ReservoirEngine(p).submit(
            SubmitSpec(jnp.ones((10, 1), jnp.float32), want_states=True))
        assert res.states.shape == (10, 96)
        assert res.final_state.shape == (96,)
        assert res.output is res.states and res.preds is None


class TestChunkedParity:
    """Acceptance: chunked scheduler == one-shot engine, bit for bit."""

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    @pytest.mark.parametrize("want_states", [True, False])
    def test_scheduler_bit_identical_to_one_shot(self, backend,
                                                 want_states):
        p = _params(mode="fp32")
        eng = ReservoirEngine(p, backend=backend, stats=ServeStats())
        n, t = 4, 24
        reqs = _requests([t] * n, seed=2)
        srv = AsyncReservoirServer(eng, n_slots=n, chunk_steps=8,
                                   want_states=want_states,
                                   chunk_time=1.0)
        for r in reqs:
            srv.submit(r, arrival_time=0.0)
        res = srv.run()
        batch = jnp.asarray(np.stack([r.inputs for r in reqs]))
        one_shot = np.asarray(eng.rollout(batch) if want_states
                              else eng.predictions(batch))
        for i, r in enumerate(reqs):
            np.testing.assert_array_equal(res[r.uid].output, one_shot[i])

    def test_int8_scheduler_bit_identical(self):
        p = _params(mode="int8-csd")
        eng = ReservoirEngine(p, stats=ServeStats())
        reqs = _requests([16, 16], seed=3)
        srv = AsyncReservoirServer(eng, n_slots=2, chunk_steps=4,
                                   chunk_time=1.0)
        for r in reqs:
            srv.submit(r)
        res = srv.run()
        batch = jnp.asarray(np.stack([r.inputs for r in reqs]))
        one_shot = np.asarray(eng.predictions(batch))
        for i, r in enumerate(reqs):
            np.testing.assert_array_equal(res[r.uid].output, one_shot[i])

    def test_ragged_lengths_match_per_request_rollout(self):
        """Mixed lengths + mid-chunk retirement: allclose vs the engine's
        own per-request rollout (batch shape differs, so fp accumulation
        may differ by ~1 ulp)."""
        p = _params()
        eng = ReservoirEngine(p, stats=ServeStats())
        reqs = _requests([5, 17, 30, 9, 12, 23], seed=4)
        srv = AsyncReservoirServer(eng, n_slots=3, chunk_steps=8,
                                   chunk_time=1.0)
        for i, r in enumerate(reqs):
            srv.submit(r, arrival_time=0.5 * i)
        res = srv.run()
        for r in reqs:
            want = np.asarray(eng.predictions(jnp.asarray(r.inputs)))
            np.testing.assert_allclose(res[r.uid].output, want,
                                       rtol=1e-4, atol=1e-6)


class TestAdmission:
    def test_fifo_under_full_pool(self):
        """More arrivals than slots: seats are granted strictly in
        (arrival_time, submission) order as they free up."""
        p = _params()
        eng, srv = _server(p, n_slots=2, chunk_steps=8)
        qreqs = [srv.submit(r, arrival_time=0.0)
                 for r in _requests([8] * 5, seed=5)]
        srv.run()
        admits = [q.admit_time for q in qreqs]
        assert admits == sorted(admits)
        # exactly the pool width is seated at t=0; the rest wait
        assert admits[0] == admits[1] == 0.0
        assert all(a > 0.0 for a in admits[2:])
        finishes = [q.finish_time for q in qreqs]
        assert finishes == sorted(finishes)
        assert eng.stats.admitted == 5 and eng.stats.completed == 5

    def test_late_arrival_not_admitted_early(self):
        p = _params()
        _, srv = _server(p, n_slots=2, chunk_steps=8)
        early = srv.submit(_requests([8], seed=6)[0], arrival_time=0.0)
        late = srv.submit(
            SubmitSpec(np.ones((8, 1), np.float32), uid="late"),
            arrival_time=10.0)
        srv.run()
        assert early.admit_time == 0.0
        # pool was free the whole time — the clock, not capacity, gated it
        assert late.admit_time >= 10.0

    def test_mid_flight_admit_with_zero_state(self):
        """A request seated while another sequence is mid-rollout starts
        from the zero state and serves correctly."""
        p = _params()
        eng = ReservoirEngine(p, stats=ServeStats())
        srv = AsyncReservoirServer(eng, n_slots=2, chunk_steps=8,
                                   chunk_time=1.0)
        long = srv.submit(SubmitSpec(
            np.ones((40, 1), np.float32), uid="long"), arrival_time=0.0)
        short = srv.submit(SubmitSpec(
            np.ones((8, 1), np.float32), uid="short"), arrival_time=0.0)
        mid = srv.submit(SubmitSpec(
            np.full((8, 1), 0.5, np.float32), uid="mid"), arrival_time=1.5)
        res = srv.run()
        assert short.uid == "short"
        # "mid" was seated after "short" retired, while "long" was live
        assert mid.admit_time > 0.0
        assert mid.admit_time < long.finish_time
        want = np.asarray(eng.predictions(
            jnp.full((8, 1), 0.5, jnp.float32)))
        np.testing.assert_allclose(res["mid"].output, want,
                                   rtol=1e-4, atol=1e-6)

    def test_request_x0_seeds_slot_state(self):
        p = _params()
        eng = ReservoirEngine(p, stats=ServeStats())
        srv = AsyncReservoirServer(eng, n_slots=1, chunk_steps=8,
                                   chunk_time=1.0)
        x0 = np.full((96,), 0.2, np.float32)
        u = np.ones((8, 1), np.float32)
        srv.submit(SubmitSpec(u, uid=0, x0=x0))
        res = srv.run()
        want = np.asarray(eng.predictions(
            jnp.asarray(u)[None], x0=jnp.asarray(x0)[None]))[0]
        np.testing.assert_array_equal(res[0].output, want)


class TestQueueStats:
    def test_queue_wait_and_ttfp_accounting(self):
        """Virtual clock with chunk_time=1: waits are exact integers."""
        p = _params()
        eng, srv = _server(p, n_slots=1, chunk_steps=8)
        q0 = srv.submit(_requests([8], seed=7)[0], arrival_time=0.0)
        q1 = srv.submit(
            SubmitSpec(np.ones((8, 1), np.float32), uid=1),
            arrival_time=0.0)
        srv.run()
        s = eng.stats
        # q0 seats immediately; q1 waits one full chunk for the slot
        assert (q0.admit_time, q1.admit_time) == (0.0, 1.0)
        assert s.queue_wait_max_s == pytest.approx(1.0)
        assert s.mean_queue_wait_s == pytest.approx(0.5)
        # first predictions land at the end of each request's first chunk
        assert q0.first_output_time == pytest.approx(1.0)
        assert q1.first_output_time == pytest.approx(2.0)
        assert s.mean_ttfp_s == pytest.approx(1.5)
        assert s.ttfp_max_s == pytest.approx(2.0)
        assert s.enqueued == 2 and s.admitted == 2 and s.completed == 2
        assert s.chunks == 2 and s.slot_occupancy == pytest.approx(1.0)

    def test_idle_pool_fast_forwards_clock(self):
        p = _params()
        eng, srv = _server(p, n_slots=2, chunk_steps=8)
        q = srv.submit(_requests([8], seed=8)[0], arrival_time=7.25)
        srv.run()
        # no queue wait: the server jumped to the arrival instead of
        # charging idle time against the request
        assert q.admit_time == pytest.approx(7.25)
        assert eng.stats.queue_wait_max_s == pytest.approx(0.0)
        assert srv.now == pytest.approx(8.25)

    def test_occupancy_reflects_free_slots(self):
        p = _params()
        eng, srv = _server(p, n_slots=4, chunk_steps=8)
        srv.submit(_requests([8], seed=9)[0], arrival_time=0.0)
        srv.run()
        # one live slot of four for the single chunk
        assert eng.stats.slot_occupancy == pytest.approx(0.25)
        assert "occupancy" in eng.stats.render()
        assert "slot_occupancy" in eng.stats.summary()

    def test_occupancy_discounts_retiring_tail(self):
        """A sequence that finishes mid-chunk only counts its real steps —
        the zero-padded tail of its final chunk is not 'live' work."""
        p = _params()
        eng, srv = _server(p, n_slots=1, chunk_steps=16)
        srv.submit(_requests([4], seed=12)[0], arrival_time=0.0)
        srv.run()
        assert eng.stats.slot_occupancy == pytest.approx(4 / 16)

    def test_results_and_drained_flag(self):
        p = _params()
        _, srv = _server(p, n_slots=2, chunk_steps=8)
        assert srv.drained and not srv.step()
        srv.submit(_requests([4], seed=10)[0])
        assert not srv.drained
        res = srv.run()
        assert srv.drained and set(res) == {0}
        assert res[0].output.shape == (4, 2)
        assert res[0].timings["latency_s"] > 0.0


class TestDeadlines:
    def test_expired_queued_request_dropped(self):
        """Pool of one: the second request's deadline passes while it
        queues, so it is dropped — counted, never seated — and the slot
        goes to the third request instead."""
        p = _params()
        eng, srv = _server(p, n_slots=1, chunk_steps=8)
        held = srv.submit(_requests([16], seed=20)[0], arrival_time=0.0)
        doomed = srv.submit(
            SubmitSpec(np.ones((8, 1), np.float32), uid="doomed",
                       deadline=0.5),
            arrival_time=0.0)
        patient = srv.submit(
            SubmitSpec(np.ones((8, 1), np.float32), uid="patient"),
            arrival_time=0.0)
        res = srv.run()
        assert "doomed" not in res
        assert doomed.admit_time is None and doomed.finish_time is None
        assert set(res) == {held.uid, "patient"}
        s = eng.stats
        assert s.timed_out == 1
        assert s.enqueued == 3 and s.admitted == 2 and s.completed == 2
        assert "1 timed out" in s.render()

    def test_deadline_met_is_served(self):
        p = _params()
        _, srv = _server(p, n_slots=1, chunk_steps=8)
        q = srv.submit(_requests([8], seed=21)[0], arrival_time=0.0,
                       deadline=5.0)
        res = srv.run()
        assert q.finish_time is not None and 0 in res

    def test_admitted_request_runs_past_deadline(self):
        """A deadline bounds the queue wait, not the service time: once
        seated, the rollout completes even if it outlives the deadline."""
        p = _params()
        _, srv = _server(p, n_slots=1, chunk_steps=8)
        q = srv.submit(_requests([32], seed=22)[0], arrival_time=0.0,
                       deadline=1.5)            # 4 chunks > deadline
        res = srv.run()
        assert q.finish_time == pytest.approx(4.0)
        assert res[0].output.shape == (32, 2)

    def test_all_expired_queue_drains(self):
        """A queue holding only expired requests drains without running
        chunks for them (and run() terminates)."""
        p = _params()
        eng, srv = _server(p, n_slots=1, chunk_steps=8)
        srv.submit(_requests([24], seed=23)[0], arrival_time=0.0)
        for i in range(3):
            srv.submit(SubmitSpec(
                np.ones((8, 1), np.float32), uid=f"late{i}", deadline=1.0),
                arrival_time=0.0)
        res = srv.run()
        assert set(res) == {0}
        assert eng.stats.timed_out == 3
        # only the first request's chunks ran
        assert eng.stats.chunks == 3


class TestContinuousBatcherUnit:
    def test_slot_reuse_and_retire(self):
        p = _params()
        eng = ReservoirEngine(p, stats=ServeStats())
        cb = ContinuousBatcher(eng, n_slots=2, chunk_steps=4)
        from repro.serve.scheduler import QueuedRequest
        a = QueuedRequest(RolloutRequest(
            uid="a", inputs=np.ones((4, 1), np.float32)))
        b = QueuedRequest(RolloutRequest(
            uid="b", inputs=np.ones((12, 1), np.float32)))
        assert cb.admit(a) == 0 and cb.admit(b) == 1
        assert not cb.has_free_slot() and cb.live == 2
        retired, real = cb.run_chunk()
        assert [q.uid for q, _ in retired] == ["a"]
        assert real == 8                        # both slots fully live
        assert cb.has_free_slot() and cb.live == 1
        c = QueuedRequest(RolloutRequest(
            uid="c", inputs=np.ones((4, 1), np.float32)))
        assert cb.admit(c) == 0                 # freed slot is reused
        retired, real = cb.run_chunk()
        assert [q.uid for q, _ in retired] == ["c"]
        retired, real = cb.run_chunk()
        (qb, out_b), = retired
        assert qb.uid == "b" and out_b.shape == (12, 2)
        assert real == 4                        # b's last 4 of 12 steps


class TestRecompilationGuard:
    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_n_chunks_trace_once_per_shape(self, backend):
        """Rolling N chunks through the async server must trace the
        rollout exactly once per (shape, regime) — a cache-key regression
        that recompiles per chunk fails this immediately."""
        p = _params()
        eng, srv = _server(p, backend=backend, n_slots=4, chunk_steps=4)
        for r in _requests([16, 16, 16, 16, 16, 16], seed=5):
            srv.submit(r)
        srv.run()
        assert eng.stats.chunks >= 6            # plenty of chunks ran...
        counts = eng.trace_counts
        assert counts, "trace counter never ticked"
        assert all(n == 1 for n in counts.values()), dict(counts)
        assert len(counts) == 1                 # ...over ONE chunk shape

    def test_trace_count_grows_only_on_new_shape(self):
        p = _params()
        eng = ReservoirEngine(p, stats=ServeStats())
        u1 = jnp.zeros((2, 4, 1), jnp.float32)
        z = jnp.zeros((2, 96), jnp.float32)
        eng.run_segment(u1, z)
        eng.run_segment(u1, z)
        assert sum(eng.trace_counts.values()) == 1
        eng.run_segment(jnp.zeros((2, 8, 1), jnp.float32), z)
        assert sum(eng.trace_counts.values()) == 2


    @pytest.mark.parametrize("zero_copy", [False, True])
    def test_steady_state_compiles_no_new_write(self, zero_copy):
        """Construction compiles the pool write at every stack size; a
        server seating sweeps of every size compiles none after that,
        and a lane growth compiles each size once more."""
        from repro.serve.scheduler import _STACKS
        p = _params()
        _, srv = _server(p, n_slots=24, chunk_steps=4, zero_copy=zero_copy)
        write = srv.batcher._pool_write
        assert write._cache_size() == len(_STACKS)
        rng = np.random.default_rng(14)
        t = 0.0
        for burst in (1, 2, 3, 5, 9, 16, 24, 4):
            for _ in range(burst):
                srv.submit(SubmitSpec(rng.standard_normal(
                    (int(rng.integers(1, 17)), 1)).astype(np.float32)),
                    arrival_time=t)
            t += 6.0
        srv.run()
        assert srv.stats.completed == 64
        assert write._cache_size() == len(_STACKS)
        srv.submit(SubmitSpec(np.ones((40, 1), np.float32)))
        srv.run()
        assert write._cache_size() == len(_STACKS) * (1 + zero_copy)


def _qreq(uid, steps, x0=None, seed=0):
    from repro.serve.scheduler import QueuedRequest
    rng = np.random.default_rng(seed)
    return QueuedRequest(RolloutRequest(
        uid=uid, inputs=rng.standard_normal((steps, 1)).astype(np.float32),
        x0=x0))


class TestBatchedAdmission:
    """A sweep's admissions are written to the device in stacks, one
    transfer and one pool-write program per stack: the pool must come out
    bit-identical to seating and writing each request on its own."""

    @staticmethod
    def _pool(cb):
        lanes = None if cb._u_dev is None else np.asarray(cb._u_dev)
        return lanes, np.asarray(cb._states)

    @pytest.mark.parametrize("zero_copy", [False, True])
    @pytest.mark.parametrize("with_x0", [False, True])
    @pytest.mark.parametrize("grow", [False, True])
    @pytest.mark.parametrize("k", [1, 3, 8, 17, 40])
    def test_sweep_pool_equals_per_request_writes(self, k, grow, with_x0,
                                                  zero_copy):
        p = _params()
        cs, dim, resident = 4, 96, 2
        rng = np.random.default_rng(k)
        # the sweep's middle request outgrows the 4 starting lanes
        lengths = [int(rng.integers(1, 4 * cs + 1)) for _ in range(k)]
        if grow:
            lengths[k // 2] = 9 * cs + 1
        x0s = [rng.standard_normal(dim).astype(np.float32)
               if with_x0 and i % 2 == 0 else None for i in range(k)]

        def seat(per_request):
            eng = ReservoirEngine(p, stats=ServeStats())
            cb = ContinuousBatcher(eng, n_slots=resident + k + 3,
                                   chunk_steps=cs, zero_copy=zero_copy,
                                   warm=False)
            for i in range(resident):
                cb.admit(_qreq(f"r{i}", 6, x0=np.full(dim, 0.5, np.float32),
                               seed=100 + i))
            cb.flush()
            writes = 0
            for i in range(k):
                cb.admit(_qreq(i, lengths[i], x0=x0s[i], seed=i))
                if per_request:
                    writes += cb.flush()[0]
            writes += cb.flush()[0]
            assert cb.flush() == (0, 0)         # nothing left staged
            return cb, writes

        alone, n_alone = seat(per_request=True)
        swept, n_swept = seat(per_request=False)
        assert n_alone == k and n_swept == -(-k // 16)
        (lanes_a, states_a), (lanes_s, states_s) = map(
            self._pool, (alone, swept))
        np.testing.assert_array_equal(states_s, states_a)
        # the host reference: each seated slot holds its x0 (or zeros)
        # and its zero-padded input, every other slot is untouched
        want = np.zeros_like(states_s)
        want[:resident] = 0.5
        for i, x0 in enumerate(x0s):
            if x0 is not None:
                want[resident + i] = x0
        np.testing.assert_array_equal(states_s, want)
        if not zero_copy:
            assert lanes_s is None and lanes_a is None
            return
        np.testing.assert_array_equal(lanes_s, lanes_a)
        assert swept._max_chunks == (16 if grow else 4)
        for i in range(k):
            flat = lanes_s[resident + i].reshape(-1, 1)
            np.testing.assert_array_equal(
                flat[: lengths[i]], _qreq(i, lengths[i], seed=i).request.inputs)
            assert not flat[lengths[i]:].any()
        assert not lanes_s[resident + k:].any()

    @pytest.mark.parametrize("zero_copy", [False, True])
    def test_lone_admit_then_run_chunk(self, zero_copy):
        """A direct ``admit`` stays staged until the pool is read:
        ``run_chunk`` writes it first and rolls it from its ``x0``."""
        p = _params()
        eng = ReservoirEngine(p, stats=ServeStats())
        cb = ContinuousBatcher(eng, n_slots=2, chunk_steps=8,
                               zero_copy=zero_copy)
        x0 = np.full((96,), 0.3, np.float32)
        q = _qreq("a", 8, x0=x0, seed=3)
        u = q.request.inputs.copy()
        assert cb.admit(q) == 0
        assert len(cb._staged) == 1
        if zero_copy:
            q.request.inputs[:] = 999.0     # copied at admit, not at write
        (qr, out), = cb.run_chunk()[0]
        assert qr.uid == "a" and not cb._staged
        batch = np.zeros((2, 8, 1), np.float32)
        batch[0] = u
        x0s = np.zeros((2, 96), np.float32)
        x0s[0] = x0
        want, _ = eng.run_segment(jnp.asarray(batch), jnp.asarray(x0s))
        np.testing.assert_array_equal(out, np.asarray(want)[0])


class TestZeroCopyServing:
    def test_host_syncs_only_at_retirement(self):
        """The zero-copy hot loop defers every device->host transfer to
        slot retirement: chunks that retire nothing sync nothing."""
        p = _params()
        eng = ReservoirEngine(p, stats=ServeStats())
        cb = ContinuousBatcher(eng, n_slots=2, chunk_steps=4,
                               zero_copy=True)
        from repro.serve.scheduler import QueuedRequest
        cb.admit(QueuedRequest(RolloutRequest(
            uid="a", inputs=np.ones((12, 1), np.float32))))
        cb.admit(QueuedRequest(RolloutRequest(
            uid="b", inputs=np.ones((8, 1), np.float32))))
        retired, _ = cb.run_chunk()             # nobody finishes...
        assert not retired
        assert cb.host_syncs == 0               # ...so nothing synced
        retired, _ = cb.run_chunk()             # b retires at step 8
        assert [q.uid for q, _ in retired] == ["b"]
        assert cb.host_syncs == 2               # b's two chunk buffers
        retired, _ = cb.run_chunk()             # a retires at step 12
        assert [q.uid for q, _ in retired] == ["a"]
        # a's first two buffers were already synced by b's retirement
        # (shared chunk buffers sync at most once); only chunk 3 is new
        assert cb.host_syncs == 3

    def test_shared_chunk_buffer_syncs_once(self):
        p = _params()
        eng, srv = _server(p, n_slots=2, chunk_steps=4, zero_copy=True)
        for r in _requests([8, 8], seed=6):     # same slots, same chunks
            srv.submit(r)
        res = srv.run()
        assert len(res) == 2
        # 2 chunks ran; both retirements share the same 2 buffers
        assert srv.batcher.host_syncs == 2
        assert srv.batcher.host_syncs <= eng.stats.chunks

    def test_zero_copy_output_matches_legacy_path(self):
        p = _params()
        outs = {}
        for zero_copy in (False, True):
            eng = ReservoirEngine(p, stats=ServeStats())
            batcher = ContinuousBatcher(eng, n_slots=3, chunk_steps=4,
                                        zero_copy=zero_copy)
            srv = AsyncReservoirServer(eng, batcher=batcher, chunk_time=1.0)
            for r in _requests([10, 7, 13], seed=7):
                srv.submit(r)
            outs[zero_copy] = srv.run()
        assert set(outs[True]) == set(outs[False])
        for uid in outs[True]:
            assert (outs[True][uid].output == outs[False][uid].output).all()

    def test_sharded_server_zero_copy_passthrough(self):
        """The sharded server exposes the same zero_copy knob and serves
        identical outputs either way (carried across a shrink rebuild
        via the batcher's resolved flag)."""
        from repro.dist import (DistributedReservoirServer,
                                ShardedReservoirEngine)
        p = _params()
        outs = {}
        for zc in (False, True):
            eng = ShardedReservoirEngine(p, n_shards=1, stats=ServeStats())
            srv = DistributedReservoirServer(
                eng, slots_per_shard=2, chunk_steps=4, chunk_time=1.0,
                zero_copy=zc, stats=ServeStats())
            assert srv.batcher.zero_copy is zc
            for r in _requests([10, 6, 7], seed=9):
                srv.submit(r)
            outs[zc] = srv.run()
        assert set(outs[True]) == set(outs[False])
        for uid in outs[True]:
            assert (outs[True][uid].output == outs[False][uid].output).all()

    def test_shrink_snapshot_survives_host_input_mutation(self):
        """Elastic shrink must carry a sequence's remaining inputs from
        the device-resident lane, not the host buffer — the zero-copy
        contract frees the caller's array the moment admit() uploads it."""
        from repro.dist import (DistributedReservoirServer,
                                ShardedReservoirEngine)
        p = _params()
        rng = np.random.default_rng(11)
        inputs = rng.standard_normal((24, 1)).astype(np.float32)

        def serve(mutate):
            buf = inputs.copy()
            eng = ShardedReservoirEngine(p, n_shards=1, stats=ServeStats())
            srv = DistributedReservoirServer(
                eng, slots_per_shard=1, chunk_steps=4, chunk_time=1.0,
                zero_copy=True, stats=ServeStats())
            srv.submit(SubmitSpec(buf, uid="m"))
            srv.step()                          # one chunk consumed
            if mutate:
                buf[:] = 999.0                  # host buffer is dead
            srv.shrink(0)                       # snapshot + re-admission
            return np.asarray(srv.run()["m"].output)

        clean = serve(mutate=False)
        mutated = serve(mutate=True)
        assert (clean == mutated).all()

    def test_deferred_calls_flagged_in_stats(self):
        p = _params()
        eng, srv = _server(p, n_slots=2, chunk_steps=4, zero_copy=True)
        for r in _requests([8, 8], seed=10):
            srv.submit(r)
        srv.run()
        assert eng.stats.deferred_calls == eng.stats.chunks > 0
        assert "deferred_calls" in eng.stats.summary()
        # legacy path records fully-synced calls, never flags
        eng2, srv2 = _server(p, n_slots=2, chunk_steps=4, zero_copy=False)
        for r in _requests([8, 8], seed=10):
            srv2.submit(r)
        srv2.run()
        assert eng2.stats.deferred_calls == 0
        assert "deferred_calls" not in eng2.stats.summary()

    def test_device_resident_inputs_single_upload(self):
        """Admission moves the request's whole input to the device once;
        run_chunk never touches the host copy again (mutating it after
        admission must not change the output)."""
        p = _params()
        eng = ReservoirEngine(p, stats=ServeStats())
        from repro.serve.scheduler import QueuedRequest
        rng = np.random.default_rng(8)
        inputs = rng.standard_normal((8, 1)).astype(np.float32)
        ref = eng.predictions(jnp.asarray(inputs)[None])[0]
        cb = ContinuousBatcher(eng, n_slots=1, chunk_steps=4,
                               zero_copy=True)
        q = QueuedRequest(RolloutRequest(uid="z", inputs=inputs))
        cb.admit(q)
        inputs[:] = 999.0                       # host buffer is dead now
        retired, _ = cb.run_chunk()
        assert not retired
        (qr, out), = cb.run_chunk()[0]
        assert qr.uid == "z"
        assert np.allclose(out, np.asarray(ref))

    @pytest.mark.parametrize("n_slots,chunk_steps", [(8, 4), (11, 3)])
    def test_sync_rewrites_only_the_chunks_riders(self, n_slots,
                                                  chunk_steps):
        """A sync visits the synced chunk's riders only (at most
        ``n_slots`` entries), leaves no entry pointing at a synced chunk,
        and serves the host path's outputs bit for bit."""
        from repro import obs
        from repro.serve.scheduler import _DeviceChunk
        p = _params()
        lengths = [5 + (7 * i) % 29 for i in range(3 * n_slots)]
        outs = {}
        for zero_copy in (False, True):
            obs.configure()
            try:
                _, srv = _server(p, n_slots=n_slots,
                                 chunk_steps=chunk_steps,
                                 zero_copy=zero_copy)
                for r in _requests(lengths, seed=12):
                    srv.submit(r)
                while srv.step():
                    for entries in srv.batcher._chunks:
                        for e in entries:
                            assert (not isinstance(e[0], _DeviceChunk)
                                    or e[0].dev is not None)
                outs[zero_copy] = srv.results
                tr = obs.tracer()
            finally:
                obs.disable()
            assert tr.dropped == 0
        syncs = tr.spans(name="scheduler.sync")
        retires = tr.spans(name="scheduler.retire")
        assert sum(s.attrs["retired"] for s in retires) == len(lengths)
        for r in retires:
            n_sync = sum(s.parent == "scheduler.retire"
                         and r.start <= s.start <= r.end for s in syncs)
            assert r.attrs["entries_walked"] <= n_slots * n_sync
        assert srv.batcher._walked > 0
        assert set(outs[True]) == set(outs[False]) == set(range(len(lengths)))
        for uid in outs[True]:
            np.testing.assert_array_equal(np.asarray(outs[True][uid].output),
                                          np.asarray(outs[False][uid].output))

    def test_multi_tenant_chunk_riders_are_their_groups_slots(
            self, monkeypatch):
        """Two engines in one zero-copy pool: each chunk launches one
        device buffer per engine, whose riders are exactly that engine's
        slots, and every request's output equals a single-tenant pool's."""
        from repro.serve import ModelRegistry
        from repro.serve import scheduler as sched
        pA, pB = _params(seed=1), _params(seed=2, leak=0.55)
        n_slots, cs = 8, 4
        lengths = [6 + (5 * i) % 19 for i in range(14)]
        reqs = _requests(lengths, seed=13)
        models = ["A" if i % 3 else "B" for i in range(len(reqs))]
        reg = ModelRegistry(backend="xla")
        reg.register("A", pA)
        reg.register("B", pB)

        def serve(model_of):
            eng = reg.engine("A")
            eng.stats = ServeStats()
            srv = AsyncReservoirServer(eng, n_slots=n_slots, chunk_steps=cs,
                                       chunk_time=1.0, zero_copy=True,
                                       registry=reg)
            for r, m in zip(reqs, models):
                if model_of is None or m == model_of:
                    srv.submit(SubmitSpec(r.inputs, uid=r.uid, model=m))
            return srv

        made, riders = [], {}

        class Recording(sched._DeviceChunk):
            __slots__ = ()

            def __init__(self, dev):
                super().__init__(dev)
                made.append(self)

        srv = serve(None)
        cb = srv.batcher
        materialize = cb._materialize

        def spy(chunk):
            riders[id(chunk)] = sorted(s for s, _j in chunk.riders)
            materialize(chunk)

        cb._materialize = spy
        monkeypatch.setattr(sched, "_DeviceChunk", Recording)
        mixed = 0
        while True:
            before = len(made)
            if not srv.step():
                break
            new = made[before:]
            if not new:
                continue
            groups = {}
            for slot, m in cb.last_models.items():
                groups.setdefault(m, []).append(slot)
            assert len(new) == len(groups)
            mixed += len(groups) == 2
            got = [riders.get(id(c)) or sorted(s for s, _j in c.riders)
                   for c in new]
            assert sorted(got) == sorted(sorted(g) for g in groups.values())
        monkeypatch.undo()
        assert mixed > 0
        assert all(c.dev is None and c.riders is None for c in made)
        res = srv.results
        assert set(res) == set(range(len(reqs)))
        for m in ("A", "B"):
            alone = serve(m).run()
            for uid, out in alone.items():
                assert res[uid].timings["model"] == m
                np.testing.assert_array_equal(np.asarray(res[uid].output),
                                              np.asarray(out.output))


class TestServeStatsZeroDivision:
    def test_all_timed_out_summary_and_render(self):
        """Zero requests completed (all expired in the queue): every
        derived metric must come out 0, not raise ZeroDivisionError."""
        s = ServeStats()
        for _ in range(3):
            s.record_enqueue()
            s.record_timeout()
        assert s.admitted == s.completed == s.first_outputs == 0
        assert s.mean_queue_wait_s == 0.0
        assert s.mean_ttfp_s == 0.0
        assert s.steps_per_sec == 0.0
        assert s.goodput_steps_per_sec == 0.0
        assert s.padding_efficiency == 1.0
        assert s.slot_occupancy == 1.0
        summary = s.summary()
        assert summary["timed_out"] == 3 and summary["mean_ttfp_ms"] == 0.0
        assert "3 timed out" in s.render()

    def test_fresh_stats_render(self):
        s = ServeStats()
        assert s.summary()["steps_per_sec"] == 0.0
        assert isinstance(s.render(), str)

    def test_merge_of_empty_and_zero_parts(self):
        merged = ServeStats.merge([])
        assert merged.calls == 0 and merged.latency_ewma_s == 0.0
        assert isinstance(merged.render(), str)
        merged = ServeStats.merge([ServeStats(), ServeStats()])
        assert merged.mean_ttfp_s == 0.0 and merged.mean_queue_wait_s == 0.0
        assert isinstance(merged.summary(), dict)

    def test_all_timed_out_through_real_server(self):
        p = _params()
        eng, srv = _server(p, n_slots=1, chunk_steps=4)
        # one seated request keeps the pool busy while the rest expire
        srv.submit(SubmitSpec(np.ones((24, 1), np.float32), uid=0),
                   arrival_time=0.0)
        for i in range(3):
            srv.submit(SubmitSpec(
                np.ones((8, 1), np.float32), uid=f"late{i}", deadline=0.5),
                arrival_time=0.0)
        res = srv.run()
        assert set(res) == {0}
        st = srv.stats
        assert st.timed_out == 3 and st.completed == 1
        assert st.first_outputs == 1            # honest ttfp denominator
        assert st.mean_ttfp_s >= 0.0
        assert isinstance(st.render(), str)

    def test_ttfp_mean_uses_first_outputs_not_admitted(self):
        s = ServeStats()
        s.record_admission(1.0)
        s.record_admission(1.0)                 # two seated...
        s.record_first_output(4.0)              # ...only one produced output
        assert s.first_outputs == 1
        assert s.mean_ttfp_s == 4.0             # not 2.0
