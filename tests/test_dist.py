"""Sharded serving: shard_map engine parity, sharded batcher, elastic shrink.

The acceptance contract is the ISSUE's: the sharded engine must be
*bit-identical per sequence* to the single-device engine on both backends
(states AND fused-readout predictions, fp32 + int8-csd, chunked +
one-shot).  Each shard runs the identical compiled rollout callable on
its batch slice and rows never mix through the recurrence, so equality is
exact, not approximate.

Multi-device tests (classes named ``*MultiDevice*``) need 8 devices; the
CI dist job runs this module under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.  In the plain
tier-1 run (1 device) they are covered instead by the subprocess test at
the bottom, which forces 8 virtual devices the way the HLO-walker test
does.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.esn import ESNConfig, fit_readout, init_esn, run_reservoir
from repro.dist import (DistributedReservoirServer, ShardedContinuousBatcher,
                        ShardedReservoirEngine)
from repro.runtime.elastic import (AutoscalePolicy, grow_serve_plan,
                                   shrink_serve_plan)
from repro.runtime.faults import FaultEvent, FaultPlan
from repro.serve import (ReservoirEngine, RolloutRequest, ServeStats,
                         SubmitSpec)

N_DEV = len(jax.devices())
multi_device = pytest.mark.skipif(
    N_DEV < 8, reason="needs 8 devices (run by the CI dist job)")


def _params(mode="fp32", dim=96, leak=0.7, seed=1, block=32):
    cfg = ESNConfig(reservoir_dim=dim, element_sparsity=0.8, mode=mode,
                    leak=leak, seed=seed, block=block, output_dim=2)
    p = init_esn(cfg)
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((50, 1)), jnp.float32)
    states = run_reservoir(p, u, engine="scan")
    y = jnp.concatenate([u, jnp.roll(u, 1)], axis=-1)
    return fit_readout(p, states, y, lam=1e-2)


def _requests(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [SubmitSpec(rng.standard_normal((t, 1)).astype(np.float32), uid=i)
            for i, t in enumerate(lengths)]


class TestServeStatsMerge:
    def _part(self, calls=2, steps=100, seconds=0.5, wait_max=0.1):
        s = ServeStats()
        for _ in range(calls):
            s.record_call(batch=4, steps=steps // calls // 4,
                          seconds=seconds / calls)
        s.record_enqueue()
        s.record_admission(wait_max)
        s.record_chunk(live_steps=steps // 2, total_steps=steps)
        return s

    def test_merge_sums_counters_and_maxes_maxima(self):
        a = self._part(wait_max=0.1)
        b = self._part(calls=4, wait_max=0.7)
        m = ServeStats.merge([a, b])
        assert m.calls == a.calls + b.calls
        assert m.steps_padded == a.steps_padded + b.steps_padded
        assert m.seconds == pytest.approx(a.seconds + b.seconds)
        assert m.queue_wait_max_s == pytest.approx(0.7)
        assert m.admitted == 2 and m.enqueued == 2
        # calls-weighted ewma
        want = (a.latency_ewma_s * a.calls + b.latency_ewma_s * b.calls) / 6
        assert m.latency_ewma_s == pytest.approx(want)

    def test_merge_timed_out_and_empty(self):
        a = ServeStats()
        a.record_timeout()
        a.record_timeout()
        m = ServeStats.merge([a, ServeStats()])
        assert m.timed_out == 2
        assert ServeStats.merge([]).calls == 0

    def test_shard_breakdown_in_summary_and_render(self):
        m = ServeStats.merge([self._part(), self._part()],
                             labels=["shard0", "shard1"])
        summ = m.summary()
        assert set(summ["shards"]) == {"shard0", "shard1"}
        assert summ["shards"]["shard0"]["calls"] == 2
        r = m.render()
        assert "shard0:" in r and "shard1:" in r and "occupancy" in r

    def test_timed_out_rendered(self):
        s = ServeStats()
        s.record_enqueue()
        s.record_timeout()
        assert "1 timed out" in s.render()
        assert s.summary()["timed_out"] == 1


class TestShrinkServePlan:
    def test_every_survivor_usable(self):
        plan = shrink_serve_plan(8, 3)
        assert plan["survivors"] == 5 and plan["usable_devices"] == 5
        assert plan["mesh_shape"] == (5, 1)

    def test_actions_cover_serving_recovery(self):
        acts = " ".join(shrink_serve_plan(8, 1)["actions"])
        assert "re-admit" in acts.lower()
        assert "snapshot" in acts.lower()
        assert "cached" in acts.lower()


class TestGrowServePlan:
    def test_inverse_of_shrink(self):
        plan = grow_serve_plan(5, 3)
        assert plan["n_shards_before"] == 5
        assert plan["n_shards_after"] == 8 and plan["added"] == 3
        assert plan["mesh_shape"] == (8, 1)

    def test_device_ceiling_caps_width(self):
        plan = grow_serve_plan(6, 4, max_shards=8)
        assert plan["n_shards_after"] == 8 and plan["added"] == 2
        assert grow_serve_plan(8, 2, max_shards=8)["added"] == 0

    def test_actions_cover_rebalance(self):
        acts = " ".join(grow_serve_plan(2, 2)["actions"])
        assert "rebalance" in acts.lower()
        assert "snapshot" in acts.lower()


class TestAutoscalePolicy:
    def test_grows_on_backlog(self):
        pol = AutoscalePolicy(max_shards=8, grow_queue_per_slot=1.0)
        assert pol.decide(pending=20, live=16, n_slots=16, n_shards=4) == 1
        # at the ceiling: never grows past max_shards
        assert pol.decide(pending=20, live=16, n_slots=16, n_shards=8) == 0

    def test_shrinks_only_when_idle(self):
        pol = AutoscalePolicy(min_shards=2, shrink_occupancy=0.25)
        assert pol.decide(pending=0, live=1, n_slots=16, n_shards=4) == -1
        # queued work blocks scale-down even at low occupancy
        assert pol.decide(pending=1, live=1, n_slots=16, n_shards=4) == 0
        # never below min_shards
        assert pol.decide(pending=0, live=0, n_slots=16, n_shards=2) == 0

    def test_steady_state_holds(self):
        pol = AutoscalePolicy()
        assert pol.decide(pending=4, live=12, n_slots=16, n_shards=4) == 0


class TestSingleShardParity:
    """n_shards=1 runs everywhere and must already be exactly the
    single-device engine (the shard_map wrapper adds nothing)."""

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_bit_identical(self, backend):
        p = _params()
        single = ReservoirEngine(p, backend=backend, stats=ServeStats())
        sharded = ShardedReservoirEngine(p, n_shards=1, backend=backend,
                                         stats=ServeStats())
        rng = np.random.default_rng(0)
        u = jnp.asarray(rng.standard_normal((4, 12, 1)), jnp.float32)
        np.testing.assert_array_equal(np.asarray(sharded.rollout(u)),
                                      np.asarray(single.rollout(u)))
        z = jnp.zeros((4, 96), jnp.float32)
        pr_s, xf_s = sharded.run_segment(u, z)
        pr_1, xf_1 = single.run_segment(u, z)
        np.testing.assert_array_equal(np.asarray(pr_s), np.asarray(pr_1))
        np.testing.assert_array_equal(np.asarray(xf_s), np.asarray(xf_1))

    def test_serve_api_and_padding_accounting(self):
        p = _params()
        sharded = ShardedReservoirEngine(p, n_shards=1, stats=ServeStats())
        res = sharded.submit_many(_requests([5, 9, 12], seed=2))
        assert set(res) == {0, 1, 2} and res[1].output.shape == (9, 2)
        assert sharded.stats.steps_real > 0

    def test_distributed_server_matches_engine(self):
        p = _params()
        eng = ShardedReservoirEngine(p, n_shards=1, stats=ServeStats())
        single = ReservoirEngine(p, stats=ServeStats())
        srv = DistributedReservoirServer(eng, slots_per_shard=3,
                                         chunk_steps=8, chunk_time=1.0,
                                         stats=ServeStats())
        reqs = _requests([5, 17, 30, 9, 12, 23], seed=3)
        for i, r in enumerate(reqs):
            srv.submit(r, arrival_time=0.5 * i)
        res = srv.run()
        for r in reqs:
            want = np.asarray(single.predictions(jnp.asarray(r.inputs)))
            np.testing.assert_allclose(res[r.uid].output, want,
                                       rtol=1e-4, atol=1e-6)
        merged = srv.shard_summary()
        assert merged.completed == 6 and merged.shards is not None
        assert "shard0" in merged.summary()["shards"]


@multi_device
class TestMultiDeviceParity:
    """8-shard engine == single-device engine, bit for bit."""

    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    @pytest.mark.parametrize("mode", ["fp32", "int8-csd"])
    def test_one_shot_and_chunked_bit_identical(self, backend, mode):
        p = _params(mode=mode)
        single = ReservoirEngine(p, backend=backend, stats=ServeStats())
        sharded = ShardedReservoirEngine(p, n_shards=8, backend=backend,
                                         stats=ServeStats())
        assert sharded.n_shards == 8
        rng = np.random.default_rng(4)
        u = jnp.asarray(rng.standard_normal((16, 12, 1)), jnp.float32)
        # states and fused-readout predictions, one-shot
        np.testing.assert_array_equal(np.asarray(sharded.rollout(u)),
                                      np.asarray(single.rollout(u)))
        np.testing.assert_array_equal(np.asarray(sharded.predictions(u)),
                                      np.asarray(single.predictions(u)))
        # chunked: carry the sharded final state, resume, compare the
        # stitched trajectory against the single-device one-shot
        p1, xf = sharded.run_segment(u[:, :6],
                                     jnp.zeros((u.shape[0], 96),
                                               jnp.float32))
        p2 = sharded.predictions(u[:, 6:], x0=xf)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(p1), np.asarray(p2)], axis=1),
            np.asarray(single.predictions(u)))

    def test_ragged_batch_pads_to_shard_multiple(self):
        p = _params()
        single = ReservoirEngine(p, stats=ServeStats())
        sharded = ShardedReservoirEngine(p, n_shards=8, stats=ServeStats())
        rng = np.random.default_rng(5)
        u = jnp.asarray(rng.standard_normal((5, 10, 1)), jnp.float32)
        out = sharded.predictions(u)
        assert out.shape == (5, 10, 2)          # padding rows trimmed
        # local batch is 1 here, which XLA may lower as a gemv with a
        # different accumulation order — allow an ulp (the bit-identity
        # contract is tested at local batch >= 2 above)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(single.predictions(u)),
                                   rtol=1e-5, atol=1e-6)
        # padded rows counted as executed (8 rows ran for 5 real)
        assert sharded.stats.sequences == 8
        assert sharded.stats.steps_real == 50
        assert sharded.stats.steps_padded == 80


@multi_device
class TestMultiDeviceServer:
    def test_least_loaded_admission_spreads_shards(self):
        p = _params()
        eng = ShardedReservoirEngine(p, n_shards=8, stats=ServeStats())
        cb = ShardedContinuousBatcher(eng, slots_per_shard=2, chunk_steps=4)
        from repro.serve.scheduler import QueuedRequest
        for i in range(8):
            cb.admit(QueuedRequest(RolloutRequest(
                uid=i, inputs=np.ones((4, 1), np.float32))))
        # one request per shard before any shard takes a second
        assert cb.free_slots_by_shard() == [1] * 8
        for s in range(8):
            assert cb.shard_stats[s].admitted == 1

    def test_sweep_write_is_shard_local_and_least_loaded(self):
        """The sharded pool write compiles to shard-local updates, and one
        sweep seats least-loaded and writes every seated slot exactly."""
        from repro.serve.scheduler import _STACKS
        p = _params()
        eng = ShardedReservoirEngine(p, n_shards=8, stats=ServeStats())
        srv = DistributedReservoirServer(eng, slots_per_shard=2,
                                         chunk_steps=4, chunk_time=1.0,
                                         zero_copy=True, stats=ServeStats())
        cb = srv.batcher
        for k in _STACKS:
            hlo = cb._pool_write.lower(
                cb._u_dev, cb._states,
                *cb._place_stack(cb._stack((), k))).compile().as_text()
            for op in ("all-gather", "all-reduce", "collective-permute",
                       "all-to-all", "reduce-scatter"):
                assert op not in hlo, (k, op)
        rng = np.random.default_rng(12)
        specs = [SubmitSpec(
            rng.standard_normal((3 + 2 * i, 1)).astype(np.float32), uid=i,
            x0=rng.standard_normal(96).astype(np.float32) if i % 3 else None)
            for i in range(8)]
        for s in specs:
            srv.submit(s)
        srv._admit_arrived()
        assert cb.free_slots_by_shard() == [1] * 8
        for s in range(8):
            assert cb.shard_stats[s].admitted == 1
        for buf in (cb._u_dev, cb._states):
            assert buf.sharding.is_equivalent_to(eng.batch_sharding,
                                                 buf.ndim)
        lanes, states = np.asarray(cb._u_dev), np.asarray(cb._states)
        seated = {q.uid: i for i, q in enumerate(cb._slots) if q is not None}
        assert sorted(seated) == list(range(8))
        want_lanes = np.zeros_like(lanes)
        want_states = np.zeros_like(states)
        for s in specs:
            slot = seated[s.uid]
            want_lanes[slot].reshape(-1, 1)[: len(s.inputs)] = s.inputs
            if s.x0 is not None:
                want_states[slot] = s.x0
        np.testing.assert_array_equal(lanes, want_lanes)
        np.testing.assert_array_equal(states, want_states)

    def test_results_match_single_device(self):
        p = _params()
        eng = ShardedReservoirEngine(p, n_shards=8, stats=ServeStats())
        single = ReservoirEngine(p, stats=ServeStats())
        srv = DistributedReservoirServer(eng, slots_per_shard=2,
                                         chunk_steps=8, chunk_time=1.0,
                                         stats=ServeStats())
        reqs = _requests([5, 17, 30, 9, 12, 23, 8, 40, 11, 16], seed=6)
        for i, r in enumerate(reqs):
            srv.submit(r, arrival_time=0.25 * i)
        res = srv.run()
        assert len(res) == len(reqs)
        for r in reqs:
            want = np.asarray(single.predictions(jnp.asarray(r.inputs)))
            np.testing.assert_allclose(res[r.uid].output, want,
                                       rtol=1e-4, atol=1e-6)
        merged = srv.shard_summary()
        assert merged.completed == len(reqs)
        assert len(merged.shards) == 8


@multi_device
class TestMultiDeviceShrink:
    def test_shard_loss_loses_no_request(self):
        p = _params()
        eng = ShardedReservoirEngine(p, n_shards=8, stats=ServeStats())
        single = ReservoirEngine(p, stats=ServeStats())
        srv = DistributedReservoirServer(eng, slots_per_shard=1,
                                         chunk_steps=4, chunk_time=1.0,
                                         stats=ServeStats())
        reqs = _requests([16] * 12, seed=7)
        for r in reqs:
            srv.submit(r, arrival_time=0.0)
        srv.step()                               # 8 in flight, mid-rollout
        assert srv.batcher.live == 8
        plan = srv.shrink(failed=3)
        assert plan["n_shards_after"] == 5 and srv.n_shards == 5
        assert srv.readmitted == 8 and srv.reshards == 1
        assert srv.batcher.n_shards == 5
        res = srv.run()
        assert len(res) == 12                    # nothing lost
        # re-admissions must not double-count queue telemetry
        assert srv.stats.admitted == srv.stats.enqueued == 12
        assert srv.stats.completed == 12
        # shard telemetry spans both topology epochs: totals cover the
        # whole run, with per-epoch shard labels
        merged = srv.shard_summary()
        assert merged.completed == 12
        assert any(label.startswith("epoch0/") for label in merged.shards)
        assert any(label.startswith("epoch1/") for label in merged.shards)
        for r in reqs:
            want = np.asarray(single.predictions(jnp.asarray(r.inputs)))
            np.testing.assert_allclose(res[r.uid].output, want,
                                       rtol=1e-4, atol=1e-6)

    def test_shrink_resume_is_bit_exact_when_shapes_allow(self):
        """A sequence whose chunks all ran at the same pool shape stays
        bit-identical across the shrink: the carried state is exact and
        the resumed chunks recompute nothing."""
        p = _params()
        eng = ShardedReservoirEngine(p, n_shards=8, stats=ServeStats())
        srv = DistributedReservoirServer(eng, slots_per_shard=1,
                                         chunk_steps=4, chunk_time=1.0,
                                         stats=ServeStats())
        u = np.random.default_rng(8).standard_normal((8, 1)).astype(
            np.float32)
        srv.submit(SubmitSpec(u, uid="a"), arrival_time=0.0)
        srv.step()
        srv.shrink(failed=4)
        res = srv.run()
        assert res["a"].output.shape == (8, 2)


@multi_device
class TestMultiDeviceGrow:
    """Elastic grow under live traffic: the inverse of shrink, same
    snapshot/re-admit machinery, zero drops."""

    def test_shrink_grow_round_trip_bit_identical(self):
        """Property test: a pool shrunk then regrown under traffic
        serves every request with outputs bit-identical to an
        undisturbed run.  ``slots_per_shard=2`` keeps the local batch
        >= 2, where the per-shard program (whose shape is independent
        of the shard count) is exactly the contract's bit-identity
        regime."""
        p = _params()
        lengths = [12] * 12

        def serve(disturb):
            eng = ShardedReservoirEngine(p, n_shards=4, stats=ServeStats())
            srv = DistributedReservoirServer(eng, slots_per_shard=2,
                                             chunk_steps=4, chunk_time=1.0,
                                             stats=ServeStats())
            for r in _requests(lengths, seed=9):
                srv.submit(r, arrival_time=0.0)
            if disturb:
                srv.step()                      # 8 in flight, mid-rollout
                srv.shrink(failed=2)
                srv.step()                      # roll a chunk at width 2
                plan = srv.grow(2)
                assert plan["n_shards_after"] == 4 and srv.n_shards == 4
                assert srv.grows == 1 and srv.reshards == 1
            return srv.run(), srv

        ref, _ = serve(disturb=False)
        res, srv = serve(disturb=True)
        assert len(res) == len(ref) == 12       # zero drops
        assert srv.stats.completed == 12
        assert srv.stats.admitted == srv.stats.enqueued == 12
        for uid in ref:
            np.testing.assert_array_equal(np.asarray(res[uid].output),
                                          np.asarray(ref[uid].output))

    def test_grow_rebalances_subpools(self):
        """After a grow the least-loaded FIFO admission spreads carried
        + queued work over the new shards — the widened pool actually
        serves, it doesn't just exist."""
        p = _params()
        eng = ShardedReservoirEngine(p, n_shards=2, stats=ServeStats())
        srv = DistributedReservoirServer(eng, slots_per_shard=2,
                                         chunk_steps=4, chunk_time=1.0,
                                         stats=ServeStats())
        for r in _requests([16] * 12, seed=10):
            srv.submit(r, arrival_time=0.0)
        srv.step()
        assert srv.batcher.live == 4
        srv.grow(2)
        assert srv.n_shards == 4 and srv.batcher.n_slots == 8
        srv.step()
        # every shard of the widened pool holds seated work
        assert all(f < srv.slots_per_shard
                   for f in srv.batcher.free_slots_by_shard())
        res = srv.run()
        assert len(res) == 12 and srv.stats.completed == 12
        merged = srv.shard_summary()
        assert merged.completed == 12

    def test_fault_plan_shard_death_recovers_through_shrink(self):
        """An unplanned shard death scheduled by the fault plan is
        detected at the next step and converted into the shrink path:
        zero request loss, and an autoscale policy grows the pool back
        under the remaining backlog."""
        p = _params()
        plan = FaultPlan([FaultEvent("shard_loss", at=2.0, shard=1)])
        eng = ShardedReservoirEngine(p, n_shards=4, stats=ServeStats())
        srv = DistributedReservoirServer(
            eng, slots_per_shard=2, chunk_steps=4, chunk_time=1.0,
            stats=ServeStats(), fault_plan=plan,
            autoscale=AutoscalePolicy(min_shards=1, max_shards=4,
                                      cooldown_steps=2))
        reqs = _requests([12] * 20, seed=11)
        for r in reqs:
            srv.submit(r, arrival_time=0.0)
        res = srv.run()
        assert plan.injected.get("shard_loss") == 1
        assert srv.reshards >= 1                 # death -> shrink path
        assert srv.grows >= 1                    # backlog -> grow back
        assert len(res) == 20 and srv.stats.completed == 20

        # bit-identical to the undisturbed reference run
        eng2 = ShardedReservoirEngine(p, n_shards=4, stats=ServeStats())
        ref_srv = DistributedReservoirServer(eng2, slots_per_shard=2,
                                             chunk_steps=4, chunk_time=1.0,
                                             stats=ServeStats())
        for r in _requests([12] * 20, seed=11):
            ref_srv.submit(r, arrival_time=0.0)
        ref = ref_srv.run()
        for uid in ref:
            np.testing.assert_array_equal(np.asarray(res[uid].output),
                                          np.asarray(ref[uid].output))


@multi_device
class TestMultiDeviceMultiModel:
    """Registry-routed multi-tenant serving on the 8-shard pool: two
    models interleaved through one sharded FIFO, each bit-exact against
    its own single-tenant sharded serve at the same pool shape."""

    def test_two_models_share_sharded_pool_bit_exact(self):
        from repro.serve import ModelRegistry, SubmitSpec
        pA, pB = _params(seed=1), _params(seed=2, leak=0.55)
        rng = np.random.default_rng(12)
        n_req, t = 8, 16
        inputs = [rng.standard_normal((t, 1)).astype(np.float32)
                  for _ in range(n_req)]

        def serve(models):
            reg = ModelRegistry()
            reg.register("A", pA)
            reg.register("B", pB)
            eng = ShardedReservoirEngine(pA, n_shards=4, stats=ServeStats())
            srv = DistributedReservoirServer(
                eng, slots_per_shard=2, chunk_steps=8, chunk_time=1.0,
                stats=ServeStats(), registry=reg)
            for i, u in enumerate(inputs):
                srv.submit(SubmitSpec(u, model=models(i), uid=i),
                           arrival_time=0.0)
            return srv.run(), srv

        mixed, srv = serve(lambda i: "A" if i % 2 == 0 else "B")
        only_a, _ = serve(lambda i: "A")
        only_b, _ = serve(lambda i: "B")
        for i in range(n_req):
            ref = only_a if i % 2 == 0 else only_b
            np.testing.assert_array_equal(np.asarray(mixed[i].output),
                                          np.asarray(ref[i].output))
        ts = srv.tenant_summary()
        assert ts.shards["A"].completed == ts.shards["B"].completed == 4

    def test_publish_swaps_on_sharded_server(self):
        from repro.serve import ModelRegistry, SubmitSpec
        p1, p2 = _params(seed=3), _params(seed=4)
        reg = ModelRegistry()
        reg.register("m", p1)
        eng = ShardedReservoirEngine(p1, n_shards=4, stats=ServeStats())
        srv = DistributedReservoirServer(
            eng, slots_per_shard=1, chunk_steps=4, chunk_time=1.0,
            stats=ServeStats(), registry=reg)
        u = np.random.default_rng(5).standard_normal((12, 1)).astype(
            np.float32)
        pre = srv.submit(SubmitSpec(u, model="m", uid="pre"),
                         arrival_time=0.0)
        srv.step()                               # "pre" pinned to v1
        plan = reg.publish("m", p2)
        assert plan["version"] == 2
        post = srv.submit(SubmitSpec(u, model="m", uid="post"))
        res = srv.run()
        assert pre.pinned_version == 1 and post.pinned_version == 2
        assert srv.stats.timed_out == 0 and len(res) == 2
        # v2's mesh-mapped engine serves post; v1 finished pre in place
        ref1 = srv._tenant_engine("m", 1).predictions(
            jnp.asarray(np.broadcast_to(u[None], (4,) + u.shape)))
        ref2 = srv._tenant_engine("m", 2).predictions(
            jnp.asarray(np.broadcast_to(u[None], (4,) + u.shape)))
        np.testing.assert_array_equal(np.asarray(res["pre"].output),
                                      np.asarray(ref1)[0])
        np.testing.assert_array_equal(np.asarray(res["post"].output),
                                      np.asarray(ref2)[0])


class TestMultiDeviceSubprocess:
    """Tier-1 coverage of the 8-device tests when this process only has
    one device: re-run the MultiDevice classes under forced virtual
    devices, exactly like the HLO-walker ground-truth test."""

    @pytest.mark.skipif(N_DEV >= 8, reason="already running multi-device")
    def test_multi_device_suite(self):
        env = dict(os.environ)
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                            + env.get("XLA_FLAGS", "")).strip()
        env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run(
            [sys.executable, "-m", "pytest", "-x", "-q",
             "tests/test_dist.py", "-k", "MultiDevice and not Subprocess"],
            capture_output=True, text=True, timeout=900, env=env,
            cwd=str(Path(__file__).parent.parent))
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
        assert "passed" in out.stdout
