"""Backpressure + admission control: bounded queues, deadline shedding,
tenant fairness, and the two deadline bug regressions.

Admission contracts from the ISSUE:

* a refused submission gets an explicit ``RolloutResult`` with
  ``status="rejected"`` (reason + retry-after hint in ``timings``) —
  never a silent drop, never an unbounded queue;
* shedding keeps the engine's latency promise: a request the queue-delay
  estimate already dooms is refused at the door instead of timing out
  later;
* admitted requests are untouched — their outputs stay bit-identical to
  an unpoliced run;
* (regression) the one-shot engine path records
  ``timings["deadline_ignored"]`` and warns once instead of silently
  swallowing ``spec.deadline``;
* (regression) a queued request behind a full pool is dropped the step
  its deadline passes, not when a slot finally frees.
"""

import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.esn import ESNConfig, fit_readout, init_esn, run_reservoir
from repro.serve import (AsyncReservoirServer, BoundedQueuePolicy,
                         CompositePolicy, DeadlineShedPolicy, ModelRegistry,
                         ReservoirEngine, Rejection, ServeStats, SubmitSpec,
                         TenantFairnessPolicy, default_policy)
from repro.serve.admission import (COLD_CHUNK_SECONDS,
                                   estimate_chunk_seconds,
                                   estimate_queue_delay)


def _params(mode="fp32", dim=96, leak=0.7, seed=1, block=32):
    cfg = ESNConfig(reservoir_dim=dim, element_sparsity=0.8, mode=mode,
                    leak=leak, seed=seed, block=block, output_dim=2)
    p = init_esn(cfg)
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((50, 1)), jnp.float32)
    states = run_reservoir(p, u, engine="scan")
    y = jnp.concatenate([u, jnp.roll(u, 1)], axis=-1)
    return fit_readout(p, states, y, lam=1e-2)


def _requests(lengths, seed=0, in_dim=1):
    rng = np.random.default_rng(seed)
    return [SubmitSpec(rng.standard_normal((t, in_dim)).astype(np.float32),
                       uid=i)
            for i, t in enumerate(lengths)]


def _server(p, **kw):
    eng = ReservoirEngine(p, backend="xla", stats=ServeStats())
    kw.setdefault("chunk_time", 1.0)        # deterministic virtual clock
    return eng, AsyncReservoirServer(eng, stats=ServeStats(), **kw)


# -- fakes for pure policy-math units ----------------------------------------

class _FakeQ:
    def __init__(self, model, length=8):
        self.model = model
        self.length = length
        self.deadline = None
        self.arrival_time = 0.0


class _FakeServer:
    def __init__(self, seated, queued, n_slots):
        class B:
            pass
        self.batcher = B()
        self.batcher.n_slots = n_slots
        self.batcher.chunk_steps = 4
        self.batcher._slots = list(seated) + [None] * (n_slots - len(seated))
        self.batcher._pos = [0] * n_slots
        self._queue = [(0.0, i, q) for i, q in enumerate(queued)]
        self.chunk_time = 1.0

    @property
    def pending(self):
        return len(self._queue)


class TestEstimators:
    def test_chunk_time_wins(self):
        srv = _FakeServer([], [], n_slots=4)
        assert estimate_chunk_seconds(srv) == 1.0

    def test_cost_model_used_before_any_measurement(self):
        # chunk_time=None and no chunks run yet: the cold constant answers
        # (positive, finite) so policies work from the first submit
        eng, srv = _server(_params(), n_slots=2, chunk_steps=8,
                           chunk_time=None)
        assert srv.stats.chunks == 0
        est = estimate_chunk_seconds(srv)
        assert est == COLD_CHUNK_SECONDS
        assert 0 < est < float("inf")

    def test_queue_delay_zero_when_idle(self):
        srv = _FakeServer([], [], n_slots=4)
        assert estimate_queue_delay(srv) == 0.0

    def test_queue_delay_grows_with_backlog(self):
        a = _FakeServer([], [_FakeQ(None, 8)] * 2, n_slots=2)
        b = _FakeServer([], [_FakeQ(None, 8)] * 8, n_slots=2)
        assert estimate_queue_delay(b) > estimate_queue_delay(a) > 0


class TestBoundedQueuePolicy:
    def test_rejects_past_depth_with_explicit_result(self):
        p = _params()
        eng, srv = _server(p, n_slots=1, chunk_steps=4,
                           admission=BoundedQueuePolicy(max_depth=1))
        specs = _requests([8, 8, 8, 8], seed=3)
        outcomes = [srv.submit(s, arrival_time=0.0) for s in specs]
        rejected = [r for r in outcomes if hasattr(r, "status")
                    and r.rejected]
        assert len(rejected) == 3 and srv.pending == 1
        for r in rejected:
            assert r.status == "rejected" and r.output is None
            assert r.timings["reason"] == "queue_full"
            assert r.timings["retry_after_s"] > 0
        assert srv.stats.rejected == 3 and srv.stats.shed == 0
        # rejections never enter the queue accounting
        assert srv.stats.enqueued == 1 and srv.stats.timed_out == 0

    def test_admitted_requests_bit_identical_to_unpoliced(self):
        p = _params()
        specs = _requests([8, 8, 8], seed=4)
        _, ref_srv = _server(p, n_slots=1, chunk_steps=4)
        for s in specs:
            ref_srv.submit(s, arrival_time=0.0)
        ref = ref_srv.run()
        _, srv = _server(p, n_slots=1, chunk_steps=4,
                         admission=BoundedQueuePolicy(max_depth=64))
        for s in specs:
            srv.submit(s, arrival_time=0.0)
        res = srv.run()
        assert len(res) == 3
        for uid in ref:
            np.testing.assert_array_equal(np.asarray(res[uid].output),
                                          np.asarray(ref[uid].output))


class TestDeadlineShedPolicy:
    def test_sheds_unmeetable_deadline_at_the_door(self):
        p = _params()
        eng, srv = _server(p, n_slots=1, chunk_steps=4,
                           admission=DeadlineShedPolicy())
        # 32 steps of backlog behind a 1-slot x 4-step pool: 8 chunks
        # (8 virtual seconds) before a new arrival is guaranteed a seat
        srv.submit(SubmitSpec(np.ones((32, 1), np.float32), uid="long"),
                   arrival_time=0.0)
        doomed = srv.submit(
            SubmitSpec(np.ones((4, 1), np.float32), uid="tight",
                       deadline=2.0), arrival_time=0.0)
        assert doomed.rejected
        assert doomed.timings["reason"] == "deadline_unmeetable"
        assert doomed.timings["retry_after_s"] > 0
        assert srv.stats.shed == 1 and srv.stats.rejected == 0
        ok = srv.submit(SubmitSpec(np.ones((4, 1), np.float32), uid="lax"),
                        arrival_time=0.0)
        assert not hasattr(ok, "status") or not getattr(ok, "rejected", False)
        res = srv.run()
        assert "tight" not in res or res["tight"].rejected
        assert srv.stats.timed_out == 0     # shed at the door, not later


class TestTenantFairnessPolicy:
    def test_never_fires_below_contention(self):
        pol = TenantFairnessPolicy()
        srv = _FakeServer([_FakeQ("a")], [], n_slots=4)
        assert pol.admit(srv, _FakeQ("a")) is None

    def test_equal_weights_split_the_pool(self):
        pol = TenantFairnessPolicy()
        seated = [_FakeQ("a")] * 3 + [_FakeQ("b")] * 1
        srv = _FakeServer(seated, [_FakeQ("a"), _FakeQ("a")], n_slots=4)
        # in_system=8 incl. candidate, equal split cap=4: "a" holds 5
        verdict = pol.admit(srv, _FakeQ("a"))
        assert isinstance(verdict, Rejection)
        assert verdict.reason == "tenant_over_share" and not verdict.shed
        # the underrepresented tenant still gets in
        assert pol.admit(srv, _FakeQ("b")) is None

    def test_weights_tilt_the_split(self):
        seated = [_FakeQ("a")] * 3 + [_FakeQ("b")] * 2
        srv = _FakeServer(seated, [], n_slots=4)
        equal = TenantFairnessPolicy()
        assert equal.admit(srv, _FakeQ("a")) is not None
        tilted = TenantFairnessPolicy(weights={"a": 3.0, "b": 1.0})
        assert tilted.admit(srv, _FakeQ("a")) is None

    def test_multi_tenant_server_integration(self):
        reg = ModelRegistry(backend="xla")
        reg.register("a", _params(seed=1))
        reg.register("b", _params(seed=2))
        eng = reg.engine("a")
        eng.stats = ServeStats()
        srv = AsyncReservoirServer(eng, n_slots=2, chunk_steps=4,
                                   chunk_time=1.0, registry=reg,
                                   stats=ServeStats(),
                                   admission=TenantFairnessPolicy())
        def spec(model, uid):
            return SubmitSpec(np.ones((8, 1), np.float32), model=model,
                              uid=uid)
        for i in range(4):
            assert not getattr(srv.submit(spec("a", f"a{i}"),
                                          arrival_time=0.0),
                               "rejected", False)
        # under contention the second tenant still gets in ...
        assert not getattr(srv.submit(spec("b", "b0"), arrival_time=0.0),
                           "rejected", False)
        # ... and the hog is the one refused
        hog = srv.submit(spec("a", "a4"), arrival_time=0.0)
        assert hog.rejected and hog.timings["reason"] == "tenant_over_share"
        res = srv.run()
        assert srv.stats.completed == 5 and len(res) == 6  # 5 ok + 1 reject


class TestCompositeAndDefault:
    def test_first_rejection_wins(self):
        always = BoundedQueuePolicy(max_depth=0)
        srv = _FakeServer([], [_FakeQ(None)], n_slots=2)
        verdict = CompositePolicy(DeadlineShedPolicy(), always).admit(
            srv, _FakeQ(None))
        assert verdict is not None and verdict.reason == "queue_full"

    def test_default_policy_shape(self):
        pol = default_policy(max_depth=7, weights={"a": 2.0})
        kinds = [type(p) for p in pol.policies]
        assert kinds == [BoundedQueuePolicy, DeadlineShedPolicy,
                         TenantFairnessPolicy]
        assert pol.policies[0].max_depth == 7
        assert pol.policies[2].weights == {"a": 2.0}


class TestEngineDeadlineIgnoredRegression:
    """Satellite bugfix 1: the one-shot engine path used to swallow
    ``spec.deadline`` silently."""

    def test_timings_record_and_warn_once(self):
        import repro.serve.engine as engine_mod
        p = _params()
        eng = ReservoirEngine(p, backend="xla")
        u = np.ones((8, 1), np.float32)
        engine_mod._WARNED_DEADLINE = False
        with pytest.warns(UserWarning, match="deadline"):
            res = eng.submit(SubmitSpec(u, deadline=5.0))
        assert res.timings["deadline_ignored"] is True
        # warn-once: the second deadline-bearing submit stays silent but
        # still records the timings flag
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            res2 = eng.submit(SubmitSpec(u, deadline=5.0))
        assert res2.timings["deadline_ignored"] is True
        # no flag at all when no deadline was asked for
        res3 = eng.submit(SubmitSpec(u))
        assert "deadline_ignored" not in res3.timings


class TestDeadlineDropOnClockAdvanceRegression:
    """Satellite bugfix 2: the admission sweep only examines the queue
    head while slots are free, so a request waiting behind a full pool
    used to linger past its deadline until a slot freed."""

    def test_expired_request_dropped_while_pool_still_full(self):
        p = _params()
        _, srv = _server(p, n_slots=1, chunk_steps=2)
        # A occupies the only slot for 4 chunks (t=4); B's deadline
        # passes at t=2 while A is still running
        srv.submit(SubmitSpec(np.ones((8, 1), np.float32), uid="A"),
                   arrival_time=0.0)
        srv.submit(SubmitSpec(np.ones((2, 1), np.float32), uid="B",
                              deadline=2.0), arrival_time=0.0)
        srv.step()                            # seats A, now=1.0
        srv.step()                            # now=2.0 (== deadline: holds)
        assert srv.stats.timed_out == 0 and srv.pending == 1
        srv.step()                            # now=3.0 > deadline
        # dropped NOW, with the pool still full — not at slot-free time
        assert srv.stats.timed_out == 1
        assert srv.pending == 0 and srv.batcher.live == 1
        res = srv.run()
        assert "B" not in res and srv.stats.completed == 1
