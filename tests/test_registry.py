"""Multi-tenant registry: live swap, cross-tenant interleaving, quotas.

The acceptance contracts from the ISSUE:

* a ``publish()`` under a running Poisson trace drops nothing and times
  nothing out by the swap, and outputs are *bit-exact* on both sides of
  the cutover — pre-cutover admissions match the old engine, post-cutover
  the new one (version pinned at admission, never migrated);
* interleaving tenants in one slot pool is bit-identical to serving each
  tenant alone: every per-model chunk call runs at the FULL pool shape,
  so a row's arithmetic never depends on who its neighbours are;
* per-tenant quotas hold requests without head-of-line blocking, and
  registry deadline policies drop expired queued work — all accounted in
  per-tenant stats.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.esn import ESNConfig, fit_readout, init_esn, run_reservoir
from repro.plan import plan_cache_stats
from repro.serve import (AsyncReservoirServer, ModelRegistry, ReservoirEngine,
                         ServeStats, SubmitSpec, engine_cache_clear,
                         engine_cache_stats, engine_for)

DIM = 64


def _params(seed=1, leak=0.7):
    cfg = ESNConfig(reservoir_dim=DIM, element_sparsity=0.8, mode="fp32",
                    leak=leak, seed=seed, block=32, output_dim=2)
    p = init_esn(cfg)
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((50, 1)), jnp.float32)
    states = run_reservoir(p, u, engine="scan")
    y = jnp.concatenate([u, jnp.roll(u, 1)], axis=-1)
    return fit_readout(p, states, y, lam=1e-2)


def _pool_ref(engine, inputs, n_slots):
    """One-shot reference at the POOL batch shape: rows are independent,
    so tiling the request across all slots gives the exact bits its pool
    row produces."""
    batch = jnp.asarray(np.broadcast_to(
        inputs[None], (n_slots,) + inputs.shape))
    return np.asarray(engine.predictions(batch))[0]


class TestRegistryBasics:
    def test_register_version_activate_and_rollback(self):
        reg = ModelRegistry(backend="xla")
        v1 = reg.register("m", _params(1))
        assert (v1.version, reg.active_version("m")) == (1, 1)
        v2 = reg.register("m", _params(2))
        assert (v2.version, reg.active_version("m")) == (2, 2)
        assert reg.versions("m") == [1, 2] and reg.models == ["m"]
        with pytest.raises(ValueError, match="immutable"):
            reg.register("m", _params(3), version=2)
        plan = reg.publish("m", version=1)       # rollback
        assert reg.active_version("m") == 1
        assert plan["previous_version"] == 2 and plan["version"] == 1
        assert plan["prewarm_s"] >= 0.0 and len(plan["actions"]) == 5
        with pytest.raises(KeyError):
            reg.active_version("ghost")
        with pytest.raises(KeyError, match="no version"):
            reg.get("m", 9)
        with pytest.raises(ValueError, match="params"):
            reg.publish("m")

    def test_engine_cache_keyed_on_registry_identity(self):
        """Two versions with VALUE-equal params must get distinct cached
        engines — (name, version) is the key, not array identity."""
        engine_cache_clear()
        engine_cache_stats(reset=True)
        p = _params(4)
        import dataclasses as dc
        p2 = dc.replace(p)                       # same arrays, new object
        reg = ModelRegistry(backend="xla")
        reg.register("m", p)
        reg.register("m", p2)
        e1, e2 = reg.engine("m", 1), reg.engine("m", 2)
        assert e1 is not e2
        assert reg.engine("m", 1) is e1          # cache hit on the key
        st = engine_cache_stats()
        assert st["tenants"]["m"]["misses"] == 2
        assert st["tenants"]["m"]["hits"] >= 1

    def test_plan_cache_tenant_counters(self):
        plan_cache_stats(reset=True)
        reg = ModelRegistry(backend="xla")
        reg.register("counted", _params(5))
        reg.engine("counted")
        st = plan_cache_stats()
        assert "counted" in st["tenants"]
        assert st["tenants"]["counted"]["hits"] + \
            st["tenants"]["counted"]["misses"] >= 1

    def test_registry_submit_one_shot(self):
        reg = ModelRegistry(backend="xla")
        reg.register("m", _params(1))
        u = np.ones((9, 1), np.float32)
        res = reg.submit(SubmitSpec(u, model="m"))
        assert res.preds.shape == (9, 2) and res.final_state.shape == (DIM,)
        with pytest.raises(ValueError, match="spec.model"):
            reg.submit(SubmitSpec(u))

    def test_bare_engine_rejects_model_spec(self):
        eng = ReservoirEngine(_params(1))
        with pytest.raises(ValueError, match="registry"):
            eng.submit(SubmitSpec(np.ones((4, 1), np.float32), model="m"))
        srv = AsyncReservoirServer(ReservoirEngine(_params(1),
                                                   stats=ServeStats()),
                                   n_slots=1, chunk_time=1.0)
        with pytest.raises(ValueError, match="no registry"):
            srv.submit(SubmitSpec(np.ones((4, 1), np.float32), model="m"))

    def test_mismatched_dims_rejected_in_shared_pool(self):
        small = ESNConfig(reservoir_dim=32, element_sparsity=0.8,
                          mode="fp32", leak=0.7, seed=9, block=32,
                          output_dim=2)
        reg = ModelRegistry(backend="xla")
        reg.register("big", _params(1))
        reg.register("small", init_esn(small))
        eng = reg.engine("big")
        eng.stats = ServeStats()
        srv = AsyncReservoirServer(eng, n_slots=2, chunk_steps=8,
                                   chunk_time=1.0, registry=reg)
        srv.submit(SubmitSpec(np.ones((8, 1), np.float32), model="small",
                              want_states=True))
        with pytest.raises(ValueError, match="share input/reservoir dims"):
            srv.run()


class TestCrossTenantInterleaving:
    @pytest.mark.parametrize("backend", ["xla", "pallas"])
    def test_bit_identical_to_single_tenant(self, backend):
        """A/B interleaved in one pool == each served alone, bit for bit."""
        pA, pB = _params(1), _params(2, leak=0.55)
        rng = np.random.default_rng(0)
        n, t = 4, 24
        inputs = [rng.standard_normal((t, 1)).astype(np.float32)
                  for _ in range(n)]
        reg = ModelRegistry(backend=backend)
        reg.register("A", pA)
        reg.register("B", pB)
        eng = reg.engine("A")
        eng.stats = ServeStats()
        srv = AsyncReservoirServer(eng, n_slots=n, chunk_steps=8,
                                   chunk_time=1.0, registry=reg)
        for i, u in enumerate(inputs):
            srv.submit(SubmitSpec(u, model="A" if i % 2 == 0 else "B",
                                  uid=i), arrival_time=0.0)
        res = srv.run()
        # single-tenant references at the same (n_slots, T) pool shape
        batch = jnp.asarray(np.stack(inputs))
        refA = np.asarray(reg.engine("A").predictions(batch))
        refB = np.asarray(reg.engine("B").predictions(batch))
        for i in range(n):
            ref = refA if i % 2 == 0 else refB
            np.testing.assert_array_equal(
                np.asarray(res[i].output), ref[i])
            assert res[i].timings["model"] == ("A" if i % 2 == 0 else "B")
            assert res[i].timings["version"] == 1
        ts = srv.tenant_summary()
        assert ts.completed == n
        assert ts.shards["A"].completed == ts.shards["B"].completed == 2


class TestLiveSwap:
    def test_mid_traffic_swap_bit_exact_zero_drops(self):
        """Poisson trace against model "m"; v2 published mid-flight.

        Every request completes (nothing dropped or timed out by the
        swap), requests admitted before the cutover are bit-exact against
        the v1 engine, requests admitted after against v2."""
        p1, p2 = _params(1), _params(7, leak=0.5)
        rng = np.random.default_rng(3)
        n_slots, t, n_req = 4, 24, 14
        inputs = [rng.standard_normal((t, 1)).astype(np.float32)
                  for _ in range(n_req)]
        arrivals = np.cumsum(rng.exponential(0.4, n_req))
        arrivals -= arrivals[0]

        reg = ModelRegistry(backend="xla")
        reg.register("m", p1)
        eng = reg.engine("m")
        eng.stats = ServeStats()
        srv = AsyncReservoirServer(eng, n_slots=n_slots, chunk_steps=8,
                                   chunk_time=1.0, registry=reg)
        handles = [srv.submit(SubmitSpec(u, model="m", uid=i),
                              arrival_time=float(at))
                   for i, (u, at) in enumerate(zip(inputs, arrivals))]
        # serve a few chunks, then swap with work in flight and queued
        swapped_at = None
        while srv.step():
            if swapped_at is None and srv.stats.completed >= 3:
                assert srv.batcher.live > 0      # genuinely mid-traffic
                plan = reg.publish("m", p2)
                swapped_at = srv.now
                assert plan["version"] == 2
        res = srv.results

        assert len(res) == n_req                 # zero drops
        assert srv.stats.timed_out == 0
        assert swapped_at is not None
        e1, e2 = reg.engine("m", 1), reg.engine("m", 2)
        pinned = [q.pinned_version for q in handles]
        assert set(pinned) == {1, 2}             # trace straddles the swap
        for i, q in enumerate(handles):
            eng_v = e1 if q.pinned_version == 1 else e2
            ref = _pool_ref(eng_v, inputs[i], n_slots)
            np.testing.assert_array_equal(np.asarray(res[i].output), ref)
            assert res[i].timings["version"] == q.pinned_version
        # in-flight work admitted before the cutover finished on v1
        pre = [q for q in handles if q.admit_time is not None
               and q.admit_time < swapped_at]
        assert all(q.pinned_version == 1 for q in pre)

    def test_swap_prewarm_compiles_before_cutover(self):
        """During publish() the new version's chunk program is compiled
        against the pool shape — the first post-swap chunk retraces
        nothing."""
        p1, p2 = _params(1), _params(8)
        reg = ModelRegistry(backend="xla")
        reg.register("m", p1)
        eng = reg.engine("m")
        eng.stats = ServeStats()
        srv = AsyncReservoirServer(eng, n_slots=2, chunk_steps=8,
                                   chunk_time=1.0, registry=reg)
        # compile v1's chunk program via one served request
        srv.submit(SubmitSpec(np.ones((8, 1), np.float32), model="m",
                              uid="warm"))
        srv.run()
        reg.publish("m", p2)
        e2 = reg.engine("m", 2)
        traces_after_publish = dict(e2.trace_counts)
        assert traces_after_publish                  # prewarm traced it
        srv.submit(SubmitSpec(np.ones((8, 1), np.float32), model="m",
                              uid="post"))
        srv.run()
        assert dict(e2.trace_counts) == traces_after_publish
        # retired version demoted: (m, 1) sits at the LRU eviction front
        from repro.serve.engine import _engine_cache
        assert next(iter(_engine_cache))[0] == ("m", 1)


class TestQuotasAndDeadlines:
    def test_quota_holds_without_head_of_line_blocking(self):
        pA, pB = _params(1), _params(2)
        reg = ModelRegistry(backend="xla")
        reg.register("A", pA)
        reg.register("B", pB, quota=1)
        eng = reg.engine("A")
        eng.stats = ServeStats()
        srv = AsyncReservoirServer(eng, n_slots=3, chunk_steps=8,
                                   chunk_time=1.0, registry=reg)
        # two B requests up front, then an A request behind them
        for i in range(2):
            srv.submit(SubmitSpec(np.ones((16, 1), np.float32),
                                  model="B", uid=f"b{i}"), arrival_time=0.0)
        srv.submit(SubmitSpec(np.ones((8, 1), np.float32),
                              model="A", uid="a0"), arrival_time=0.0)
        max_b_live = 0
        while srv.step():
            b_live = sum(1 for q in srv.batcher._slots
                         if q is not None and q.model == "B")
            max_b_live = max(max_b_live, b_live)
        assert max_b_live == 1                   # quota enforced
        assert len(srv.results) == 3             # held, not dropped
        assert srv.stats.quota_held > 0
        assert srv.tenant_stats["B"].quota_held > 0
        assert srv.tenant_stats["A"].quota_held == 0
        # b1 queued behind the quota, but a0 seated past it at t=0
        assert srv.results["a0"].timings["admit_time"] == 0.0
        assert "quota_held" in srv.stats.summary()

    def test_registry_deadline_policy_applies_to_specs(self):
        p = _params(1)
        reg = ModelRegistry(backend="xla")
        reg.register("m", p, deadline_s=1.5)
        eng = reg.engine("m")
        eng.stats = ServeStats()
        srv = AsyncReservoirServer(eng, n_slots=1, chunk_steps=8,
                                   chunk_time=1.0, registry=reg)
        # slot busy for 4 ticks; the queued request expires at 1.5
        srv.submit(SubmitSpec(np.ones((32, 1), np.float32), model="m",
                              uid="busy"), arrival_time=0.0)
        doomed = srv.submit(SubmitSpec(np.ones((8, 1), np.float32),
                                       model="m", uid="late"),
                            arrival_time=0.0)
        res = srv.run()
        assert doomed.deadline == 1.5            # policy became absolute
        assert set(res) == {"busy"}
        assert srv.stats.timed_out == 1
        assert srv.tenant_stats["m"].timed_out == 1
        # an explicit spec deadline wins over the policy
        q = srv.submit(SubmitSpec(np.ones((4, 1), np.float32), model="m",
                                  deadline=99.0, uid="patient"))
        assert q.deadline == 99.0

    def test_legacy_engine_for_still_keyed_by_identity(self):
        """The tenant=None regime is unchanged: same params object hits,
        and kwargs bypass the cache entirely."""
        engine_cache_clear()
        p = _params(6)
        a = engine_for(p, "xla")
        assert engine_for(p, "xla") is a
        b = engine_for(p, "xla", specialize=True)  # kwargs -> no cache
        assert b is not a
