"""Observability layer: metrics, tracing, events, serve integration.

Load-bearing properties:

* **Merge exactness** — fixed-bucket histogram counts are additive, so
  merging per-shard histograms yields *identical* percentiles to one
  histogram fed the union of the samples (property-tested).  This is what
  makes the distributed server's merged p50/p99/p999 export honest rather
  than an approximation-of-approximations.
* **One timings schema** — the one-shot engine path and the queued
  scheduler path answer ``RolloutResult.timings`` with the same
  documented key set (:func:`repro.serve.api.lifecycle_timings`).
* **Zero steady-state retraces** — rolling many chunks of one shape
  emits compile events once and ``retrace`` events never.
* **Off by default** — without ``obs.configure()`` every instrumented
  site is a no-op and results carry no trace ids.
"""

import json
import os
import subprocess
import sys
import threading
import types
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import obs
from repro.core.esn import ESNConfig, fit_readout, init_esn, run_reservoir
from repro.obs.metrics import (DEFAULT_LATENCY_BUCKETS, HistogramData,
                               MetricsRegistry)
from repro.serve import scheduler
from repro.serve import (AsyncReservoirServer, ReservoirEngine, ServeStats,
                         SubmitSpec)

import jax
import jax.numpy as jnp


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends with instrumentation off."""
    obs.disable()
    yield
    obs.disable()


def _params(dim=96, seed=1, block=32):
    cfg = ESNConfig(reservoir_dim=dim, element_sparsity=0.8, leak=0.7,
                    seed=seed, block=block, output_dim=2)
    p = init_esn(cfg)
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((50, 1)), jnp.float32)
    states = run_reservoir(p, u, engine="scan")
    y = jnp.concatenate([u, jnp.roll(u, 1)], axis=-1)
    return fit_readout(p, states, y, lam=1e-2)


def _serve(n=6, **server_kw):
    eng = ReservoirEngine(_params(), backend="xla", stats=ServeStats())
    server_kw.setdefault("chunk_time", 1.0)
    srv = AsyncReservoirServer(eng, n_slots=4, chunk_steps=8, **server_kw)
    rng = np.random.default_rng(0)
    for i in range(n):
        srv.submit(SubmitSpec(
            rng.standard_normal((10 + 3 * i, 1)).astype(np.float32), uid=i),
            arrival_time=0.1 * i)
    return eng, srv, srv.run()


# -- histograms --------------------------------------------------------------
class TestHistogramMerge:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(1, 200), st.integers(2, 5), st.integers(0, 10_000))
    def test_merged_percentiles_equal_union(self, n, shards, seed):
        """THE merge property: per-shard histograms merged == one
        histogram fed the union, for every percentile — exactly."""
        rng = np.random.default_rng(seed)
        samples = rng.lognormal(mean=-6.0, sigma=3.0, size=n)
        parts = [HistogramData(buckets=DEFAULT_LATENCY_BUCKETS)
                 for _ in range(shards)]
        union = HistogramData(buckets=DEFAULT_LATENCY_BUCKETS)
        for i, v in enumerate(samples):
            parts[i % shards].observe(float(v))
            union.observe(float(v))
        merged = HistogramData.merge(parts)
        assert merged.total == union.total == n
        assert merged.counts == union.counts
        assert merged.sum == pytest.approx(union.sum)
        for p in (0.0, 25.0, 50.0, 90.0, 99.0, 99.9, 100.0):
            assert merged.percentile(p) == union.percentile(p)

    def test_percentile_is_bucket_upper_bound(self):
        h = HistogramData(buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.5, 1.7, 3.0):
            h.observe(v)
        assert h.percentile(50) == 2.0            # rank 2 lands in (1, 2]
        assert h.percentile(100) == 4.0
        h.observe(100.0)                          # overflow bucket
        assert h.percentile(100) == 100.0         # vmax, not +inf
        assert HistogramData(buckets=(1.0,)).percentile(99) == 0.0

    def test_merge_rejects_mismatched_buckets(self):
        a = HistogramData(buckets=(1.0, 2.0))
        b = HistogramData(buckets=(1.0, 3.0))
        with pytest.raises(AssertionError):
            HistogramData.merge([a, b])

    def test_stats_and_metrics_agree_on_counts(self):
        """ServeStats.merge and a merged metrics histogram count the same
        events when fed the same completions."""
        waits = [[0.01, 0.2, 0.5], [0.003, 0.9]]
        stats_parts, hist_parts = [], []
        for shard in waits:
            s = ServeStats()
            h = HistogramData(buckets=DEFAULT_LATENCY_BUCKETS)
            for w in shard:
                s.record_enqueue()
                s.record_admission(w)
                h.observe(w)
            stats_parts.append(s)
            hist_parts.append(h)
        merged_stats = ServeStats.merge(stats_parts)
        merged_hist = HistogramData.merge(hist_parts)
        assert merged_stats.admitted == merged_hist.total == 5
        assert merged_stats.queue_wait_s == pytest.approx(merged_hist.sum)


# -- registry export ---------------------------------------------------------
class TestMetricsRegistry:
    def _populated(self):
        m = MetricsRegistry(namespace="repro")
        m.inc("requests_total", 3, model="a")
        m.inc("requests_total", 1, model="b")
        m.set("n_shards", 4)
        rng = np.random.default_rng(0)
        for v in rng.lognormal(-5, 2, size=50):
            m.observe("queue_wait_seconds", float(v))
        return m

    def test_prometheus_text_shape(self):
        text = self._populated().prometheus_text()
        assert '# TYPE repro_requests_total counter' in text
        assert 'repro_requests_total{model="a"} 3' in text
        assert '# TYPE repro_n_shards gauge' in text
        assert '# TYPE repro_queue_wait_seconds histogram' in text
        assert 'le="+Inf"' in text
        assert 'repro_queue_wait_seconds_count 50' in text
        # cumulative buckets end at the total count
        lines = [l for l in text.splitlines() if "_bucket" in l]
        assert lines[-1].endswith(" 50")

    def test_json_roundtrip_preserves_percentiles(self):
        m = self._populated()
        m2 = MetricsRegistry.from_json(json.loads(json.dumps(m.to_json())))
        h, h2 = m.histogram("queue_wait_seconds"), \
            m2.histogram("queue_wait_seconds")
        for p in (50, 99, 99.9):
            assert h.percentile(p) == h2.percentile(p)
        assert m2.counter("requests_total").value(model="a") == 3
        assert m2.prometheus_text() == m.prometheus_text()


# -- serve integration -------------------------------------------------------
class TestServeObservability:
    def test_percentiles_exported_from_async_server(self):
        obs.configure()
        _eng, _srv, results = _serve()
        m = obs.metrics()
        qw = m.histogram("queue_wait_seconds")
        ttfp = m.histogram("ttfp_seconds")
        lat = m.histogram("request_latency_seconds")
        assert qw.count() == 6 and ttfp.count() == 6 and lat.count() == 6
        for h in (qw, ttfp, lat):
            for p in (50, 99, 99.9):
                assert h.percentile(p) > 0.0
        text = m.prometheus_text()
        assert "repro_queue_wait_seconds_bucket" in text
        assert "repro_ttfp_seconds_count 6" in text

    def test_one_timings_schema_on_both_paths(self):
        """Engine one-shot and scheduler paths answer the same documented
        key set — including first_output/ttfp on multi-chunk requests."""
        obs.configure()
        eng, _srv, results = _serve()
        rng = np.random.default_rng(1)
        one = eng.submit(SubmitSpec(
            rng.standard_normal((12, 1)).astype(np.float32)))
        base = {"arrival_time", "admit_time", "first_output_time",
                "finish_time", "queue_wait_s", "ttfp_s", "latency_s",
                "seconds"}
        assert base | {"trace_id"} == set(one.timings)
        for res in results.values():
            assert base | {"trace_id"} == set(res.timings)
            t = res.timings
            assert t["queue_wait_s"] == pytest.approx(
                t["admit_time"] - t["arrival_time"])
            assert t["ttfp_s"] == pytest.approx(
                t["first_output_time"] - t["arrival_time"])
            assert t["latency_s"] == pytest.approx(
                t["finish_time"] - t["arrival_time"])
            assert (t["arrival_time"] <= t["admit_time"]
                    <= t["first_output_time"] <= t["finish_time"])

    def test_first_output_precedes_finish_on_long_requests(self):
        """Regression: a request whose first output landed chunks before
        retirement reports that mark, not its finish time."""
        obs.configure()
        eng = ReservoirEngine(_params(), backend="xla", stats=ServeStats())
        srv = AsyncReservoirServer(eng, n_slots=2, chunk_steps=4,
                                   chunk_time=1.0)
        rng = np.random.default_rng(2)
        srv.submit(SubmitSpec(
            rng.standard_normal((20, 1)).astype(np.float32), uid="long"))
        res = srv.run()["long"]
        t = res.timings
        assert t["first_output_time"] < t["finish_time"]
        assert t["ttfp_s"] < t["latency_s"]

    def test_trace_id_threads_through_lifecycle(self):
        obs.configure()
        _eng, _srv, results = _serve(n=3)
        tr = obs.tracer()
        for res in results.values():
            tid = res.timings["trace_id"]
            names = [s.name for s in tr.spans(trace_id=tid)]
            assert "request.enqueue" in names
            assert "request.queued" in names
            assert "request.serve" in names
            assert "request.wait" in names
            # each span's clock by name: the lifecycle on the server's
            # virtual clock, the queue wait on the host's wall clock
            for s in tr.spans(trace_id=tid):
                assert s.clock == ("wall" if s.name == "request.wait"
                                   else "server"), s.name

    def test_explicit_trace_id_wins(self):
        obs.configure()
        eng = ReservoirEngine(_params(), backend="xla", stats=ServeStats())
        res = eng.submit(SubmitSpec(
            np.zeros((4, 1), np.float32), trace_id="mine"))
        assert res.timings["trace_id"] == "mine"
        assert obs.tracer().spans(trace_id="mine")

    def test_flight_recorder_jsonl_export(self, tmp_path):
        obs.configure()
        _serve(n=3)
        path = tmp_path / "trace.jsonl"
        n = obs.tracer().export_jsonl(path)
        lines = path.read_text().splitlines()
        assert len(lines) == n > 0
        rec = json.loads(lines[0])
        assert {"name", "start", "end", "duration_s", "clock"} <= set(rec)

    def test_zero_steady_state_retraces(self):
        """Compile events fire once per program; rolling many chunks of
        one pool shape must never emit a retrace."""
        obs.configure()
        _serve(n=8)
        ev = obs.events()
        assert ev.count("retrace") == 0
        assert ev.count("xla_trace") >= 1
        # warmed steady-state window: drain, serve more, still zero
        ev.drain()
        _serve(n=4)
        assert not [e for e in ev.events() if e.kind == "retrace"]

    def test_disabled_is_noop(self):
        assert not obs.enabled()
        eng, _srv, results = _serve(n=2)
        for res in results.values():
            assert "trace_id" not in res.timings
            assert "seconds" in res.timings
        assert obs.metrics() is None and obs.tracer() is None


# -- wall-clock phase spans --------------------------------------------------
PHASES = {"scheduler.admit", "scheduler.gather", "scheduler.retire",
          "scheduler.deliver"}


def _steps_and_children(spans):
    """Each ``scheduler.step`` span with the spans recorded under it."""
    steps = sorted((s for s in spans if s.name == "scheduler.step"),
                   key=lambda s: s.start)
    kids = {id(st): [] for st in steps}
    for s in spans:
        if s.parent == "scheduler.step":
            owner = [st for st in steps if st.start <= s.start <= st.end]
            assert len(owner) == 1, s
            kids[id(owner[0])].append(s)
    return [(st, kids[id(st)]) for st in steps]


def _check_phase_spans(tracer, chunks: int, engine_span: str) -> None:
    """One ``scheduler.step`` per ``step()``, one that ran a chunk per
    chunk, each chunk's phases parented to its step and no longer than
    it in all; every ``scheduler.sync`` under a phase of its step."""
    spans = tracer.spans()
    assert tracer.dropped == 0
    steps = _steps_and_children(spans)
    assert sum(st.attrs["chunk"] for st, _ in steps) == chunks
    for st, kids in steps:
        assert st.parent is None and st.clock == "wall"
        names = sorted(k.name for k in kids)
        if st.attrs["chunk"]:
            assert set(names) - {"scheduler.sync"} == PHASES | {engine_span}
        assert sum(k.duration_s for k in kids) <= st.duration_s
        for k in kids:
            assert st.start <= k.start <= k.end <= st.end
    for s in tracer.spans(name="scheduler.sync"):
        assert s.parent in ("scheduler.retire", "scheduler.step")
        assert s.attrs["d2h_bytes"] > 0
    for s in tracer.spans(name="scheduler.retire"):
        assert set(s.attrs) == {"retired", "entries_walked"}
    admits = tracer.spans(name="scheduler.admit")
    assert all(set(s.attrs) == {"admitted", "writes", "h2d_bytes"}
               for s in admits)
    assert {s.parent for s in tracer.spans(name="request.wait")} == {
        "scheduler.admit"}


class TestPhaseSpans:
    @pytest.mark.parametrize("zero_copy", [False, True])
    def test_one_step_span_per_chunk_with_its_phases(self, zero_copy):
        obs.configure()
        eng = ReservoirEngine(_params(), backend="xla", stats=ServeStats())
        srv = AsyncReservoirServer(eng, n_slots=4, chunk_steps=8,
                                   zero_copy=zero_copy)
        rng = np.random.default_rng(0)
        for i in range(6):
            srv.submit(SubmitSpec(
                rng.standard_normal((10 + 3 * i, 1)).astype(np.float32),
                uid=i))
        srv.run()
        tr = obs.tracer()
        _check_phase_spans(tr, srv.stats.chunks,
                           "engine.dispatch" if zero_copy
                           else "engine.rollout")
        assert not tr.spans(name="scheduler.chunk")
        admitted = sum(s.attrs["admitted"]
                       for s in tr.spans(name="scheduler.admit"))
        retired = sum(s.attrs["retired"]
                      for s in tr.spans(name="scheduler.retire"))
        assert admitted == retired == 6
        # each sweep that seats anything sends one stack, padded to a
        # power of two: indices and state rows, and on the zero-copy pool
        # each request's input lanes too
        cb = srv.batcher
        lane = (cb._max_chunks * cb.chunk_steps * cb._in_dim * 4
                if zero_copy else 0)
        for s in tr.spans(name="scheduler.admit"):
            n = s.attrs["admitted"]
            k = next(s for s in scheduler._STACKS if s >= n) if n else 0
            assert s.attrs["writes"] == (n > 0)
            assert s.attrs["h2d_bytes"] == k * (4 + 4 * cb._dim + lane)
        assert len(tr.spans(name="scheduler.sync")) == \
            srv.batcher.host_syncs
        assert len(tr.spans(name="request.wait")) == 6

    @pytest.mark.parametrize("k", [5, 16, 37])
    def test_sweep_span_counts_its_stacks(self, k):
        """One sweep seating ``k`` requests reports them all and one
        pool-write program per stack of up to 16."""
        obs.configure()
        eng = ReservoirEngine(_params(), backend="xla", stats=ServeStats())
        srv = AsyncReservoirServer(eng, n_slots=40, chunk_steps=8,
                                   zero_copy=True)
        for i in range(k):
            srv.submit(SubmitSpec(np.ones((12, 1), np.float32), uid=i))
        srv.step()
        (span,) = obs.tracer().spans(name="scheduler.admit")
        assert span.attrs["admitted"] == k
        assert span.attrs["writes"] == -(-k // 16)

    def test_disabled_reads_no_clock_and_builds_no_annotation(
            self, monkeypatch):
        import jax.profiler

        def refuse(*_a, **_kw):
            raise AssertionError("built with tracing off")

        reads = []

        def perf_counter():
            reads.append(1)
            return 0.0

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
        monkeypatch.setattr(obs, "time", types.SimpleNamespace(
            perf_counter=refuse))
        monkeypatch.setattr(scheduler, "time", types.SimpleNamespace(
            perf_counter=perf_counter))
        closed = obs.configure()
        obs.disable()
        assert obs.timed_span("a", x=1) is obs.timed_span("b")
        _eng, srv, results = _serve(n=3)
        assert len(results) == 3 and srv.stats.chunks > 0
        # the step's own reads only: the chunk's wall time, start and end
        assert len(reads) == 2 * srv.stats.chunks
        assert obs.tracer() is None and obs.detached() is closed
        assert len(closed.tracer) == 0

    def test_traced_step_mirrors_phases_into_profiler(self, monkeypatch):
        import jax.profiler
        entered = []

        class Recording:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(self.name)

            def __exit__(self, *exc):
                return None

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
        obs.configure()
        _serve(n=2, zero_copy=True)
        names = set(entered)
        assert PHASES | {"scheduler.step", "scheduler.sync",
                         "engine.dispatch"} <= names
        assert entered.count("scheduler.step") == len(
            obs.tracer().spans(name="scheduler.step"))


class TestSpanParents:
    def test_nested_timed_spans_and_recorded_spans(self):
        obs.configure()
        with obs.timed_span("outer") as outer:
            with obs.timed_span("inner", k=1) as inner:
                inner.attrs["late"] = 2
                obs.span("point", 0.0)
            obs.span("beside", 0.0)
            assert outer is not None
        obs.span("alone", 0.0)
        got = {s.name: s for s in obs.tracer().spans()}
        assert got["outer"].parent is None
        assert got["inner"].parent == "outer"
        assert got["inner"].attrs == {"k": 1, "late": 2}
        assert got["point"].parent == "inner"
        assert got["beside"].parent == "outer"
        assert got["alone"].parent is None
        assert got["outer"].start <= got["inner"].start
        assert got["inner"].end <= got["outer"].end

    def test_each_thread_keeps_its_own_parents(self):
        obs.configure()
        with obs.timed_span("main"):
            t = threading.Thread(target=lambda: obs.span("other", 0.0))
            t.start()
            t.join(timeout=10)
            assert not t.is_alive()
        assert obs.tracer().spans(name="other")[0].parent is None

    def test_exception_closes_the_span(self):
        obs.configure()
        with pytest.raises(ValueError):
            with obs.timed_span("failing"):
                raise ValueError("x")
        obs.span("after", 0.0)
        assert obs.tracer().spans(name="failing")
        assert obs.tracer().spans(name="after")[0].parent is None

    def test_detached_keeps_the_closed_window(self):
        state = obs.configure()
        with obs.timed_span("kept"):
            pass
        obs.disable()
        assert obs.detached() is state
        assert obs.timed_span("off") is obs.timed_span("off")
        obs.disable()
        assert obs.detached() is state
        obs.configure()
        assert obs.detached() is None


# -- stats render ------------------------------------------------------------
class TestStatsRender:
    def test_render_surfaces_timed_out_and_quota_held(self):
        s = ServeStats()
        s.record_enqueue()
        s.record_admission(0.1)
        s.record_chunk(live_steps=4, total_steps=8)
        s.record_completion(0.5)
        s.record_timeout()
        s.record_quota_hold()
        s.record_quota_hold()
        line = s.render()
        assert "1 timed out" in line
        assert "2 quota held" in line

    def test_render_shows_zeros_not_silence(self):
        s = ServeStats()
        s.record_enqueue()
        s.record_admission(0.0)
        s.record_chunk(live_steps=1, total_steps=1)
        line = s.render()
        assert "0 timed out" in line
        assert "0 quota held" in line


# -- dist: merged shard export ----------------------------------------------
class TestDistObservability:
    def test_sharded_server_merged_percentiles(self):
        """Queue-wait/ttfp percentiles export from the distributed server
        with per-shard labels merging into one exact histogram."""
        from repro.dist import (DistributedReservoirServer,
                                ShardedReservoirEngine)
        obs.configure()
        eng = ShardedReservoirEngine(_params(), n_shards=1, backend="xla",
                                     stats=ServeStats())
        srv = DistributedReservoirServer(eng, slots_per_shard=3,
                                         chunk_steps=8, chunk_time=1.0)
        rng = np.random.default_rng(3)
        for i in range(5):
            srv.submit(SubmitSpec(
                rng.standard_normal((10, 1)).astype(np.float32), uid=i),
                arrival_time=0.1 * i)
        srv.run()
        m = obs.metrics()
        qw = m.histogram("queue_wait_seconds")
        assert qw.count() == 5
        # per-shard series carry a shard label; the unlabeled view is the
        # exact merge of every shard's series
        shard_total = 0
        for key, data in qw.series.items():
            assert any(k == "shard" for k, _v in key)
            shard_total += data.total
        assert shard_total == 5
        for p in (50, 99, 99.9):
            assert qw.percentile(p) > 0.0
        assert m.histogram("ttfp_seconds").count() == 5


class TestPhaseSpansMultiDevice:
    """The distributed server inherits the phase spans: one sharded pool
    over four devices (run in a child with four virtual CPU devices)."""

    @pytest.mark.skipif(len(jax.devices()) < 4,
                        reason="needs 4 devices (run by the subprocess "
                               "test below)")
    @pytest.mark.parametrize("zero_copy", [False, True])
    def test_sharded_server_records_the_same_phases(self, zero_copy):
        from repro.dist import (DistributedReservoirServer,
                                ShardedReservoirEngine)
        from repro.launch.mesh import make_data_mesh
        obs.configure()
        eng = ShardedReservoirEngine(_params(), mesh=make_data_mesh(4),
                                     backend="xla", stats=ServeStats())
        srv = DistributedReservoirServer(eng, slots_per_shard=2,
                                         chunk_steps=8, zero_copy=zero_copy)
        rng = np.random.default_rng(4)
        for i in range(12):
            srv.submit(SubmitSpec(
                rng.standard_normal((10 + 2 * i, 1)).astype(np.float32),
                uid=i))
        srv.run()
        _check_phase_spans(obs.tracer(), srv.stats.chunks,
                           "engine.dispatch" if zero_copy
                           else "engine.rollout")
        admitted = sum(s.attrs["admitted"]
                       for s in obs.tracer().spans(name="scheduler.admit"))
        assert admitted == 12

    def test_subprocess_four_devices(self):
        env = dict(os.environ)
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                            + env.get("XLA_FLAGS", "")).strip()
        env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
        out = subprocess.run(
            [sys.executable, "-m", "pytest", "-x", "-q", "-p",
             "no:cacheprovider", "tests/test_obs.py", "-k",
             "MultiDevice and not subprocess"],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=str(Path(__file__).parent.parent))
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
        assert "2 passed" in out.stdout
