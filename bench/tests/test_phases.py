"""The program's phase spans as the benchmark reads them: the per-layer
readers of ``metrics/`` (through ``program_spans.py``), a traced run on the
CPU, and ``phases.py``'s innermost split of the device's idle time."""

import pytest

import phases
import run
import trace_reduce
from conftest import small_spec
from repro import obs
from repro.dist import ShardedContinuousBatcher
from test_trace_reduce import FIXTURE, HOST, profile

NEW = ("admit_ms", "retire_ms", "deliver_ms")
SEED = 2**31 + 5


@pytest.fixture(autouse=True)
def _obs_clean():
    obs.disable()
    yield
    obs.disable()


def _window(steps):
    """A closed window of the program's tracer: per step (start, chunk,
    {phase: seconds}) its ``scheduler.step`` and phases, laid end to end
    inside it; returns the step count."""
    state = obs.configure(metrics=False, events=False)
    tr = state.tracer
    for start, chunk, parts in steps:
        t = start
        for name, secs in parts.items():
            tr.record(name, t, t + secs, parent="scheduler.step")
            t += secs
        tr.record("scheduler.step", start, t + 0.001, parent=None,
                  chunk=chunk)
    obs.disable()
    return len(steps)


STEPS = [(0.0, True, {"scheduler.admit": 0.002, "scheduler.gather": 0.001,
                      "scheduler.retire": 0.004,
                      "scheduler.deliver": 0.003}),
         (1.0, True, {"scheduler.admit": 0.004, "scheduler.retire": 0.002,
                      "scheduler.deliver": 0.001}),
         (2.0, False, {"scheduler.admit": 0.003})]


@pytest.mark.parametrize("name,value", [("admit_ms.thru", 4.5),
                                        ("retire_ms.tail", 3.0),
                                        ("deliver_ms.thru", 2.0)])
def test_phase_readers_divide_by_the_chunks(name, value):
    n = _window(STEPS)
    ctx = {"step_s": [0.01] * n}
    assert run.read_metric(name, ctx) == pytest.approx(value)
    # a window that is not this run's (another count of steps) reads
    # nothing
    assert run.read_metric(name, {"step_s": [0.01] * (n + 1)}) is None


def test_queue_wait_is_the_nearest_rank_p95():
    state = obs.configure(metrics=False, events=False)
    state.tracer.record("scheduler.step", 0.0, 1.0, chunk=True)
    for i in range(40):
        state.tracer.record("request.wait", 0.0, 0.001 * (i + 1))
    obs.disable()
    ctx = {"step_s": [1.0]}
    assert run.read_metric("queue_wait_ms.tail", ctx) == pytest.approx(38.0)


@pytest.mark.parametrize("metric", ["admit_ms.thru", "queue_wait_ms.tail"])
def test_readers_of_a_program_without_the_spans(metric, monkeypatch):
    """The parent program has no ``obs.detached`` and no phase spans: its
    readings are nothing, never an error."""
    _window([(0.0, True, {})])
    assert run.read_metric(metric, {"step_s": [0.01]}) is None
    monkeypatch.delattr(obs, "detached")
    assert run.read_metric(metric, {"step_s": [0.01]}) is None


def _zero_copy(init):
    def zero_copy_init(self, *a, **kw):
        init(self, *a, **(kw | {"zero_copy": True}))
    return zero_copy_init


@pytest.mark.parametrize("loop,chips", [("closed", 1), ("open", 1),
                                        ("closed", 4)])
def test_traced_run_reads_every_phase(loop, chips, cpu_devices,
                                      cpu_devices_x4, monkeypatch):
    """A traced run at a CPU test's size: each new metric of the loop's
    suffix reads a positive number, and the program's tracer dropped
    nothing."""
    suffix = "tail" if loop == "open" else "thru"
    spec = small_spec(loop, chips=chips)
    spec["per_layer"] = [{"name": f"{f}.{suffix}", "unit": "ms"}
                         for f in NEW]
    if loop == "open":
        spec["per_layer"].append({"name": "queue_wait_ms.tail",
                                  "unit": "ms"})
    if chips == 4:
        monkeypatch.setattr(ShardedContinuousBatcher, "__init__", _zero_copy(
            ShardedContinuousBatcher.__init__))
    out = run.run_cell(spec, SEED, 0.5, True,
                       cpu_devices if chips == 1 else cpu_devices_x4)
    assert out["correct"], out["checks"]
    got = out["metrics"]
    assert set(got) == {m["name"] for m in spec["per_layer"]}
    assert all(m["value"] > 0 for m in got.values()), got
    state = obs.detached()
    assert state.tracer.dropped == 0
    line = phases.summarize(state.tracer.spans(), state.tracer.dropped)
    per = line["ms_per_step"]
    parts = sum(per[k] for k in ("scheduler.admit", "scheduler.gather",
                                 "scheduler.retire", "scheduler.deliver",
                                 "self"))
    dispatch = per["engine.dispatch"]
    if chips == 1:
        # the CPU pool copies inputs in and syncs each chunk: its call is
        # an engine.rollout, and its sync a direct child of the step
        dispatch = 1e3 * sum(s.duration_s for s in state.tracer.spans(
            name="engine.rollout")) / line["chunks"]
        parts += per["scheduler.sync"]
    assert parts + dispatch == pytest.approx(per["scheduler.step"])
    assert line["attrs"]["scheduler.admit.admitted"] > 0


def test_summary_of_a_window():
    _window(STEPS)
    state = obs.detached()
    line = phases.summarize(state.tracer.spans(), 0)
    assert line["chunks"] == 2 and line["steps"] == 3
    per = line["ms_per_step"]
    assert per["scheduler.admit"] == pytest.approx(4.5)
    assert per["scheduler.step"] == pytest.approx(
        (0.011 + 0.008 + 0.004) * 1e3 / 2)
    assert per["self"] == pytest.approx(1.5)
    assert line["slow_steps"] == []


def test_slow_step_names_its_longest_phase():
    _window([(0.0, True, {"scheduler.admit": 0.002,
                          "scheduler.retire": 0.120,
                          "scheduler.deliver": 0.001})])
    line = phases.summarize(obs.detached().tracer.spans(), 0)
    assert line["slow_steps"] == [[0.0, 0.124, "scheduler.retire", 0.12]]


NESTED = [("bench.window", 1000, 9000),
          ("bench.submit", 1000, 1000),
          ("bench.step", 2000, 7000),
          ("scheduler.step", 2100, 6800),
          ("scheduler.admit", 2100, 900),          # starts with its step
          ("scheduler.retire", 5000, 3000),
          ("scheduler.sync", 6000, 1500)]


def test_innermost_annotation_takes_the_gap():
    r = phases.reduce(profile([("kernel", 3000, 1000)], NESTED))
    assert r["busy_s"] == pytest.approx(1000e-9)
    assert r["idle"] == pytest.approx({
        "bench.submit": 1000e-9, "bench.step": 200e-9,
        "scheduler.admit": 900e-9, "scheduler.step": 1900e-9,
        "scheduler.retire": 1500e-9, "scheduler.sync": 1500e-9,
        "other": 1000e-9})
    assert r["busy_s"] + sum(r["idle"].values()) == pytest.approx(
        r["window_s"])
    # the harness's own reduction sees only its labels
    old = trace_reduce.reduce(profile([("kernel", 3000, 1000)], NESTED))
    assert old["idle"]["bench.step"] == pytest.approx(7000e-9 - 1000e-9)


@pytest.mark.parametrize("source", ["fixture", "synthetic"])
def test_without_program_annotations_it_is_trace_reduce(source):
    """On a trace with only the harness's labels (the recorded v5e trace,
    or a synthetic one), the result is the harness's, number for
    number."""
    if source == "fixture":
        args = (FIXTURE,)
    else:
        args = (profile([("fusion", 500, 1000), ("kernel", 3000, 2000),
                         ("copy", 4000, 1500)], HOST, n_devices=2),)
    assert phases.reduce(*args) == trace_reduce.reduce(*args)
