"""``recur_ops``: the recurrence's multiply-adds in the window, from the
program's ``engine.dispatch`` counts, over what the matrix needs."""

import pytest

import run
import work
from repro import obs


@pytest.fixture(autouse=True)
def _obs_clean():
    obs.disable()
    yield
    obs.disable()


def _ctx(steps: int, launches: int) -> dict:
    return {"step_s": [0.01] * steps, "launches": launches,
            "rows_per_chip": 4, "chunk_steps": 8,
            "work": work.Work(nnz=10, reservoir_dim=32, input_dim=2,
                              output_dim=1, weight_bytes=1)}


def _window(dispatch_attrs: list) -> int:
    """A closed window of the program's tracer: one chunk step per
    dispatch, with the dispatch's attributes; returns the step count."""
    tr = obs.configure(metrics=False, events=False).tracer
    for k, attrs in enumerate(dispatch_attrs):
        tr.record("engine.dispatch", k, k + 0.001, parent="scheduler.step",
                  **attrs)
        tr.record("scheduler.step", k, k + 0.01, parent=None, chunk=True)
    obs.disable()
    return len(dispatch_attrs)


def test_reads_issued_over_needed():
    # two launches of 4 rows x 8 steps: the dense product of a 32-node
    # matrix (32 * 32 per row-step) against its 10 nonzeros
    n = _window([{"recur_ops": 32 * 32 * 4 * 8}] * 2)
    assert run.read_metric("recur_ops.ks", _ctx(n, 2)) == pytest.approx(
        32 * 32 / 10)


def test_a_program_without_the_count_reads_nothing():
    n = _window([{}] * 3)
    assert run.read_metric("recur_ops.ks", _ctx(n, 3)) is None
    # nor a window that is not this run's
    n = _window([{"recur_ops": 5}] * 2)
    assert run.read_metric("recur_ops.ks", _ctx(n + 1, 2)) is None
