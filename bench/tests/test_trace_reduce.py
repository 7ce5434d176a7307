"""The reduction from a profiler trace to busy time, ops and idle gaps."""

from pathlib import Path
from types import SimpleNamespace as NS

import pytest

import trace_reduce

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_small.xplane.pb"


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def profile(device_ops, host_events, n_devices=1):
    planes = [NS(name=f"/device:TPU:{d}", lines=[
        NS(name="XLA Modules", events=[ev("jit_f", 0, 10_000)]),
        NS(name="XLA Ops", events=[ev(*e) for e in device_ops])])
        for d in range(n_devices)]
    planes.append(NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev(*e) for e in host_events])]))
    return NS(planes=planes)


HOST = [("bench.window", 1000, 9000),           # window [1000, 10000)
        ("bench.submit", 1000, 1000),
        ("bench.step", 2000, 5000),
        ("bench.wait", 7000, 3000),
        ("something.else", 0, 20_000)]


def test_busy_ops_and_labelled_gaps():
    ops = [("fusion", 500, 1000),               # clipped to [1000, 1500)
           ("kernel", 3000, 2000), ("copy", 4000, 1500),   # union [3000, 5500)
           ("kernel", 11_000, 500)]             # outside the window
    r = trace_reduce.reduce(profile(ops, HOST))
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(9000e-9)
    assert r["busy_s"] == pytest.approx((500 + 2500) * 1e-9)
    assert r["ops"] == pytest.approx({"kernel": 2000e-9, "copy": 1500e-9,
                                      "fusion": 500e-9})
    # gaps: [1500, 3000): submit 500, step 1000; [5500, 10000): step
    # 1500, wait 3000
    assert r["idle"] == pytest.approx({"bench.submit": 500e-9,
                                       "bench.step": 2500e-9,
                                       "bench.wait": 3000e-9})
    assert r["busy_s"] + sum(r["idle"].values()) == pytest.approx(
        r["window_s"])


def test_averaged_over_devices():
    ops = [("kernel", 2000, 1000)]
    r = trace_reduce.reduce(profile(ops, HOST, n_devices=4))
    assert r["devices"] == 4
    assert r["busy_s"] == pytest.approx(1000e-9)
    assert r["ops"] == pytest.approx({"kernel": 1000e-9})


def test_gap_without_annotation_is_other():
    host = [("bench.window", 0, 1000), ("bench.wait", 500, 200)]
    r = trace_reduce.reduce(profile([("k", 0, 400)], host))
    assert r["idle"] == pytest.approx({"other": 400e-9, "bench.wait": 200e-9})


def test_nothing_to_read():
    assert trace_reduce.reduce(profile([], HOST)) is None
    assert trace_reduce.reduce(profile([("k", 0, 1)], HOST[1:])) is None


def test_recorded_trace():
    """A trace recorded on a TPU v5e by ``record_trace.py``: four matmul
    rounds, each between a 1 ms and a 2 ms host sleep."""
    r = trace_reduce.reduce(FIXTURE)
    assert r is not None and r["devices"] == 1
    assert 0.012 < r["window_s"] < 0.1
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["busy_s"] + sum(r["idle"].values()) == pytest.approx(
        r["window_s"])
    # the sleeps are idle and labelled by what the host was doing
    assert r["idle"]["bench.wait"] >= 4 * 0.002 * 0.95
    assert r["idle"]["bench.submit"] >= 4 * 0.001 * 0.95
    assert sum(r["ops"].values()) >= r["busy_s"]
    # the device clock is moved onto the host's: every operation now lies
    # inside the four bench.step annotations (3.21255 ms in all)
    assert r["idle"]["bench.step"] + r["busy_s"] == pytest.approx(3.21255e-3)
    assert "%convolution_tanh_fusion" in r["ops"]
