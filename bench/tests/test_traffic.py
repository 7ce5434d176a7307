"""Every seed gets the same work, in its own order."""

import numpy as np
import pytest

import traffic

CLOSED = {"loop": "closed", "clients": 4,
          "lengths": {"dist": "uniform", "min": 1024, "max": 4096}}
OPEN = {"loop": "open", "rate_per_s": 500.0,
        "lengths": {"dist": "loguniform", "min": 16, "max": 256}}


@pytest.mark.parametrize("spec", [CLOSED, OPEN], ids=["closed", "open"])
def test_same_lengths_every_seed(spec):
    n = traffic.CYCLE
    a = traffic.Traffic(spec, 1, 1)
    b = traffic.Traffic(spec, 2**31 + 17, 1)
    la = [a.length(k) for k in range(n)]
    lb = [b.length(k) for k in range(n)]
    assert la != lb
    assert sorted(la) == sorted(lb)
    lo, hi = spec["lengths"]["min"], spec["lengths"]["max"]
    assert lo <= min(la) < lo + 0.01 * (hi - lo)
    assert hi - 0.01 * (hi - lo) < max(la) <= hi


def test_same_seed_same_inputs():
    a = traffic.Traffic(OPEN, 7, 1)
    b = traffic.Traffic(OPEN, 7, 1)
    for k in (0, 5, 3000):
        assert np.array_equal(a.inputs(k), b.inputs(k))
        assert a.due(k) == b.due(k)
        assert a.inputs(k).shape == (a.length(k), 1)
        assert a.inputs(k).dtype == np.float32


def test_open_loop_mean_rate():
    t = traffic.Traffic(OPEN, 3, 1)
    dues = [t.due(k) for k in range(traffic.CYCLE)]
    assert all(x < y for x, y in zip(dues, dues[1:]))
    assert dues[-1] == pytest.approx(traffic.CYCLE / 500.0, rel=1e-9)
