"""The one traffic generator: every mix is a data file under ``traffic/``.

A mix file (JSON) holds:

* ``loop`` -- ``"closed"``: ``clients`` callers, each sending its next
  request the moment its last one returns; ``"open"``: requests due on a
  schedule at ``rate_per_s`` (Poisson), sent whether or not earlier ones
  have returned.
* ``lengths`` -- reservoir steps per request: ``{"dist": "uniform" |
  "loguniform", "min", "max"}``.
* ``pool`` -- the server's slot pool: ``slots`` per chip, ``chunk_steps``.
* ``warmup_chunks`` (closed) or ``warmup_s`` (open): set-up traffic that
  brings the pool to steady state before the window opens.
* ``sample`` -- how many of the window's finished requests the check
  compares with the reference.

Every seed gets the same multiset of lengths and of inter-arrival gaps, in
its own order: the lengths are the distribution's quantiles at ``(j +
0.5) / CYCLE``, the gaps an exponential's, each cycle shuffled.  So the
seed changes which request comes when, never how much work a window holds.
Request inputs are slices, at seeded offsets, of one seeded signal.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

import model

CYCLE = 1024                    # lengths and gaps come in shuffled cycles
SIGNAL_STEPS = 1 << 17          # one base signal per run; requests slice it


def load(name: str, root: Path) -> dict:
    """The mix ``name`` (``traffic/<name>.json`` under ``root``)."""
    return json.loads((root / "traffic" / f"{name}.json").read_text())


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``spec``."""
    p = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "uniform":
        x = lo + p * (hi - lo + 1)
    elif spec["dist"] == "loguniform":
        x = np.exp(math.log(lo) + p * (math.log(hi + 1) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


class Traffic:
    """Request ``k`` of a run: its length, its input and (open loop) when
    it is due.  Deterministic in (mix, seed, k)."""

    def __init__(self, spec: dict, seed: int, input_dim: int):
        self.spec = spec
        self.loop = spec["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"unknown loop {self.loop!r}")
        self.rate = spec.get("rate_per_s")       # open loop only
        self._rng = model.rng_for(seed, "traffic")
        self._lengths_set = _quantiles(spec["lengths"], CYCLE)
        self.max_length = int(self._lengths_set.max())
        self._lengths: list = []
        self._offsets: list = []
        self._unit: list = [0.0]         # unit-rate arrival times
        srng = model.rng_for(seed, "signal")
        self._signal = np.stack(
            [model.signal(srng, SIGNAL_STEPS + self.max_length)
             for _ in range(input_dim)], axis=-1)

    def _extend(self) -> None:
        self._lengths += self._rng.permutation(self._lengths_set).tolist()
        self._offsets += self._rng.integers(
            0, SIGNAL_STEPS, CYCLE).tolist()
        gaps = -np.log1p(-(np.arange(CYCLE) + 0.5) / CYCLE)
        gaps /= gaps.mean()
        t = self._unit[-1] + np.cumsum(self._rng.permutation(gaps))
        self._unit += t.tolist()

    def length(self, k: int) -> int:
        while k >= len(self._lengths):
            self._extend()
        return int(self._lengths[k])

    def inputs(self, k: int) -> np.ndarray:
        """Request ``k``'s input, (length, input_dim) float32."""
        n = self.length(k)
        off = self._offsets[k]
        return self._signal[off:off + n]

    def due(self, k: int) -> float:
        """Open loop: seconds from the schedule's start to request ``k``."""
        while k + 1 >= len(self._unit):
            self._extend()
        return self._unit[k + 1] / self.rate
