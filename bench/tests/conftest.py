"""The benchmark's own tests: ``python -m pytest bench/tests`` from the
root of a checkout.  They import the harness modules by file, and the
program under test from ``src``."""

import json
import os
import sys
from pathlib import Path

import pytest

# four CPU devices, for the four-chip cell's path (set before JAX starts)
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=4")))

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def small_cfg(name: str = "esn1024_int8", dim: int = 256) -> dict:
    """A configuration of the benchmark at a width a CPU test can hold,
    with the configuration's own limits."""
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    cfg["reservoir_dim"] = dim
    return cfg


def small_spec(loop: str = "closed", cfg: dict | None = None,
               chips: int = 1) -> dict:
    """A cell at a CPU test's size, reporting every end-to-end metric;
    ``chips`` > 1 serves it sharded over that many CPU devices, with the
    pool's slot count per shard."""
    if loop == "closed":
        mix = {"loop": "closed", "clients": 8 * chips,
               "lengths": {"dist": "uniform", "min": 16, "max": 64},
               "pool": {"slots": 4, "chunk_steps": 8},
               "warmup_chunks": 10, "sample": 6}
    else:
        mix = {"loop": "open", "rate_per_s": 40.0,
               "lengths": {"dist": "loguniform", "min": 4, "max": 32},
               "pool": {"slots": 4, "chunk_steps": 8},
               "warmup_s": 0.3, "sample": 6}
    e2e = [{"name": n, "unit": "u"} for n in
           ("steps_per_s", "setup_s", "latency_p50_ms", "latency_p95_ms")]
    return {"name": f"small.{loop}", "chips": chips,
            "cfg": small_cfg() if cfg is None else cfg, "traffic": mix,
            "end_to_end": e2e if loop == "open" else e2e[:2],
            "per_layer": []}


@pytest.fixture
def cpu_devices():
    import jax
    return jax.devices()[:1]


@pytest.fixture
def cpu_devices_x4():
    import jax
    return jax.devices()[:4]
