#!/usr/bin/env python3
"""Record the small trace that ``test_trace_reduce.py`` reduces.

    python bench/tests/record_trace.py OUT.xplane.pb      # on the chip

Four rounds of one jitted matmul between host sleeps, inside the
benchmark's own annotations (``bench.window`` around all of it;
``bench.submit``, ``bench.step`` and ``bench.wait`` inside), traced with
the profiler's Python tracer off, as ``run.py`` traces.  Prints the
rounds' host times as JSON so the test's expectations can be checked
against them.
"""

from __future__ import annotations

import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp


def main(out: str) -> int:
    f = jax.jit(lambda x, w: jnp.tanh(x @ w) @ w)
    x = jnp.ones((512, 1024), jnp.float32)
    w = jnp.full((1024, 1024), 1e-3, jnp.float32)
    f(x, w).block_until_ready()
    d = tempfile.mkdtemp(prefix="bench-fixture-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("bench.submit"):
                time.sleep(0.001)
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x, w).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.002)
    window = time.perf_counter() - t
    jax.profiler.stop_trace()
    src = next(Path(d).rglob("*.xplane.pb"))
    shutil.copy(src, out)
    shutil.rmtree(d, ignore_errors=True)
    print(json.dumps({"window_s": window, "bytes": Path(out).stat().st_size,
                      "device_kind": jax.devices()[0].device_kind}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
