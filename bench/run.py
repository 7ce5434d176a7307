#!/usr/bin/env python3
"""On-chip benchmark of the reservoir server: one cell, one seed, one run.

    python bench/run.py --workload esn1024.batch --seed 7 --seconds 10 --trace 0

Run from the root of a checkout.  ``BENCHMARK.json`` names the cell; the
cell names a configuration (``configs/<name>.json``: the reservoir, its
source, and the limits of the check) and a traffic mix
(``traffic/<name>.json``, read by ``traffic.py``).  Each per-layer metric
is read by ``metrics/<name>.py``, named by the metric's name up to its
first dot, so ``step_ms.thru`` and ``step_ms.tail`` share ``step_ms.py``.

A run sets up (the configuration's reservoir and readout, the engine and
server of the program under test, warm-up traffic until the pool is in
steady state), measures for ``--seconds``, then checks a seeded sample of
the window's finished requests against the plain reference of
``model.py``.  Earlier lines of stdout are diagnostics; the compared
numbers with their limits are the last lines of stderr; the last line of
stdout is the result.  ``--trace 1`` measures the per-layer metrics under
the profiler and the program's own tracer instead of the end-to-end ones.

Without a TPU, or with fewer chips than the cell asks for, it exits 3 and
prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import model  # noqa: E402
import traffic as traffic_mod  # noqa: E402
import work  # noqa: E402

CACHE_DIR = BENCH / ".jax_cache"
GRACE_S = 60.0              # how long a due answer may come after the close
TRACE_SPANS = 1 << 18       # capacity of the program's span recorder
SLOW_STEP_S = 0.05          # a server.step() this long is logged apart


def say(**fields) -> None:
    """One diagnostic line (never the last line of a run)."""
    print(json.dumps(fields, default=str), flush=True)


def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with everything it names."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((root / entry["file"]).read_text())

    def reports(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    end_to_end = [m for m in bench["end_to_end"] if reports(m)]
    moved = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return {"name": name, "chips": cell["chips"], "cfg": cfg,
            "traffic": traffic_mod.load(cell["traffic"], BENCH),
            "end_to_end": end_to_end, "per_layer": per_layer}


def enable_cache() -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout, for every program however fast it compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


class CompileCounter:
    """Traces and backend compiles that JAX reports while ``on``."""

    def __init__(self):
        import jax
        self.on = False
        self.traces = self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, _secs: float, **_kw) -> None:
        if not self.on:
            return
        if name == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
        elif name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1


class GcWatch:
    """The interpreter's garbage-collection pauses while ``on``."""

    def __init__(self):
        self.on = False
        self.pauses: list = []
        self._t0 = 0.0
        gc.callbacks.append(self._event)

    def _event(self, phase: str, _info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.on:
            self.pauses.append(time.perf_counter() - self._t0)

    def close(self) -> None:
        gc.callbacks.remove(self._event)


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def build_server(cfg: dict, weights: model.Weights, pool: dict, chips: int):
    """The program under test, given only the weights and the pool shape."""
    import jax.numpy as jnp
    from repro.core.esn import ESNConfig, ESNParams
    from repro.core.sparse import FixedMatrix
    from repro.serve import AsyncReservoirServer, ReservoirEngine

    fields = {k: cfg[k] for k in ESNConfig.__dataclass_fields__ if k in cfg}
    config = ESNConfig(**fields)
    w = FixedMatrix.compile(weights.dense, weight_bits=config.weight_bits,
                            mode=config.digit_mode, block=config.block)
    params = ESNParams(w=w, w_in=jnp.asarray(weights.w_in),
                       w_out=jnp.asarray(weights.w_out), config=config)
    if chips == 1:
        engine = ReservoirEngine(params)
        server = AsyncReservoirServer(engine, n_slots=pool["slots"],
                                      chunk_steps=pool["chunk_steps"])
    else:
        from repro.dist import (DistributedReservoirServer,
                                ShardedReservoirEngine)
        from repro.launch.mesh import make_data_mesh
        engine = ShardedReservoirEngine(params, mesh=make_data_mesh(chips))
        server = DistributedReservoirServer(
            engine, slots_per_shard=pool["slots"],
            chunk_steps=pool["chunk_steps"])
    return engine, server


class Session:
    """The client side: submits requests of the mix, steps the server,
    and keeps every answer with the host times it was due and arrived."""

    def __init__(self, server, mix: traffic_mod.Traffic, origin: float,
                 out_dim: int):
        from repro.serve import SubmitSpec
        self._spec = SubmitSpec
        self.server = server
        self.mix = mix
        self.out_dim = out_dim
        self.origin = origin            # open loop: when request 0 is due
        self.next = 0                   # index of the next request
        self.inflight: dict = {}        # k -> (due, submitted)
        # k -> (due, submitted, finished, predictions or None if the answer
        # failed or came malformed)
        self.done: dict = {}
        self.step_s: list = []          # wall time of each server.step()
        # the steps over SLOW_STEP_S: (start in s from the window's
        # opening, wall s, this thread's CPU s), to tell a stall spent
        # computing from one spent waiting
        self.slow: list = []
        self.t_open = 0.0
        self.recording = False

    def due(self, k: int) -> float:
        return self.origin + self.mix.due(k)

    def submit_warm(self, u) -> None:
        """One request outside the mix (key ``"warm"``)."""
        now = time.perf_counter()
        self.server.submit(self._spec(u, uid="warm"))
        self.inflight["warm"] = (now, now)

    def submit(self, due: float) -> None:
        k = self.next
        self.next += 1
        u = self.mix.inputs(k)
        self.server.submit(self._spec(u, uid=k))
        self.inflight[k] = (due, time.perf_counter())

    def step(self) -> None:
        c0 = time.thread_time()
        t0 = time.perf_counter()
        self.server.step()
        t1 = time.perf_counter()
        if self.recording:
            self.step_s.append(t1 - t0)
            if t1 - t0 > SLOW_STEP_S:
                self.slow.append((t0 - self.t_open, t1 - t0,
                                  time.thread_time() - c0))
        results = self.server.results
        while results:
            k, res = results.popitem()
            due, sent = self.inflight.pop(k)
            if k != "warm":
                self.done[k] = (due, sent, t1, answer(
                    res, self.mix.length(k), self.out_dim))

    @property
    def busy(self) -> bool:
        return bool(self.inflight)


def closed_loop(sess: Session, clients: int, *, until: float | None = None,
                chunks: int | None = None) -> None:
    """Keep ``clients`` requests outstanding; stop at the host time
    ``until`` or after ``chunks`` steps."""
    n = 0
    while True:
        if until is not None and time.perf_counter() >= until:
            return
        if chunks is not None and n >= chunks:
            return
        with annotate("bench.submit"):
            while len(sess.inflight) < clients:
                sess.submit(time.perf_counter())
        with annotate("bench.step"):
            sess.step()
        n += 1


def open_loop(sess: Session, until: float, *, also=None) -> list:
    """Send each request when it is due, until the host time ``until`` (and
    past it while ``also()`` holds); returns each send's lateness."""
    late = []
    while True:
        now = time.perf_counter()
        if now >= until and (also is None or not also()):
            return late
        with annotate("bench.submit"):
            while sess.due(sess.next) <= now:
                due = sess.due(sess.next)
                sess.submit(due)
                late.append(sess.inflight[sess.next - 1][1] - due)
        if sess.busy:
            with annotate("bench.step"):
                sess.step()
        else:
            with annotate("bench.wait"):
                time.sleep(max(0.0, min(sess.due(sess.next), until)
                               - time.perf_counter()))


def percentile(values: list, q: float) -> float:
    """The ``q``-quantile (nearest rank) of ``values``; inf counts."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def check(cfg: dict, weights, mix, sess: Session, window: list, seed: int,
          k_sample: int, controls: dict | None = None) -> dict:
    """Compare a seeded sample of the window's answers, the longest among
    them, with the plain reference.  Each of ``controls`` (name ->
    ``model.reference_preds`` keywords) is the reference in a lower
    precision, read in the program's place on the same sample."""
    ok = sorted(window)
    if not ok:
        return {"sampled": 0, "max_abs_err": math.inf, "rms_err": math.inf}
    longest = max(ok, key=lambda k: (mix.length(k), -k))
    rest = [k for k in ok if k != longest]
    rng = model.rng_for(seed, "sample")
    n = min(k_sample - 1, len(rest))
    sample = [longest] + sorted(rng.choice(rest, n, replace=False).tolist())
    inputs = [mix.inputs(k) for k in sample]
    served = [sess.done[k][3] for k in sample]
    refs = model.reference_preds(cfg, weights, inputs)
    out = compare(served, refs) | {"sampled": len(sample)}
    if controls:
        out["controls"] = {
            name: compare(model.reference_preds(cfg, weights, inputs, **kw),
                          refs) for name, kw in controls.items()}
    return out


def compare(served: list, refs: list) -> dict:
    """Widest and RMS gap between served and reference predictions."""
    import numpy as np
    err = np.concatenate([(np.asarray(s, np.float64) - r).ravel()
                          for s, r in zip(served, refs)])
    if not np.all(np.isfinite(err)):
        return {"max_abs_err": math.inf, "rms_err": math.inf}
    return {"max_abs_err": float(np.max(np.abs(err))),
            "rms_err": float(np.sqrt(np.mean(err ** 2)))}


def answer(res, length: int, out_dim: int):
    """A served result's predictions, or None if it failed or came
    malformed (another shape, a non-finite value)."""
    import numpy as np
    if getattr(res, "status", None) != "ok" or res.preds is None:
        return None
    p = np.asarray(res.preds)
    if p.shape != (length, out_dim) or not np.all(np.isfinite(p)):
        return None
    return p


def reduce_trace(trace_dir: str):
    import trace_reduce
    paths = list(Path(trace_dir).rglob("*.xplane.pb"))
    return trace_reduce.reduce(paths[0]) if paths else None


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             devices: list, controls: dict | None = None) -> dict:
    """One run of one cell; returns the result line's fields (and the
    diagnostics printed on the way).  ``controls``: see :func:`check`;
    their readings come back under ``controls``."""
    import jax
    import numpy as np
    sys.path.insert(0, str(ROOT / "src"))
    from repro import obs

    cfg, mix_spec, chips = spec["cfg"], spec["traffic"], spec["chips"]
    pool = mix_spec["pool"]
    compiles = CompileCounter()
    gcw = GcWatch()

    # the benchmark's own work, outside set-up: the reservoir and its
    # fitted readout (the yardstick's, as the reference is), the traffic
    t_weights = time.perf_counter()
    weights = model.make_weights(cfg)
    mix = traffic_mod.Traffic(mix_spec, seed, cfg["input_dim"])
    t_setup = time.perf_counter()
    say(weights_and_traffic_s=t_setup - t_weights)
    engine, server = build_server(cfg, weights, pool, chips)
    program = engine.program
    say(program=program.describe() if program is not None
        else engine.xla_schedule, backend=engine.backend,
        matmul_terms=getattr(program, "n_matmul_terms", None),
        shiftadd_terms=getattr(program, "n_shiftadd_terms", None),
        resident_bytes=getattr(program, "resident_bytes", None),
        slots=server.batcher.n_slots, chunk_steps=server.batcher.chunk_steps,
        chips=chips)

    # warm-up traffic, led by a request as long as the mix's longest, so
    # the pool's input lanes reach their final count (and the gather its
    # final shape) before the window
    open_ = mix.loop == "open"
    sess = Session(server, mix, time.perf_counter(), cfg["output_dim"])
    sess.submit_warm(np.zeros((mix.max_length, cfg["input_dim"]), np.float32))
    if open_:
        t_open = sess.origin + mix_spec["warmup_s"]
        open_loop(sess, t_open)
    else:
        closed_loop(sess, mix_spec["clients"], chunks=mix_spec["warmup_chunks"])
    setup_s = time.perf_counter() - t_setup

    # the window
    stats = server.stats
    live0, total0 = stats.slot_steps_live, stats.slot_steps_total
    traces0 = sum(engine.trace_counts.values())
    trace_dir = None
    if trace:
        obs.configure(metrics=False, events=False, trace_capacity=TRACE_SPANS)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles.on = gcw.on = True
    sess.recording = True
    first_k = sess.next
    with annotate("bench.window"):
        t0 = sess.t_open = time.perf_counter()
        if open_:
            t0 = sess.t_open = max(t0, t_open)
            late = open_loop(sess, t0 + seconds)
        else:
            late = []
            closed_loop(sess, mix_spec["clients"], until=t0 + seconds)
        t1 = time.perf_counter()
    sess.recording = False
    compiles.on = gcw.on = False
    gcw.close()
    live1, total1 = stats.slot_steps_live, stats.slot_steps_total
    retraces = sum(engine.trace_counts.values()) - traces0
    dispatch_s = []
    if trace:
        dispatch_s = [s.duration_s for s in obs.tracer().spans(
            name="engine.dispatch") if t0 <= s.start <= t1]
        obs.disable()

    # the window's requests: open loop, those due in it (each waited for
    # up to GRACE_S past the close); closed loop, those finished in it
    if open_:
        lo = first_k
        while lo > 0 and sess.due(lo - 1) >= t0:
            lo -= 1
        while sess.due(lo) < t0:
            lo += 1
        hi = lo
        while sess.due(hi) < t1:
            hi += 1
        due = range(lo, hi)
        open_loop(sess, t1, also=lambda: time.perf_counter() < t1 + GRACE_S
                  and not all(k in sess.done for k in reversed(due)))
        window = [k for k in due if k in sess.done]
        attempted = len(due)
        latencies = [(sess.done[k][2] - sess.done[k][0]) if k in sess.done
                     else math.inf for k in due]
    else:
        window = [k for k, d in sess.done.items() if t0 <= d[2] <= t1]
        attempted = len(window)
        latencies = []
    if trace:
        jax.profiler.stop_trace()
    bad = [k for k in window if sess.done[k][3] is None]
    failed = attempted - len(window) + len(bad)
    window = [k for k in window if k not in bad]
    delivered = [k for k in window if t0 <= sess.done[k][2] <= t1]
    steps = sum(mix.length(k) for k in delivered)
    say(window_s=t1 - t0, requests_attempted=attempted,
        requests_delivered=len(delivered), steps_delivered=steps,
        chunks=(total1 - total0) // (server.batcher.n_slots
                                     * server.batcher.chunk_steps),
        retraces_in_window=retraces, traces_in_window=compiles.traces,
        compiles_in_window=compiles.compiles,
        generator_late_p95_ms=1e3 * percentile(late, 0.95) if late else None,
        step_max_ms=1e3 * max(sess.step_s, default=0.0),
        steps_over_50ms=len(sess.slow),
        slow_steps=[[round(x, 6) for x in row] for row in sess.slow[:40]],
        gc_pauses=len(gcw.pauses), gc_s=sum(gcw.pauses),
        gc_max_ms=1e3 * max(gcw.pauses, default=0.0),
        compile_cache=str(CACHE_DIR))

    memory = [d.memory_stats() or {} for d in devices]
    memory_peak = max(m.get("peak_bytes_in_use", 0) for m in memory)
    reduced = reduce_trace(trace_dir) if trace else None
    if trace_dir is not None:
        shutil.rmtree(trace_dir, ignore_errors=True)

    values = {"setup_s": setup_s, "steps_per_s": steps / (t1 - t0)}
    if latencies:
        values["latency_p50_ms"] = 1e3 * percentile(latencies, 0.50)
        values["latency_p95_ms"] = 1e3 * percentile(latencies, 0.95)
    ctx = {"cfg": cfg, "chips": chips, "device_kind": devices[0].device_kind,
           "work": work.work_of(weights.q, cfg),
           "live_steps": live1 - live0, "total_steps": total1 - total0,
           "launches": (total1 - total0) // (server.batcher.n_slots
                                             * server.batcher.chunk_steps),
           "rows_per_chip": server.batcher.n_slots // chips,
           "chunk_steps": server.batcher.chunk_steps,
           "step_s": sess.step_s, "dispatch_s": dispatch_s, "trace": reduced}

    # free the program's state before the reference runs
    del engine, server, stats
    sess.server = None
    gc.collect()
    result = check(cfg, weights, mix, sess, window, seed, mix_spec["sample"],
                   controls)

    limits = cfg["check"]
    checks = {"failed": {"value": failed, "limit": 0}}
    for key in ("max_abs_err", "rms_err"):
        checks[key] = {"value": result[key], "limit": limits[key]}
    correct = (result["sampled"] > 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    out = {"correct": correct, "attempted": attempted, "failed": failed}
    if trace:
        metrics = {}
        for m in spec["per_layer"]:
            value = read_metric(m["name"], ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    out |= {"metrics": metrics, "device": device}
    if reduced is not None:
        device |= {"busy_s": reduced["busy_s"],
                   "window_s": reduced["window_s"]}
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in list(reduced["ops"].items())[:10]],
            "idle_gaps": [[k, v] for k, v in
                          list(reduced["idle"].items())[:10]]}
        say(trace_programs=reduced["programs"],
            trace_ops=list(reduced["ops"].items())[:40])
    if controls:
        out["controls"] = result["controls"]
    out["checks"] = checks
    return out


def finite(x):
    """``x`` with every non-finite number (a failed request's latency, a
    comparison that found NaN) as null, so the line stays JSON."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return None
    return x


def read_metric(name: str, ctx: dict):
    """``metrics/<name up to its first dot>.py``'s reading, or None."""
    import importlib.util
    base = name.split(".")[0]
    path = BENCH / "metrics" / f"{base}.py"
    mod_spec = importlib.util.spec_from_file_location(f"metric_{base}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(ctx)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 3
    say(compile_cache=enable_cache())
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: JAX found platform {devices[0].platform!r}, not a "
              "TPU; the benchmark runs only on the chip", file=sys.stderr)
        return 3
    if len(devices) < spec["chips"]:
        print(f"bench: {args.workload} needs {spec['chips']} chips, JAX "
              f"found {len(devices)}", file=sys.stderr)
        return 3
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                   devices[:spec["chips"]])
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(finite(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
