"""Observed serving: metrics, request traces and the compile-event log.

Runs the continuous-batching server from ``serve_async.py`` with the
observability layer switched on (`repro.obs` is a no-op until
``obs.configure()`` is called) and shows what each sink buys you:

* **metrics** — counters and fixed-bucket latency histograms; the
  summary prints exact p50/p99/p999 queue-wait, time-to-first-prediction
  and end-to-end latency, and the same registry renders a
  Prometheus-format scrape payload;
* **tracing** — every request threads a ``trace_id`` through its
  lifecycle spans (enqueue -> queued -> first_output -> serve), so one
  slow request can be reconstructed stage by stage from the flight
  recorder, which is also dumped as JSONL for offline digging;
* **events** — compile/retrace facts: the first trace of each rollout
  variant is expected, a ``retrace`` under steady traffic is a bug, and
  here it prints as a count you can alert on.

Run:  PYTHONPATH=src python examples/serve_observed.py
      PYTHONPATH=src python examples/serve_observed.py --dim 512
      PYTHONPATH=src python examples/serve_observed.py --trace-out t.jsonl
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.cache import enable_compile_cache
from repro import obs
from repro.core.esn import ESNConfig, fit_readout, init_esn, run_reservoir
from repro.serve import (AsyncReservoirServer, ReservoirEngine, ServeStats,
                         SubmitSpec)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "xla", "pallas"])
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--chunk-steps", type=int, default=16)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--utilization", type=float, default=0.8,
                    help="arrival rate as a fraction of service rate")
    ap.add_argument("--trace-out", default="serve_trace.jsonl",
                    help="path for the JSONL span dump")
    ap.add_argument("--metrics-out", default="serve_metrics.prom",
                    help="path for the Prometheus text payload")
    args = ap.parse_args()

    # instrumentation on *before* the engine exists, so the build and
    # every compile land in the event log
    obs.configure()

    cfg = ESNConfig(reservoir_dim=args.dim, element_sparsity=0.85,
                    output_dim=2, seed=0)
    params = init_esn(cfg)
    rng = np.random.default_rng(0)
    train_u = jnp.asarray(rng.standard_normal((400, 1)), jnp.float32)
    states = run_reservoir(params, train_u, engine="scan")
    targets = jnp.concatenate([train_u, jnp.roll(train_u, 1)], axis=-1)
    params = fit_readout(params, states, targets, lam=1e-2)
    engine = ReservoirEngine(params, backend=args.backend, stats=ServeStats())

    lengths = rng.integers(8, 97, args.requests)
    reqs = [SubmitSpec(rng.standard_normal((int(t), 1)).astype(np.float32),
                       uid=i)
            for i, t in enumerate(lengths)]
    total_steps = int(lengths.sum())

    # calibrated Poisson arrivals, same recipe as serve_async.py
    warm = jnp.asarray(
        rng.standard_normal((args.slots, args.chunk_steps, 1)), jnp.float32)
    warm_x0 = jnp.zeros((args.slots, args.dim), jnp.float32)
    preds, _ = engine.run_segment(warm, warm_x0)
    jax.block_until_ready(preds)                             # compile
    t0 = time.perf_counter()
    preds, _ = engine.run_segment(warm, warm_x0)
    jax.block_until_ready(preds)
    t_chunk = time.perf_counter() - t0
    service_rate = args.slots * args.chunk_steps / t_chunk
    mean_gap = float(np.mean(lengths)) / (args.utilization * service_rate)
    arrivals = np.cumsum(rng.exponential(mean_gap, args.requests))
    arrivals -= arrivals[0]

    compiles = obs.events().count("xla_trace") \
        + obs.events().count("pallas_trace")
    print(f"warmup done: {compiles} rollout variants compiled "
          f"(backend={engine.backend})")

    srv = AsyncReservoirServer(engine, n_slots=args.slots,
                               chunk_steps=args.chunk_steps,
                               stats=ServeStats())
    for r, at in zip(reqs, arrivals):
        srv.submit(r, arrival_time=float(at))
    results = srv.run()
    print(f"served {len(results)} requests, {total_steps} steps "
          f"in {srv.now * 1e3:.1f} ms of server time")

    # -- live metrics snapshot ---------------------------------------------
    print("\n== metrics snapshot (merged across label sets) ==")
    for name, val in sorted(obs.metrics().summary().items()):
        if isinstance(val, dict):
            print(f"  {name:28s} n={val['count']:<4d} "
                  f"p50={val['p50'] * 1e3:8.3f} ms  "
                  f"p99={val['p99'] * 1e3:8.3f} ms  "
                  f"p999={val['p999'] * 1e3:8.3f} ms")
        else:
            print(f"  {name:28s} {val:g}")

    # -- one request, reassembled from its trace ---------------------------
    slowest = max(results.values(),
                  key=lambda r: r.timings["latency_s"])
    tid = slowest.timings["trace_id"]
    print(f"\n== lifecycle of the slowest request (trace_id={tid}, "
          f"{slowest.timings['latency_s'] * 1e3:.2f} ms end to end) ==")
    for s in obs.tracer().spans(trace_id=tid):
        print(f"  {s.name:22s} {s.duration_s * 1e3:8.3f} ms "
              f"[{s.clock} clock] {s.attrs}")

    # -- compile-event ledger ----------------------------------------------
    retraces = obs.events().count("retrace")
    print(f"\ncompile events: {compiles} first traces at warmup, "
          f"{retraces} retraces under traffic"
          + (" (steady state held)" if retraces == 0 else "  <-- BUG"))

    # -- exports ------------------------------------------------------------
    n = obs.tracer().export_jsonl(args.trace_out)
    with open(args.metrics_out, "w") as fh:
        fh.write(obs.metrics().prometheus_text())
    print(f"dumped {n} spans to {args.trace_out} and the scrape payload "
          f"to {args.metrics_out}")
    obs.disable()
    print("OK")


if __name__ == "__main__":
    main()
