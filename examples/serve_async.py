"""Continuous-batching reservoir serving: queue -> slots -> chunked rollout.

Simulates a Poisson stream of variable-length prediction requests against
a trained reservoir and serves it two ways:

* **one-shot** — the classic ``ReservoirEngine.serve()``: wait until the
  whole request list exists, pad it into buckets, roll, answer.
* **continuous** — ``AsyncReservoirServer``: a fixed pool of batch slots,
  the engine rolled in ``chunk_steps`` segments, each live slot's
  reservoir state carried between chunks, finished sequences retired and
  queued ones admitted mid-flight.

Both produce identical predictions; the point is the clock.  The report
prints goodput (useful reservoir steps per second of makespan, measured
from the first arrival), queue waits, time-to-first-prediction and slot
occupancy.

Run:  PYTHONPATH=src python examples/serve_async.py
      PYTHONPATH=src python examples/serve_async.py --dim 512 --slots 16
      PYTHONPATH=src python examples/serve_async.py --backend pallas
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.cache import enable_compile_cache
from repro.core.esn import ESNConfig, fit_readout, init_esn, run_reservoir
from repro.serve import (AsyncReservoirServer, PaddingBucketer,
                         ReservoirEngine, ServeStats, SubmitSpec)


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
    args = ap.parse_args()

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

    # Poisson arrivals calibrated against one measured pool chunk.  The
    # warmup compiles the exact chunk program the scheduler runs
    # (predictions + carried final state at the pool shape).
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
    print(f"{args.requests} requests, {total_steps} steps total, arrivals "
          f"spread over {arrivals[-1] * 1e3:.1f} ms "
          f"(~{args.utilization:.0%} of service rate)")

    # -- one-shot: the batch exists only after the last arrival ------------
    bucketer = PaddingBucketer(len_buckets=(16, 32, 64, 96),
                               batch_buckets=(1, 2, 4, 8))
    engine.submit_many(reqs, bucketer=bucketer)              # warmup
    t0 = time.perf_counter()
    res_one = engine.submit_many(reqs, bucketer=bucketer)
    makespan_one = float(arrivals[-1]) + time.perf_counter() - t0

    # -- continuous: admit on arrival, chunk, retire, repeat ---------------
    srv = AsyncReservoirServer(engine, n_slots=args.slots,
                               chunk_steps=args.chunk_steps,
                               stats=ServeStats())
    handles = [srv.submit(r, arrival_time=float(at))
               for r, at in zip(reqs, arrivals)]
    res_cont = srv.run()
    makespan_cont = srv.now

    for uid, out in res_cont.items():
        np.testing.assert_allclose(np.asarray(out.output),
                                   np.asarray(res_one[uid].output),
                                   rtol=1e-4, atol=1e-6)
    print(f"\nboth paths served {len(res_cont)} requests with matching "
          f"predictions (backend={engine.backend})")
    print(f"  one-shot   : {total_steps / makespan_one:9.0f} steps/s goodput "
          f"({makespan_one * 1e3:.1f} ms makespan)")
    print(f"  continuous : {total_steps / makespan_cont:9.0f} steps/s goodput "
          f"({makespan_cont * 1e3:.1f} ms makespan, "
          f"{makespan_one / makespan_cont:.2f}x)")
    print("\nqueue stats:", srv.stats.render())
    worst = max(handles, key=lambda q: q.first_output_time - q.arrival_time)
    print(f"worst time-to-first-prediction: request {worst.uid} "
          f"({(worst.first_output_time - worst.arrival_time) * 1e3:.2f} ms "
          f"after arrival)")
    print("OK")


if __name__ == "__main__":
    main()
