"""Sharded reservoir serving: one FIFO, 8 shards, a mid-flight shard loss.

The reservoir matrix is fixed and replicated (the paper's premise), so
serving scale-out is pure batch-axis data parallelism:

    global FIFO ──► least-loaded admission ──► per-shard slot sub-pools
                                                  │ one shard_map call
                                                  ▼ per chunk
                                        8 x (slots, chunk_steps) rollouts
                                        (zero collectives in the hot loop)

This example streams a Poisson trace of prediction requests into a
:class:`~repro.dist.DistributedReservoirServer` over 8 virtual CPU
devices, kills 3 shards mid-flight, and shows the elastic path: the mesh
shrinks to the survivors, the engine rebuilds from the cached
ExecutionPlan, every in-flight sequence is re-admitted with its carried
reservoir state — no request lost, every prediction still matching the
single-device engine.

Run:  PYTHONPATH=src python examples/serve_sharded.py
      PYTHONPATH=src python examples/serve_sharded.py --shards 4 --fail 1
"""

import argparse
import os
import sys

# 8 virtual devices on one CPU; must be set before jax initializes
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.cache import enable_compile_cache
from repro.core.esn import ESNConfig, fit_readout, init_esn, run_reservoir
from repro.dist import DistributedReservoirServer, ShardedReservoirEngine
from repro.serve import ReservoirEngine, ServeStats, SubmitSpec


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=256)
    ap.add_argument("--shards", type=int, default=8)
    ap.add_argument("--slots-per-shard", type=int, default=4)
    ap.add_argument("--chunk-steps", type=int, default=16)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--fail", type=int, default=3,
                    help="shards to kill mid-flight (0 disables)")
    args = ap.parse_args()
    assert args.shards <= len(jax.devices()), \
        f"{args.shards} shards > {len(jax.devices())} devices"

    cfg = ESNConfig(reservoir_dim=args.dim, element_sparsity=0.85,
                    output_dim=2, seed=0)
    params = init_esn(cfg)
    rng = np.random.default_rng(0)
    train_u = jnp.asarray(rng.standard_normal((400, 1)), jnp.float32)
    states = run_reservoir(params, train_u, engine="scan")
    targets = jnp.concatenate([train_u, jnp.roll(train_u, 1)], axis=-1)
    params = fit_readout(params, states, targets, lam=1e-2)

    engine = ShardedReservoirEngine(params, n_shards=args.shards,
                                    stats=ServeStats())
    srv = DistributedReservoirServer(engine,
                                     slots_per_shard=args.slots_per_shard,
                                     chunk_steps=args.chunk_steps,
                                     chunk_time=1.0, stats=ServeStats())
    print(f"mesh: {args.shards} data shards x {args.slots_per_shard} slots, "
          f"chunk_steps={args.chunk_steps} (virtual clock, 1 tick/chunk)")

    lengths = rng.integers(16, 97, args.requests)
    reqs = [SubmitSpec(rng.standard_normal((int(t), 1)).astype(np.float32),
                       uid=i)
            for i, t in enumerate(lengths)]
    arrivals = np.cumsum(rng.exponential(0.15, args.requests))
    arrivals -= arrivals[0]
    for r, at in zip(reqs, arrivals):
        srv.submit(r, arrival_time=float(at))
    print(f"{args.requests} requests ({int(lengths.sum())} steps) arriving "
          f"over {arrivals[-1]:.1f} ticks\n")

    # serve a few chunks, then lose shards mid-flight
    fail_after = 4
    while srv.step():
        if args.fail and srv.reshards == 0 and srv.stats.chunks >= fail_after:
            live = srv.batcher.live
            plan = srv.shrink(failed=args.fail)
            print(f"tick {srv.now:.1f}: lost {args.fail} shards with {live} "
                  f"sequences in flight")
            print(f"  replan: {plan['n_shards_before']} -> "
                  f"{plan['n_shards_after']} shards, "
                  f"{plan['readmitted']} sequences re-admitted with carried "
                  f"state")
            for act in plan["actions"]:
                print(f"    - {act}")
    res = srv.results

    # every prediction must match the single-device engine
    single = ReservoirEngine(params, stats=ServeStats())
    for r in reqs:
        want = np.asarray(single.predictions(jnp.asarray(r.inputs)))
        np.testing.assert_allclose(np.asarray(res[r.uid].output), want,
                                   rtol=1e-4, atol=1e-6)
    print(f"\nall {len(res)}/{args.requests} requests served "
          f"(reshards={srv.reshards}, re-admitted={srv.readmitted}); "
          f"predictions match the single-device engine")
    print("\nserver stats:", srv.stats.render())
    print("\nper-shard (all topology epochs):", srv.shard_summary().render())
    print("OK")


if __name__ == "__main__":
    main()
