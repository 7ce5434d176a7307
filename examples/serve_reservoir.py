"""Reservoir serving end-to-end: compile -> plan -> execute.

Builds a frozen reservoir (the paper's workload), trains its ridge
readout, and serves a stream of variable-length rollout requests through
the fused batched engine — which now answers with *predictions* (``W_out``
fused into the rollout epilogue), not state trajectories.  Prints the
shared ExecutionPlan's compile/cost summary (what was culled, how the
rollout bands under the VMEM budget, the paper's FPGA numbers) and the
throughput/padding statistics.

Run:  PYTHONPATH=src python examples/serve_reservoir.py --dim 512
      PYTHONPATH=src python examples/serve_reservoir.py --mode int8-csd
      PYTHONPATH=src python examples/serve_reservoir.py --backend pallas
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.cache import enable_compile_cache
from repro.core.esn import (ESNConfig, fit_readout, init_esn, predict,
                            run_reservoir)
from repro.launch.report import plan_table
from repro.serve import (PaddingBucketer, ReservoirEngine, RolloutRequest,
                         ServeStats, SubmitSpec)


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=512)
    ap.add_argument("--mode", default="fp32",
                    choices=["fp32", "int8-pn", "int8-csd"])
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "xla", "pallas"])
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-len", type=int, default=96)
    args = ap.parse_args()

    cfg = ESNConfig(reservoir_dim=args.dim, element_sparsity=0.85,
                    mode=args.mode, seed=0)
    params = init_esn(cfg)

    # one shared compile: the plan below feeds every backend and the report
    plan = params.w.plan()
    print("=== ExecutionPlan (compile once, execute everywhere) ===")
    print(plan.describe())
    print(plan_table([plan]))

    # train the readout on a short teacher signal, then serve predictions
    rng = np.random.default_rng(0)
    train_u = jnp.asarray(rng.standard_normal((400, 1)), jnp.float32)
    states = run_reservoir(params, train_u, engine="scan")
    targets = jnp.concatenate([train_u, jnp.roll(train_u, 1)], axis=-1)
    params = fit_readout(params, states, targets, lam=1e-2)

    engine = ReservoirEngine(params, backend=args.backend,
                             stats=ServeStats())
    reqs = [RolloutRequest(
                uid=i,
                inputs=rng.standard_normal(
                    (int(rng.integers(8, args.max_len + 1)), 1)
                ).astype(np.float32))
            for i in range(args.requests)]
    bucketer = PaddingBucketer(len_buckets=(16, 32, 64, 128),
                               batch_buckets=(1, 2, 4, 8, 16))

    results = {uid: r.output for uid, r in
               engine.submit_many(
                   [SubmitSpec(q.inputs, uid=q.uid) for q in reqs],
                   bucketer=bucketer).items()}           # predictions!
    print(f"\nserved {len(results)} rollout requests -> predictions "
          f"(dim={args.dim}, mode={args.mode}, backend={engine.backend})")
    print("serve stats:", engine.stats.render())

    # spot-check one request against predict() over the per-step scan
    probe = reqs[0]
    want = np.asarray(predict(params, run_reservoir(
        params, jnp.asarray(probe.inputs), engine="scan")))
    got = np.asarray(results[probe.uid])
    assert got.shape == (probe.length, 2), got.shape
    err = np.abs(got - want).max()
    assert err < 1e-3, err
    print(f"parity vs scan+predict baseline: max |diff| = {err:.2e}")

    # same requests, states contract: one SubmitSpec field away
    specs = [SubmitSpec(r.inputs, uid=r.uid, want_states=True)
             for r in reqs[:2]]
    states_res = engine.submit_many(specs, bucketer=bucketer)
    assert states_res[0].states.shape == (reqs[0].length, args.dim)

    # single-shot latency: fused-readout serve vs states-then-matmul
    u = jnp.asarray(rng.standard_normal((8, 64, 1)), jnp.float32)
    for name, fn in (
            ("two-pass", lambda: jax.block_until_ready(
                predict(params, engine.rollout(u)))),
            ("fused", lambda: jax.block_until_ready(
                engine.predictions(u)))):
        fn()  # warmup
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        print(f"  {name:8s}: {8 * 64 / dt:9.0f} steps/s "
              f"({dt * 1e3:.1f} ms for 8x64)")
    print("OK")


if __name__ == "__main__":
    main()
