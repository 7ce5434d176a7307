"""End-to-end LM training driver: config -> mesh -> steps -> checkpoints.

Exercises the full production path on whatever devices exist (CPU here):
deterministic data stream, jitted train step with the same sharding rules
the 512-chip dry-run uses, async checkpointing with auto-resume, straggler
watchdog, and an optional simulated host failure that goes through the
elastic re-plan + checkpoint-restore path.

Presets:
  tiny  (~11M params, default)  - a few hundred steps in minutes on CPU
  100m  (~124M params)          - the assignment's ~100M driver; same code,
                                  run with --steps 300 on real hardware

Run:  PYTHONPATH=src python examples/train_lm.py --steps 60
      PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 4
      PYTHONPATH=src python examples/train_lm.py --simulate-failure
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.cache import enable_compile_cache
from repro.checkpoint import store
from repro.configs.base import ModelConfig
from repro.data.pipeline import LMStreamConfig, lm_batch
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_train_step
from repro.models.transformer import LM
from repro.optim import adamw
from repro.runtime.elastic import StragglerWatchdog, replan_after_failure

PRESETS = {
    "tiny": ModelConfig(
        name="tiny-lm", family="dense", n_layers=4, d_model=256, n_heads=4,
        n_kv_heads=2, head_dim=64, d_ff=1024, vocab_size=2048,
        tie_embeddings=True, remat="none"),
    "100m": ModelConfig(
        name="lm-100m", family="dense", n_layers=12, d_model=768, n_heads=12,
        n_kv_heads=4, head_dim=64, d_ff=3072, vocab_size=32768,
        tie_embeddings=True, remat="full"),
}


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--simulate-failure", action="store_true",
                    help="kill-and-recover mid-run through the elastic path")
    args = ap.parse_args()

    cfg = PRESETS[args.preset]
    lm = LM(cfg)
    mesh = make_host_mesh()
    print(f"preset={args.preset} params={lm.param_count():,} "
          f"devices={len(jax.devices())}")

    stream = LMStreamConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                            global_batch=args.batch, seed=0)
    opt_cfg = adamw.AdamWConfig(lr=3e-3, warmup_steps=20,
                                total_steps=max(args.steps, 100))

    params = lm.init(jax.random.PRNGKey(0)).params
    opt = adamw.init_state(params)
    state = {"params": params, "opt": opt}

    start = 0
    resumed = store.latest_step(args.ckpt_dir)
    if resumed is not None:
        state = store.restore(state, args.ckpt_dir, resumed)
        start = resumed + 1
        print(f"resumed from checkpoint step {resumed}")

    step_fn = jax.jit(make_train_step(lm, mesh, opt_cfg), donate_argnums=0)
    ck = store.Checkpointer(args.ckpt_dir, every=args.ckpt_every, keep=2)
    wd = StragglerWatchdog(threshold=4.0)

    losses = []
    for step in range(start, args.steps):
        t0 = time.perf_counter()
        batch = {k: jnp.asarray(v) for k, v in
                 lm_batch(stream, step).items()}
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])
        wd.record(step, time.perf_counter() - t0)
        losses.append(loss)
        ck.maybe_save(state, step)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {loss:7.4f} "
                  f"lr {float(metrics['lr']):.2e} "
                  f"gnorm {float(metrics['grad_norm']):.2f} "
                  f"({time.perf_counter() - t0:.2f}s)")

        if args.simulate_failure and step == args.steps // 2:
            print("\n--- simulating host failure: 16 of 256 devices lost ---")
            plan = replan_after_failure(256, failed=16, model_parallel=16)
            for action in plan["actions"]:
                print("   ", action)
            print(f"    new mesh: {plan['mesh_shape']} {plan['mesh_axes']}")
            ck.finalize()
            resumed = store.latest_step(args.ckpt_dir)
            assert resumed is not None, "no verified checkpoint to resume!"
            state = store.restore(state, args.ckpt_dir, resumed)
            print(f"    restored verified checkpoint step {resumed}; "
                  f"resuming\n")

    ck.finalize()
    first = np.mean(losses[:5]) if len(losses) >= 5 else losses[0]
    last = np.mean(losses[-5:])
    print(f"\nloss {first:.3f} -> {last:.3f} over {len(losses)} steps "
          f"(stragglers flagged: {len(wd.flagged)})")
    if len(losses) >= 40:
        assert last < first - 0.3, "training did not reduce loss"
        print("OK: loss decreased")


if __name__ == "__main__":
    main()
