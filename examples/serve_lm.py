"""Batched LM serving driver on the repro.serve layer.

Loads a small LM (random weights — the point is the serving machinery),
takes a set of *variable-length* prompts, groups them through the serve
layer's :class:`PaddingBucketer` (one compiled prefill/decode pair per
bucket shape instead of one per request shape), decodes tokens, and
reports throughput + padding efficiency via :class:`ServeStats`.

With ``--frozen-sparse`` the final-projection matmul additionally runs
through the paper's FixedMatrix pipeline (int8 + CSD digit planes) and
reports the cost-model numbers — the LM-serving face of the paper's
fixed-matrix specialization.

Run:  PYTHONPATH=src python examples/serve_lm.py --tokens 16
"""

import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.cache import enable_compile_cache
from repro.configs.base import ModelConfig
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.transformer import LM
from repro.serve import PaddingBucketer, RolloutRequest, ServeStats

CFG = ModelConfig(
    name="serve-demo", family="dense", n_layers=4, d_model=256, n_heads=4,
    n_kv_heads=2, head_dim=64, d_ff=1024, vocab_size=2048,
    tie_embeddings=True, remat="none")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--min-prompt", type=int, default=24)
    ap.add_argument("--max-prompt", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--frozen-sparse", action="store_true")
    args = ap.parse_args()

    lm = LM(CFG)
    mesh = make_host_mesh()
    params = lm.init(jax.random.PRNGKey(0)).params
    rng = np.random.default_rng(0)

    # Ragged prompts -> padded microbatches via the serve layer's bucketer.
    reqs = [RolloutRequest(
                uid=i,
                inputs=rng.integers(
                    0, CFG.vocab_size,
                    (int(rng.integers(args.min_prompt, args.max_prompt + 1)),
                     1)).astype(np.int32))
            for i in range(args.requests)]
    bucketer = PaddingBucketer(len_buckets=(32, 64, 128, 256),
                               batch_buckets=(1, 2, 4, 8, 16))
    stats = ServeStats()
    decoded = {}
    step_cache = {}  # (bucket_len,) -> jitted prefill/decode pair

    for mb in bucketer.group(reqs):
        bpad, tpad, _ = mb.inputs.shape
        cache_len = tpad + args.tokens
        if tpad not in step_cache:
            step_cache[tpad] = (
                jax.jit(make_prefill_step(lm, mesh, cache_len)),
                jax.jit(make_decode_step(lm, mesh), donate_argnums=1))
        prefill, decode = step_cache[tpad]
        prompts = jnp.asarray(mb.inputs[:, :, 0])  # (bpad, tpad) tokens

        t0 = time.perf_counter()
        logits, caches = prefill(params, {"tokens": prompts})
        logits.block_until_ready()
        stats.record_call(batch=bpad, steps=tpad,
                          seconds=time.perf_counter() - t0,
                          real_steps=mb.real_steps)

        # Seed decode from each request's REAL last prompt token, not the
        # padded position.  (Right-padding does leave pad tokens in the KV
        # cache — acceptable for this random-weights demo; production
        # serving would mask them in attention.)
        lens = np.asarray(mb.lengths + [tpad] * (bpad - len(mb.requests)))
        tok = jnp.argmax(
            logits[jnp.arange(bpad), lens - 1], axis=-1
        ).astype(jnp.int32)[:, None]
        out = [tok]
        t0 = time.perf_counter()
        for _ in range(args.tokens - 1):
            logits, caches = decode(params, caches, tok)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(tok)
        jax.block_until_ready(tok)
        stats.record_call(batch=bpad, steps=args.tokens - 1,
                          seconds=time.perf_counter() - t0,
                          real_steps=(args.tokens - 1) * len(mb.requests))
        seq = np.concatenate([np.asarray(t) for t in out], axis=1)
        for j, req in enumerate(mb.requests):
            decoded[req.uid] = seq[j]

    assert len(decoded) == args.requests
    for uid, seq in decoded.items():
        assert seq.shape == (args.tokens,)
        assert (seq >= 0).all() and (seq < CFG.vocab_size).all()
    print(f"served {args.requests} ragged prompts "
          f"({args.min_prompt}-{args.max_prompt} tokens) through "
          f"{len(step_cache)} bucket shapes")
    print("serve stats:", stats.render())

    if args.frozen_sparse:
        from repro.core.sparse import FixedMatrix
        table = np.asarray(params["embed"], np.float32)  # (V, d) tied head
        t0 = time.perf_counter()
        fm = FixedMatrix.compile(table.T, weight_bits=8, mode="csd")
        t_compile = time.perf_counter() - t0
        cost = fm.fpga_cost()
        dense_bytes = table.size * 2
        plane_bytes = fm.ones / 8 + fm.plan().stats.blocks_nnz * 16
        print(f"\nfrozen-sparse head: compiled in {t_compile:.1f}s — "
              f"{fm.ones} ones, element sparsity {fm.element_sparsity:.2f}")
        print(f"  spatial-model latency {cost.latency_ns:.0f} ns/token; "
              f"bf16 stream {dense_bytes / 1e6:.1f} MB vs digit-plane "
              f"{plane_bytes / 1e6:.1f} MB per read")
    print("OK")


if __name__ == "__main__":
    main()
