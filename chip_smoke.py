#!/usr/bin/env python3
"""Bring-up smoke: the reservoir serving path, compiled, on a TPU.

One process drives the path a user calls — ``SubmitSpec`` ->
``AsyncReservoirServer`` -> ``ReservoirEngine`` -> kernel — and checks each
served prediction against a plain ``jax.numpy`` reference rolled under
``jax.default_matmul_precision("highest")``.  Matrices, readout signal and
traffic are all made from ``--seed``.  Run from the repository root:

    python chip_smoke.py              # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4    # sharded serving on four chips, only

Diagnostics go to earlier lines.  The last line of stdout is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``.  A
failed phase, a diff over its bound, a retrace after warm-up, or a
platform other than the TPU ends the script non-zero without that line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.esn_paper import LARGE_1024  # noqa: E402
from repro.core.esn import (ESNConfig, ESNParams, fit_readout,  # noqa: E402
                            init_esn, predict, run_reservoir)
from repro.dist import (DistributedReservoirServer,  # noqa: E402
                        ShardedReservoirEngine)
from repro.kernels.platform import pallas_interpret  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402
from repro.serve import (AsyncReservoirServer, ReservoirEngine,  # noqa: E402
                         SubmitSpec)

# Bounds on |served - reference| over every predicted step, as (max, RMS).
# The predictions reach about 1.4 in magnitude, with a standard deviation
# of about 0.45; the RMS bound is what keeps a wrong-but-bounded answer
# from passing under the max bound.
#
# int8, both backends: the integer recurrent product is exact, the
# one-deep input projection is one f32 multiply (the Pallas kernels do it
# on the VPU; XLA takes no MXU pass for it either) and the readout runs at
# full f32 precision, so a path differs from the reference only in the
# readout's f32 summation order (XLA: 3e-6 on a TPU v5e at seed 0).  What
# the bound leaves room for is a tanh ulp flipping a few requantized state
# digits (1/127 of a state unit each, times a readout weight).  Dropping
# the shift-add terms of a 98%-sparse matrix drifts 0.13 max, 0.048 RMS
# (tests/test_chip_smoke.py; 2.6, 1.1 at phase d's size on a v5e), well
# over it.
INT8_TOL = (0.05, 0.01)
# fp32: the recurrent product itself runs on bfloat16 operands by default
# (ROADMAP 1.4 decides whether the fp32 cells pay for "highest"), so every
# step carries a relative error near 3e-3 into a state that the echo-state
# contraction keeps bounded.  On a v5e at seed 0 the pipelined kernel
# drifts up to 0.16 max, 0.033 RMS (XLA's dense path: 0.09 max).  The
# bounds keep a factor of about 2.5.
FP32_TOL = (0.4, 0.1)

N_SLOTS = 64
CHUNK_STEPS = 16
N_REQUESTS = 128
MIN_LEN, MAX_LEN = 64, 512
TRAIN_STEPS = 2048
WASHOUT = 128
RIDGE = 1e-2


class SmokeFailure(AssertionError):
    """A phase ran but its result breaks the smoke's contract."""


def say(**fields) -> None:
    """One diagnostic line (never the last line of a passing run)."""
    print(json.dumps(fields, default=str), flush=True)


def signal(rng: np.random.Generator, n: int) -> tuple:
    """A seeded sum of three sinusoids plus noise: (inputs, next-step
    targets), each (n, 1) float32."""
    t = np.arange(n + 1)
    freq = rng.uniform(0.01, 0.05, 3)
    phase = rng.uniform(0.0, 2 * np.pi, 3)
    s = sum(a * np.sin(2 * np.pi * f * t + p)
            for a, f, p in zip((0.5, 0.3, 0.2), freq, phase))
    s = s + 0.02 * rng.standard_normal(n + 1)
    s = s.astype(np.float32)[:, None]
    return s[:-1], s[1:]


def build_params(cfg: ESNConfig, seed: int,
                 train_steps: int = TRAIN_STEPS) -> ESNParams:
    """The reservoir of ``cfg`` (seeded) with its readout ridge-fit on a
    seeded next-step prediction task, states from the reference rollout."""
    params = init_esn(dataclasses.replace(cfg, seed=seed))
    u, y = signal(np.random.default_rng(seed + 1), train_steps)
    with jax.default_matmul_precision("highest"):
        states = run_reservoir(params, jnp.asarray(u), engine="scan")
    return fit_readout(params, states, jnp.asarray(y), lam=RIDGE,
                       washout=min(WASHOUT, train_steps // 4))


def make_requests(seed: int, n: int = N_REQUESTS, min_len: int = MIN_LEN,
                  max_len: int = MAX_LEN) -> list:
    """``n`` seeded request inputs, (T_i, 1) each, T_i in [min_len,
    max_len]."""
    rng = np.random.default_rng(seed + 2)
    lengths = rng.integers(min_len, max_len + 1, n)
    return [signal(rng, int(t))[0] for t in lengths]


def reference(params: ESNParams, inputs: list) -> list:
    """Plain jnp predictions per request: the per-step scan (no engine, no
    kernel) and the readout, all at highest matmul precision.  Requests
    are zero-padded to one batch; the rollout is causal, so padding never
    reaches a request's own steps."""
    t_max = max(u.shape[0] for u in inputs)
    batch = np.zeros((len(inputs), t_max, inputs[0].shape[1]), np.float32)
    for i, u in enumerate(inputs):
        batch[i, :u.shape[0]] = u
    with jax.default_matmul_precision("highest"):
        preds = jax.jit(lambda u: predict(
            params, run_reservoir(params, u, engine="scan")))(batch)
        preds = np.asarray(preds)
    return [preds[i, :u.shape[0]] for i, u in enumerate(inputs)]


def _describe(engine) -> str:
    program = engine.program
    return program.describe() if program is not None else engine.xla_schedule


def _serve(server, inputs: list) -> list:
    for i, u in enumerate(inputs):
        server.submit(SubmitSpec(u, uid=i))
    results = server.run()
    return [results[i] for i in range(len(inputs))]


def _max_diff(results: list, refs: list) -> float:
    return max(float(np.max(np.abs(np.asarray(r.preds) - ref)))
               for r, ref in zip(results, refs))


def _rms_diff(results: list, refs: list) -> float:
    err = np.concatenate([np.asarray(r.preds) - ref
                          for r, ref in zip(results, refs)])
    return float(np.sqrt(np.mean(err ** 2)))


def serve_phase(name: str, params: ESNParams, inputs: list, refs: list, *,
                backend: str, tol: tuple[float, float],
                expect_backend: str | None = None,
                expect_regime: str | None = None, n_slots: int = N_SLOTS,
                chunk_steps: int = CHUNK_STEPS) -> dict:
    """Serve ``inputs`` through one engine behind the async server and hold
    the answers to ``refs`` within ``tol`` = (max, RMS).  Prints the
    phase's line, then raises :class:`SmokeFailure` if any check fails."""
    cfg = params.config
    max_tol, rms_tol = tol
    t0 = time.perf_counter()
    engine = ReservoirEngine(params, backend=backend)
    # the server warms (compiles) the pool's chunk program at construction
    server = AsyncReservoirServer(engine, n_slots=n_slots,
                                  chunk_steps=chunk_steps)
    compile_s = time.perf_counter() - t0
    warm = sum(engine.trace_counts.values())
    t1 = time.perf_counter()
    results = _serve(server, inputs)
    serve_s = time.perf_counter() - t1
    report = {
        "phase": name, "dim": cfg.reservoir_dim, "mode": cfg.mode,
        "element_sparsity": cfg.element_sparsity,
        "requested_backend": backend, "backend": engine.backend,
        "interpreted": engine.backend == "pallas" and pallas_interpret(),
        "regime": _describe(engine),
        "shiftadd_terms": getattr(engine.program, "n_shiftadd_terms", None),
        "n_slots": n_slots, "chunk_steps": chunk_steps,
        "compile_s": compile_s, "serve_s": serve_s,
        "requests": len(inputs),
        "served": sum(r.status == "ok" for r in results),
        "retraces_after_warmup": sum(engine.trace_counts.values()) - warm,
        "max_abs_diff": _max_diff(results, refs), "tol": max_tol,
        "rms_diff": _rms_diff(results, refs), "rms_tol": rms_tol,
    }
    say(**report)
    failures = []
    if expect_backend is not None and engine.backend != expect_backend:
        failures.append(f"backend {engine.backend!r}, expected "
                        f"{expect_backend!r}")
    if expect_backend == "pallas" and jax.default_backend() == "tpu" \
            and report["interpreted"]:
        failures.append("pallas ran interpreted on the TPU")
    if expect_regime is not None and engine.program.regime != expect_regime:
        failures.append(f"regime {engine.program.regime!r}, expected "
                        f"{expect_regime!r}")
    if report["served"] != len(inputs):
        failures.append(f"served {report['served']} of {len(inputs)}")
    if report["retraces_after_warmup"]:
        failures.append(f"{report['retraces_after_warmup']} retraces after "
                        "warm-up")
    if not report["max_abs_diff"] <= max_tol:      # NaN fails too
        failures.append(f"max |diff| {report['max_abs_diff']} > {max_tol}")
    if not report["rms_diff"] <= rms_tol:
        failures.append(f"RMS diff {report['rms_diff']} > {rms_tol}")
    if failures:
        raise SmokeFailure(f"phase {name}: " + "; ".join(failures))
    return report


def sharded_phase(params: ESNParams, inputs: list, *, n_shards: int,
                  slots_per_shard: int = N_SLOTS // 4,
                  chunk_steps: int = CHUNK_STEPS) -> dict:
    """Serve ``inputs`` from a ``DistributedReservoirServer`` over an
    ``n_shards`` data mesh and from a single-device engine with the same
    pool; rows must match per sequence and outputs must span the mesh."""
    if slots_per_shard < 2:
        raise SmokeFailure("need >= 2 rows per shard for per-sequence "
                           "bit-identity (the one-row gemv caveat)")
    n_slots = n_shards * slots_per_shard
    t0 = time.perf_counter()
    engine = ShardedReservoirEngine(params, mesh=make_data_mesh(n_shards))
    server = DistributedReservoirServer(engine,
                                        slots_per_shard=slots_per_shard,
                                        chunk_steps=chunk_steps)
    compile_s = time.perf_counter() - t0
    warm = sum(engine.trace_counts.values())
    sharded = _serve(server, inputs)
    retraces = sum(engine.trace_counts.values()) - warm

    # the rule picks the same schedule for the same params, so both sides
    # run the same program
    single_engine = ReservoirEngine(params)
    single = _serve(AsyncReservoirServer(single_engine, n_slots=n_slots,
                                         chunk_steps=chunk_steps), inputs)
    mismatched = [i for i, (a, b) in enumerate(zip(sharded, single))
                  if not np.array_equal(np.asarray(a.preds),
                                        np.asarray(b.preds))]

    # where the work ran: one chunk through the sharded engine, plus the
    # pool's carried state and input buffers after serving
    u = jnp.zeros((n_slots, chunk_steps, params.config.input_dim))
    out, final = engine.run_segment(
        u, jnp.zeros((n_slots, params.config.reservoir_dim)))
    placed = {"chunk_output": out, "chunk_final_state": final,
              "pool_state": server.batcher._states}
    if server.batcher.zero_copy:
        placed["pool_inputs"] = server.batcher._u_dev
    device_sets = {k: sorted(d.id for d in v.sharding.device_set)
                   for k, v in placed.items()}
    report = {
        "phase": f"sharded x{n_shards}", "dim": params.config.reservoir_dim,
        "mode": params.config.mode, "backend": engine.backend,
        "interpreted": engine.backend == "pallas" and pallas_interpret(),
        "regime": _describe(engine), "slots_per_shard": slots_per_shard,
        "compile_s": compile_s, "requests": len(inputs),
        "served": sum(r.status == "ok" for r in sharded),
        "retraces_after_warmup": retraces,
        "rows_mismatched_vs_single_device": len(mismatched),
        "max_abs_diff_vs_single_device": _max_diff(
            sharded, [np.asarray(r.preds) for r in single]),
        "device_sets": device_sets,
    }
    say(**report)
    failures = []
    if report["served"] != len(inputs):
        failures.append(f"served {report['served']} of {len(inputs)}")
    if retraces:
        failures.append(f"{retraces} retraces after warm-up")
    if mismatched:
        failures.append(f"{len(mismatched)} sequences differ from the "
                        "single-device engine")
    narrow = [k for k, ids in device_sets.items() if len(ids) != n_shards]
    if narrow:
        failures.append(f"not spread over {n_shards} devices: {narrow}")
    if failures:
        raise SmokeFailure("sharded phase: " + "; ".join(failures))
    return report


def one_chip(seed: int) -> None:
    """Phases (a)-(d) on one device."""
    t0 = time.perf_counter()
    large = build_params(LARGE_1024, seed)
    inputs = make_requests(seed)
    refs = reference(large, inputs)
    say(setup="LARGE_1024 reservoir, readout and reference",
        seconds=time.perf_counter() - t0)
    serve_phase("a: LARGE_1024 auto", large, inputs, refs, backend="auto",
                tol=INT8_TOL, expect_backend="pallas")
    serve_phase("b: LARGE_1024 xla", large, inputs, refs, backend="xla",
                tol=INT8_TOL)

    t0 = time.perf_counter()
    wide = build_params(ESNConfig(reservoir_dim=2048, element_sparsity=0.85),
                        seed)
    refs = reference(wide, inputs)
    say(setup="fp32 dim-2048 reservoir, readout and reference",
        seconds=time.perf_counter() - t0)
    serve_phase("c: fp32 dim 2048 pipelined", wide, inputs, refs,
                backend="pallas", tol=FP32_TOL, expect_regime="pipelined")

    sparse = build_params(ESNConfig(reservoir_dim=512, element_sparsity=0.98,
                                    mode="int8-csd"), seed)
    refs = reference(sparse, inputs)
    report = serve_phase("d: int8 dim 512 shift-add", sparse, inputs, refs,
                         backend="pallas", tol=INT8_TOL)
    if not report["shiftadd_terms"]:
        raise SmokeFailure("phase d: the matrix produced no shift-add terms")


def four_chips(seed: int, n_shards: int) -> None:
    """The sharded phase alone, over ``n_shards`` devices."""
    large = build_params(LARGE_1024, seed)
    sharded_phase(large, make_requests(seed), n_shards=n_shards)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-serving phase on 4 chips")
    args = ap.parse_args(argv)
    say(compile_cache_dir=enable_compile_cache())
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found platform {platform!r}, not a TPU; "
              "this smoke runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    say(platform=platform, device_kind=devices[0].device_kind,
        device_count=len(devices), jax=jax.__version__)
    if args.chips == 4:
        four_chips(args.seed, 4)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
