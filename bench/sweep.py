#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate the server
sustains without a growing backlog.  Run once, on the chip, when the cell
is defined; the cell's traffic file then holds a fixed rate below it.

    python bench/sweep.py --workload esn1024.stream --seed 5 --seconds 4 \
        --rates 500,1000,2000,4000

One process; for each rate in turn a fresh server of the cell, and the
cell's traffic (its lengths, pool and warm-up, the rate replaced) for
``--seconds``.  Each rate prints one line: offered and delivered
requests per second, reservoir steps per second, latency p50/p95 of the
requests due in the window (from when each was due), the backlog (requests
sent and not answered) at a quarter and at the end of the window, and how
late the generator ran.  Exits 3 without a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def measure(spec: dict, weights, rate: float, seed: int,
            seconds: float) -> dict:
    mix_spec = dict(spec["traffic"], rate_per_s=rate)
    _engine, server = run.build_server(spec["cfg"], weights, mix_spec["pool"],
                                       spec["chips"])
    mix = run.traffic_mod.Traffic(mix_spec, seed, spec["cfg"]["input_dim"])
    sess = run.Session(server, mix, time.perf_counter(),
                       spec["cfg"]["output_dim"])
    t0 = sess.origin + mix_spec["warmup_s"]
    run.open_loop(sess, t0)
    first = sess.next
    run.open_loop(sess, t0 + seconds / 4)
    backlog_q = len(sess.inflight)
    late = run.open_loop(sess, t0 + seconds)
    t1 = time.perf_counter()
    backlog_end = len(sess.inflight)
    due = [k for k in range(first, sess.next) if t0 <= sess.due(k) < t1]
    done = [k for k in due if k in sess.done]
    lat = [sess.done[k][2] - sess.done[k][0] for k in done]
    delivered = [k for k, d in sess.done.items() if t0 <= d[2] <= t1]
    return {"rate_per_s": rate, "offered_per_s": len(due) / (t1 - t0),
            "delivered_per_s": len(delivered) / (t1 - t0),
            "steps_per_s": sum(mix.length(k) for k in delivered) / (t1 - t0),
            "latency_p50_ms": 1e3 * run.percentile(lat, 0.5) if lat else None,
            "latency_p95_ms": 1e3 * run.percentile(lat, 0.95) if lat else None,
            "backlog_quarter": backlog_q, "backlog_end": backlog_end,
            "generator_late_p95_ms":
                1e3 * run.percentile(late, 0.95) if late else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, requests/s")
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    if spec["traffic"]["loop"] != "open":
        raise SystemExit(f"{args.workload} is not an open-loop cell")
    run.enable_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU; the sweep runs only on the chip",
              file=sys.stderr)
        return 3
    sys.path.insert(0, str(run.ROOT / "src"))
    weights = run.model.make_weights(spec["cfg"])
    for rate in (float(r) for r in args.rates.split(",")):
        print(json.dumps(measure(spec, weights, rate, args.seed,
                                 args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
