#!/usr/bin/env python3
"""The readings the check's limits are set from, on the chip.

    python bench/control.py --workload esn1024.batch --seconds 3 \
        --seeds 101,102,103,104,105,106,107,108,109,110,111,112

One process.  For each seed, one run of the cell (its set-up, warm-up and
a short window at the cell's own load) whose seeded sample is compared
with the reference, as every run compares it: the program's readings.
The same sample is then computed by each control of ``model.CONTROLS``
(the reference one precision step below the configuration's) and read in
the program's place.  One JSON line per seed, then the summary: for each
compared number the largest program reading (the lower reading) and the
smallest control reading (the upper one).  Exits 3 without a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys

import model
import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    spec = run.load_cell(args.workload)
    run.enable_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < spec["chips"]:
        print("control: needs the cell's chips on a TPU", file=sys.stderr)
        return 3
    keys = ("max_abs_err", "rms_err")
    lower = dict.fromkeys(keys, 0.0)
    upper = dict.fromkeys(keys, float("inf"))
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(spec, seed, args.seconds, False,
                           devices[:spec["chips"]], controls=model.CONTROLS)
        line = {"seed": seed, "correct": out["correct"],
                "attempted": out["attempted"], "failed": out["failed"],
                "program": {k: out["checks"][k]["value"] for k in keys},
                "controls": out["controls"]}
        print(json.dumps(line), flush=True)
        for k in keys:
            lower[k] = max(lower[k], line["program"][k])
            upper[k] = min([upper[k]] + [c[k] for c in
                                         out["controls"].values()])
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
