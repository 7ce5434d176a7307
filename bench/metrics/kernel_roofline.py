"""The chunk rollout program's share of its roofline, in %.

The bound of one launch is the larger of its operations over peak compute
and its minimum bytes over peak bandwidth (``work.py``, ``peaks.json``),
for the rows each chip was handed and the chunk's steps.  It is divided by
the device time of the chunk rollout program in the trace (its ``XLA
Modules`` events, named by the table ``kernel_names.json``), averaged over
the chips.  Without a matching program it reads nothing.
"""

import json
import re
from pathlib import Path

import work

NAMES = Path(__file__).resolve().parents[1] / "kernel_names.json"


def read(ctx: dict):
    trace = ctx["trace"]
    if trace is None or not ctx["launches"]:
        return None
    pattern = re.compile("|".join(json.loads(NAMES.read_text())["rollout"]))
    kernel_s = sum(s for name, s in trace["programs"].items()
                   if pattern.search(name))
    if not kernel_s:
        return None
    bound = ctx["launches"] * work.bound_s(
        ctx["work"], ctx["rows_per_chip"], ctx["chunk_steps"],
        ctx["device_kind"], ctx["cfg"])
    return 100.0 * bound / kernel_s
