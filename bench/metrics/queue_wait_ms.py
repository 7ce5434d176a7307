"""Wall-clock queue wait, 95th percentile (nearest rank), in ms: the
program's ``request.wait`` spans in the window, each from a request's
submit to its seating in a slot."""

import math

import program_spans


def read(ctx: dict):
    spans = program_spans.window(ctx)
    if spans is None or not spans["request.wait"]:
        return None
    waits = sorted(s.duration_s for s in spans["request.wait"])
    return 1e3 * waits[max(0, math.ceil(0.95 * len(waits)) - 1)]
