#!/usr/bin/env python3
"""Where a traced run's host time and the device's idle time go, by phase
of the program's serving step.

    python bench/phases.py --workload esn1024.batch --seed 7 --seconds 51

One traced run of the cell, through ``run.py`` (its lines and its result
line as ``--trace 1`` prints them), then one more line, ``phases``:

* ``ms_per_step`` -- the program's wall-clock spans in the window, in all
  per step that ran a chunk: ``scheduler.admit``, ``scheduler.gather``,
  ``engine.dispatch`` (``engine.rollout`` where the pool syncs each
  chunk), ``scheduler.retire`` (its ``scheduler.sync`` waits also apart),
  ``scheduler.deliver``, ``scheduler.step`` and the step's self time,
  which is what its direct children leave of it;
* ``attrs`` -- the sums of the phases' counts (requests admitted, bytes
  copied each way, live slots, requests retired, entries walked);
* ``dropped`` -- spans the program's tracer let fall (0, or the sums are
  short);
* ``slow_steps`` -- each step over ``run.SLOW_STEP_S``: its start from the
  window's first step, its wall time, and its longest direct child with
  that child's time;
* ``idle`` -- the device's idle time, split by the innermost host
  annotation covering it: the harness's ``bench.*`` and the program's
  ``scheduler.*`` and ``engine.*`` (:func:`reduce`).

Run from the root of a checkout; like ``run.py`` it runs only on the chip.
"""

from __future__ import annotations

import bisect
import collections
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import run  # noqa: E402
import trace_reduce  # noqa: E402

PROGRAM = ("scheduler.", "engine.")      # the program's annotations
PHASES = ("scheduler.admit", "scheduler.gather", "engine.dispatch",
          "engine.rollout", "scheduler.retire", "scheduler.sync",
          "scheduler.deliver", "scheduler.step")


def _label(name: str) -> bool:
    return name in trace_reduce.LABELS or name.startswith(PROGRAM)


def reduce(profile) -> dict | None:
    """``trace_reduce.reduce`` with the program's annotations among the
    labels of idle time: each instant of a gap goes to the innermost
    annotation covering it (the latest started; of two started together,
    the shorter), so busy and idle still add up to the window.  On a
    trace with no program annotation the result is ``trace_reduce``'s."""
    if not hasattr(profile, "planes"):
        profile = trace_reduce.read(profile)
    devices: dict = {}
    modules: dict = {}
    host: list = []
    launches: list = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name in trace_reduce.OP_LINES:
                    ops += [(ev.name.split(" = ")[0], ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in line.events]
                elif line.name == trace_reduce.MODULE_LINE:
                    modules[plane.name] = sorted(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         ev.name.split("(")[0]) for ev in line.events)
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW or _label(ev.name):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
                    elif ev.name == trace_reduce.EXECUTE:
                        launches.append(ev.start_ns)
    launches.sort()
    for name, ops in devices.items():
        off = trace_reduce._offset([m[0] for m in modules.get(name, [])],
                                   launches)
        devices[name] = [(n, s + off, e + off) for n, s, e in ops]
        modules[name] = [(n, s + off, e + off)
                         for s, e, n in modules.get(name, [])]
    spans = [(s, e) for name, s, e in host if name == trace_reduce.WINDOW]
    if not spans or not devices:
        return None
    lo, hi = spans[0]
    # by start, and of two started together the longer first, so the
    # last annotation covering an instant is the innermost
    annots = sorted(((s, e, name) for name, s, e in host if _label(name)),
                    key=lambda a: (a[0], -a[1]))
    starts = [a[0] for a in annots]
    longest = max((e - s for s, e, _ in annots), default=0.0)

    busy = 0.0
    op_ns: collections.Counter = collections.Counter()
    prog_ns: collections.Counter = collections.Counter()
    idle: collections.Counter = collections.Counter()
    for name, s, e in (m for ms in modules.values() for m in ms):
        if e > lo and s < hi:
            prog_ns[name] += min(e, hi) - max(s, lo)
    for ops in devices.values():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        for name, s, e in inside:
            op_ns[name] += e - s
        merged = trace_reduce._union([(s, e) for _, s, e in inside])
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            cover = []
            j = bisect.bisect_left(starts, g0 - longest)
            while j < len(annots) and annots[j][0] < g1:
                if annots[j][1] > g0:
                    cover.append(annots[j])
                j += 1
            cuts = sorted({x for s, e, _ in cover for x in (s, e)
                           if g0 < x < g1} | {g0, g1})
            covered = 0.0
            for a, b in zip(cuts, cuts[1:]):
                inner = None
                for s, e, name in cover:
                    if s <= a and e >= b:
                        inner = name
                if inner is not None:
                    idle[inner] += b - a
                    covered += b - a
            if g1 - g0 > covered:
                idle["other"] += g1 - g0 - covered
    n = len(devices)
    return {
        "devices": n,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * 1e-9 / n,
        "ops": {k: v * 1e-9 / n for k, v in op_ns.most_common()},
        "programs": {k: v * 1e-9 / n for k, v in prog_ns.most_common()},
        "idle": {k: v * 1e-9 / n for k, v in idle.most_common()},
    }


def summarize(spans: list, dropped: int) -> dict:
    """The ``phases`` line's span numbers (all but ``idle``) from the
    window's spans."""
    by_name = collections.defaultdict(list)
    for s in spans:
        by_name[s.name].append(s)
    steps = sorted(by_name["scheduler.step"], key=lambda s: s.start)
    chunks = sum(1 for s in steps if s.attrs.get("chunk"))
    if not chunks:
        return {"chunks": 0, "dropped": dropped}
    per_step = {name: 1e3 * sum(s.duration_s for s in by_name[name]) / chunks
                for name in PHASES}
    starts = [s.start for s in steps]
    children = collections.defaultdict(list)        # step index -> spans
    for s in spans:
        if s.parent == "scheduler.step":
            children[bisect.bisect_right(starts, s.start) - 1].append(s)
    self_s = sum(st.duration_s for st in steps) - sum(
        c.duration_s for cs in children.values() for c in cs)
    per_step["self"] = 1e3 * self_s / chunks
    attrs: collections.Counter = collections.Counter()
    for name in PHASES:
        for s in by_name[name] if name.startswith("scheduler.") else ():
            for k, v in s.attrs.items():
                if not isinstance(v, bool):
                    attrs[f"{name}.{k}"] += v
    attrs["scheduler.sync.count"] = len(by_name["scheduler.sync"])
    slow = []
    for i, st in enumerate(steps):
        if st.duration_s > run.SLOW_STEP_S:
            top = max(children[i], key=lambda c: c.duration_s, default=None)
            slow.append([round(st.start - starts[0], 6),
                         round(st.duration_s, 6),
                         None if top is None else top.name,
                         None if top is None else round(top.duration_s, 6)])
    return {"chunks": chunks, "steps": len(steps), "dropped": dropped,
            "ms_per_step": per_step, "attrs": dict(attrs),
            "slow_steps": slow}


def main(argv=None) -> int:
    found: dict = {}

    def reduce_both(trace_dir: str):
        paths = list(Path(trace_dir).rglob("*.xplane.pb"))
        if not paths:
            return None
        profile = trace_reduce.read(paths[0])
        found["reduced"] = reduce(profile)
        return trace_reduce.reduce(profile)

    run.reduce_trace = reduce_both
    argv = list(sys.argv[1:] if argv is None else argv)
    rc = run.main(argv + ["--trace", "1"])
    if rc != 0:
        return rc
    from repro import obs
    state = obs.detached()
    line = summarize(state.tracer.spans(), state.tracer.dropped)
    reduced = found.get("reduced")
    line["idle"] = None if reduced is None else [
        [k, v] for k, v in reduced["idle"].items()]
    print(json.dumps({"phases": line}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
