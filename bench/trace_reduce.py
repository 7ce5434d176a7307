"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

* ``busy_s`` -- the union of the intervals in which an operation ran on a
  device, inside the window, averaged over the devices;
* ``window_s`` -- the length of the window: the benchmark's own
  ``bench.window`` host annotation;
* ``ops`` -- device seconds per operation name, averaged over the devices;
* ``programs`` -- device seconds per compiled program (the ``XLA Modules``
  line, named up to the fingerprint in brackets), averaged likewise;
* ``idle`` -- the window's idle device time, split by what the host was
  doing: each part of a gap between device operations goes to the
  benchmark's host annotation (``bench.submit``, ``bench.step``,
  ``bench.wait``) that covers it, or to ``other``.

Device operations are the events of each ``/device:`` plane's ``XLA Ops``
line, named by their HLO instruction (the text before `` = ``).  Host
annotations are read from every ``/host:`` plane.

The profiler's device clock can sit a fixed offset from its host clock (a
TPU v5e trace put each program about 1 ms before the host call that
launched it).  Each device's programs (its ``XLA Modules`` line) are
paired in order with the host's launches (``PJRT_LoadedExecutable_Execute``),
at the shift of up to two launches that pairs them most evenly, and the
device's events are moved so that the earliest program starts with its
launch.
"""

from __future__ import annotations

import bisect
import collections

WINDOW = "bench.window"
LABELS = ("bench.submit", "bench.step", "bench.wait")
OP_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"
EXECUTE = "PJRT_LoadedExecutable_Execute"


def _offset(modules: list, launches: list) -> float:
    """How far the device clock runs behind the host's (ns; add it to
    device times), from program starts and host launch starts."""
    best = None
    for j in range(-2, 3):
        pairs = list(zip(modules[max(0, j):], launches[max(0, -j):]))
        if len(pairs) < 2:
            continue
        d = sorted(e - m for m, e in pairs)
        mid = d[len(d) // 2]
        spread = sorted(abs(x - mid) for x in d)[len(d) // 2]
        if best is None or spread < best[0]:
            best = (spread, d[-1])
    return 0.0 if best is None else best[1]


def _union(intervals: list) -> list:
    """Merge (start, end) intervals; returns them sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def read(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def reduce(profile) -> dict | None:
    """The device numbers of ``profile`` (a path or a ``ProfileData``), or
    ``None`` when it holds no window annotation or no device operation."""
    if not hasattr(profile, "planes"):
        profile = read(profile)
    devices: dict = {}
    modules: dict = {}
    host: list = []
    launches: list = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            ops = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops += [(ev.name.split(" = ")[0], ev.start_ns,
                             ev.start_ns + ev.duration_ns)
                            for ev in line.events]
                elif line.name == MODULE_LINE:
                    modules[plane.name] = sorted(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         ev.name.split("(")[0]) for ev in line.events)
            if ops:
                devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW or ev.name in LABELS:
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
                    elif ev.name == EXECUTE:
                        launches.append(ev.start_ns)
    launches.sort()
    for name, ops in devices.items():
        off = _offset([m[0] for m in modules.get(name, [])], launches)
        devices[name] = [(n, s + off, e + off) for n, s, e in ops]
        modules[name] = [(n, s + off, e + off)
                         for s, e, n in modules.get(name, [])]
    spans = [(s, e) for name, s, e in host if name == WINDOW]
    if not spans or not devices:
        return None
    lo, hi = spans[0]
    annots = sorted((s, e, name) for name, s, e in host if name in LABELS)
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
        merged = _union([(s, e) for _, s, e in inside])
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            # annotations that can overlap [g0, g1): started before g1 and
            # not more than the longest annotation before g0
            covered = 0.0
            j = bisect.bisect_left(starts, g0 - longest)
            while j < len(annots) and annots[j][0] < g1:
                s, e, name = annots[j]
                ov = min(e, g1) - max(s, g0)
                if ov > 0:
                    idle[name] += ov
                    covered += ov
                j += 1
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
