"""The program's own wall-clock spans in a traced run's window, for the
per-layer readers of ``metrics/``.

``run.py`` installs the program's tracer (``repro.obs``) just before the
window and detaches it just after, so the detached tracer holds the
window's spans and nothing else.  It is taken for this run's only if it
holds one ``scheduler.step`` span for each ``server.step()`` the harness
timed (``ctx["step_s"]``).  A program without those spans, or without
``obs.detached``, gives nothing to read.
"""

import collections


def window(ctx: dict) -> dict | None:
    """The window's spans by name, or None."""
    from repro import obs
    if not ctx["step_s"] or not hasattr(obs, "detached"):
        return None
    state = obs.detached()
    if state is None or state.tracer is None:
        return None
    by_name = collections.defaultdict(list)
    for span in state.tracer.spans():
        by_name[span.name].append(span)
    if len(by_name["scheduler.step"]) != len(ctx["step_s"]):
        return None
    return by_name


def per_step_ms(ctx: dict, name: str) -> float | None:
    """The window's ``name`` spans in all, per step that ran a chunk, in
    ms."""
    spans = window(ctx)
    if spans is None:
        return None
    steps = sum(1 for s in spans["scheduler.step"] if s.attrs.get("chunk"))
    if not steps or not spans[name]:
        return None
    return 1e3 * sum(s.duration_s for s in spans[name]) / steps
