"""Mean host time to enqueue one chunk launch, in us: the program's own
``engine.dispatch`` spans inside the window (its tracer is on in the
traced run only)."""


def read(ctx: dict):
    spans = ctx["dispatch_s"]
    if not spans:
        return None
    return 1e6 * sum(spans) / len(spans)
