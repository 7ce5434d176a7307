"""Mean wall time of one ``server.step()`` in the window, in ms: admission,
the chunk's launch and retirement with its device-to-host syncs, as the
harness's clock sees them around the call."""


def read(ctx: dict):
    steps = ctx["step_s"]
    if not steps:
        return None
    return 1e3 * sum(steps) / len(steps)
