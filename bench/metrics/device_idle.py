"""Share of the window in which no operation ran on the device, averaged
over the chips, from the profiler trace, in %."""


def read(ctx: dict):
    trace = ctx["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
