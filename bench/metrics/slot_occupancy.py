"""Share of the pool's slot-steps in the window that carried a request's
input (``ServeStats`` live over total slot-steps), in %."""


def read(ctx: dict):
    if not ctx["total_steps"]:
        return None
    return 100.0 * ctx["live_steps"] / ctx["total_steps"]
