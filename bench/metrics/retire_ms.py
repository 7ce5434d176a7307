"""Host time of retirement per chunk, in ms: the program's
``scheduler.retire`` spans in the window (assembling each finished
request's output, with the device-to-host waits of ``scheduler.sync``),
over the steps that ran a chunk."""

import program_spans


def read(ctx: dict):
    return program_spans.per_step_ms(ctx, "scheduler.retire")
