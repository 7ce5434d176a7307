"""Host time of delivery per chunk, in ms: the program's
``scheduler.deliver`` spans in the window (after the chunk: the deadline
sweep, the counts, packaging each result and the first-output marks),
over the steps that ran a chunk."""

import program_spans


def read(ctx: dict):
    return program_spans.per_step_ms(ctx, "scheduler.deliver")
