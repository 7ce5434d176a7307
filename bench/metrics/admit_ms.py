"""Host time of admission per chunk, in ms: the program's
``scheduler.admit`` spans in the window (seating each arrived request: its
input lanes' write, its state row and their host-to-device copies), over
the steps that ran a chunk."""

import program_spans


def read(ctx: dict):
    return program_spans.per_step_ms(ctx, "scheduler.admit")
