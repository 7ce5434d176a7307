"""The whole serving step's share of the chips' peak, in %: the operations
of the reservoir steps that carried a request's input in the window
(``work.py``), over the summed wall time of the window's steps times the
peak of the recurrent operand's type (``peaks.json``) times the chips."""

import work


def read(ctx: dict):
    wall = sum(ctx["step_s"])
    if not wall or not ctx["live_steps"]:
        return None
    ops = ctx["live_steps"] * ctx["work"].ops_per_row_step
    peak = work.compute_peak(ctx["device_kind"], ctx["cfg"])
    return 100.0 * ops / (wall * peak * ctx["chips"])
