"""Multiply-adds the recurrence issued in the window over those the
matrix needs, as a factor ("x"): the ``recur_ops`` attribute of the
program's ``engine.dispatch`` spans (its count for each launch), summed,
over launches x rows per chip x chunk steps x the quantized matrix's
nonzeros (``work.py``).  A dense product of the whole matrix reads
dim**2 / nnz; a table padded to the largest in-degree, its padding
factor.  A program whose spans carry no such count gives nothing."""

import program_spans


def read(ctx: dict):
    spans = program_spans.window(ctx)
    if spans is None or not ctx["launches"]:
        return None
    counts = [s.attrs["recur_ops"] for s in spans["engine.dispatch"]
              if "recur_ops" in s.attrs]
    if not counts:
        return None
    need = (ctx["launches"] * ctx["rows_per_chip"] * ctx["chunk_steps"]
            * ctx["work"].nnz)
    return sum(counts) / need
