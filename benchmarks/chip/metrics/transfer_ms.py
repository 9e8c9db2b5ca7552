"""Milliseconds per query the host spends issuing uploads and copying
results down: the program's ``joinagg.h2d`` and ``joinagg.d2h`` spans
in the traced window."""
import program_spans


def read(ctx):
    return program_spans.span_ms(ctx, "joinagg.h2d", "joinagg.d2h")
