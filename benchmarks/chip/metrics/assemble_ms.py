"""Milliseconds per query of result assembly: the program's
``joinagg.assemble`` spans (dense channel arrays to sparse groups, then
decoded columns) in the traced window."""
import program_spans


def read(ctx):
    return program_spans.span_ms(ctx, "joinagg.assemble")
