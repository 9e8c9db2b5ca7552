"""Milliseconds per query of planning on a warm plan cache: the
program's ``joinagg.plan`` spans (plan-cache lookup, path choice,
program binding) in the traced window."""
import program_spans


def read(ctx):
    return program_spans.span_ms(ctx, "joinagg.plan")
