"""Milliseconds per query the host is blocked on the chip: the
program's ``joinagg.wait`` spans (``block_until_ready`` on each kernel's
result) in the traced window."""
import program_spans


def read(ctx):
    return program_spans.span_ms(ctx, "joinagg.wait")
