"""Milliseconds per query of host work in the tree walks: the program's
``joinagg.walk`` spans less the uploads, launches, waits and downloads
inside them (CSR views, reorders, gathers and products, padding, the
walk's own bookkeeping), in the traced window."""
import program_spans


def read(ctx):
    return program_spans.self_ms(ctx, "joinagg.walk")
