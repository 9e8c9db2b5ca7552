"""Megabytes uploaded to the chip per query: the program's
``transfer.h2d_bytes`` counter over ``serve.queries``."""
import program_spans


def read(ctx):
    b = program_spans.per_query("transfer.h2d_bytes")
    return None if b is None else b / 1e6
