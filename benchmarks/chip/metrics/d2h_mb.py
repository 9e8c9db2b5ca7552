"""Megabytes downloaded from the chip per query: the program's
``transfer.d2h_bytes`` counter over ``serve.queries``."""
import program_spans


def read(ctx):
    b = program_spans.per_query("transfer.d2h_bytes")
    return None if b is None else b / 1e6
