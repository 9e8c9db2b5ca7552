"""Milliseconds a query waits between its submission and a server worker
starting it (the fusion batcher's window and the pool's queue): the
program's ``serve.queue_wait_s`` counter over ``serve.queries``."""
import program_spans


def read(ctx):
    s = program_spans.per_query("serve.queue_wait_s")
    return None if s is None else 1e3 * s
