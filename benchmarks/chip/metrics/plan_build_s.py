"""Seconds the program spent building plans on plan-cache misses
(encode, sort, CSR build, statistics, plan), summed over the run: its
``plan.build_s`` counter.  The program's own side of ``plan_s``."""
import program_spans


def read(ctx):
    return program_spans.counter("plan.build_s")
