"""Seconds the program took to plan the cell's query in set-up: the host
span the harness puts around the server's plan-cache lookup (encode,
sort, CSR build, statistics, plan)."""


def read(ctx):
    return ctx.spans.get("plan")
