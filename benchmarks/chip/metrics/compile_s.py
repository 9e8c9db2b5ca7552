"""Seconds of backend compilation in set-up, summed from JAX's
``/jax/core/compile/backend_compile_duration`` events.  A hit in the
persistent compilation cache is not a backend compile."""


def read(ctx):
    return ctx.compile_s
