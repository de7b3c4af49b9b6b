"""Seconds JAX spent compiling, or loading compiled programs from the
persistent cache, during set-up (``jax.monitoring``)."""


def read(ctx):
    return ctx.compile_s
