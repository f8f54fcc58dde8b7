"""Seconds of XLA backend compiles (and loads from the persistent cache)
during set-up, from ``jax.monitoring``."""


def read(ctx):
    return ctx.setup["compile_s"]
