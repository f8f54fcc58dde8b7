"""Set-up seconds: from process start to the first timed call (imports,
JAX and device start, characterization, compiles, warm-up calls)."""


def read(ctx):
    return ctx.setup["setup_s"]
