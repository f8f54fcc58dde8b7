"""Seconds of the characterization prewarm for the cell's conditions and
mechanisms, timed by the harness around
``runtime.prewarm_characterization``."""


def read(ctx):
    return ctx.setup["char_s"]
