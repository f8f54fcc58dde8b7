"""Lockstep-core dispatches per sweep cell over the window: the change
of the program's ``KERNEL_DISPATCHES`` counter divided by the cells
completed."""


def read(ctx):
    cells = sum(len(c.results) for c in ctx.calls if not c.error)
    if not cells:
        return None
    return ctx.counters["kernel_dispatches"] / cells
