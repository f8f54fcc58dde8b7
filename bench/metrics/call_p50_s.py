"""Median wall seconds from a run-API call to its statistics, over every
call completed in the window."""

from harness import arith


def read(ctx):
    lat = [c.t1 - c.t0 for c in ctx.calls if not c.error]
    return arith.median(lat) if lat else None
