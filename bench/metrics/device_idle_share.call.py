"""Percent of the traced window (the window's first ``simulate`` call)
in which no op ran on the device; nothing without a device trace."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.devices or t.window_s <= 0.0:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
