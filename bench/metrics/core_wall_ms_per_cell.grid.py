"""Wall milliseconds of the lockstep core per sweep cell: the program's
``flashsim.core`` spans (from the jitted core's call to the return of
its results to the host, so device time and the wait for it) over a
replay of the window's first round of ``simulate_batch`` calls with the
recorder on (``harness.spans``), divided by the cells.  Nothing from a
program without the recorder."""

from harness import spans


def read(ctx):
    r = spans.replay(ctx)
    if r is None or not r.cells or "flashsim.core" not in r.summary:
        return None
    return r.total_ms("flashsim.core") / r.cells
