"""Host requests simulated per second of wall time: cells completed in
the window times the requests the benchmark generated for each, over the
window (first call issued to last call returned)."""

from harness import arith


def read(ctx):
    done = sum(len(c.results) for c in ctx.calls if not c.error)
    if not done:
        return None
    return arith.rate(done * ctx.cell.n_requests,
                      ctx.window[1] - ctx.window[0])
