"""Device milliseconds of the lockstep core per ``simulate`` call: the
executions of the jitted core program (``jit_fcfs_core_fwd``, whose whole
body runs under the ``fcfs_core`` named scope) in the profiler trace of
the window's first call.  Nothing when no call is held whole or the core
never ran."""


def read(ctx):
    if ctx.trace is None:
        return None
    cells = sum(len(c.results) for c in ctx.traced_calls if not c.error)
    core = ctx.trace.core_s()
    if not cells or core <= 0.0:
        return None
    return 1e3 * core / cells
