"""Host milliseconds per ``simulate`` call: the program's
``flashsim.call`` spans less its ``flashsim.core`` spans, over a replay
of the window's first round of calls with the recorder on
(``harness.spans``), divided by the cells.  Nothing from a program
without the recorder."""

from harness import spans


def read(ctx):
    return spans.host_ms_per_cell(ctx)
