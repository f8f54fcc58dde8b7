"""Percent of the lockstep core's lane-steps that did a lane's own work:
the program's ``lane_steps_useful`` counter (each lane's own step bound)
over ``lane_steps_run`` (lanes × the dispatch's step count, which every
lane of a fused chunk runs), over a replay of the window's first round
of ``simulate_batch`` calls with the recorder on (``harness.spans``).
Nothing from a program without the recorder."""

from harness import spans


def read(ctx):
    r = spans.replay(ctx)
    if r is None or not r.counters.get("lane_steps_run"):
        return None
    return 100.0 * r.counters["lane_steps_useful"] / \
        r.counters["lane_steps_run"]
