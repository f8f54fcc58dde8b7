"""A stand-in reference for the harness's tests, kept out of
``bench/reference``: it accepts only a drive with garbage collection
(which ``sim`` refuses), declares one optional drive key, and records
every cell it is asked to simulate.  It models nothing: its statistics
are fixed numbers."""

from reference import sim

STAT_FIELDS = ("mean_us", "n_requests", "gc_invocations")
DRIVE_KEYS = frozenset({"ecc_engines_per_channel"})
WORKLOAD_KEYS = frozenset()

#: (scheduler, requests, condition, mechanism, seed, time_dtype) of each
#: simulate call.
CALLS = []

generate_trace = sim.generate_trace


def accepts(drive, workload):
    if not drive["gc"].get("enabled"):
        return "the stub models drives with garbage collection only"
    return None


def simulate(drive, trace, condition, mechanism, seed, time_dtype=float):
    CALLS.append((drive["scheduler"], len(trace[0]), tuple(condition),
                  mechanism, seed, time_dtype))
    return {"mean_us": 1.0, "n_requests": len(trace[0]),
            "gc_invocations": 1}
