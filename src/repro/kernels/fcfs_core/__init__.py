"""Batched open-loop fast path: the lockstep shard core (one XLA program).

The constant-duration FCFS channel collapse (``busy = max(busy, t) +
tDMA``) is a sequential max-plus recurrence over event-ordered channel
touches; this package executes the whole per-channel shard loop — the
recurrence plus the die-grant bookkeeping that feeds it — as one
lockstep-vectorized XLA program advancing every channel's next event
per step, over int64 ticks of simulated time.  ``ops.fcfs_core`` is the
dispatch entry, ``ref.fcfs_core_ref`` the plain-Python reference used
for bitwise parity tests.
"""

from repro.kernels.fcfs_core.ops import fcfs_core  # noqa: F401
from repro.kernels.fcfs_core.ref import fcfs_core_ref  # noqa: F401
