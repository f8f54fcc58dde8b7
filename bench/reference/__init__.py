"""Plain references of the simulated semantics: the benchmark's yardstick
of correctness.

A configuration file names the reference that checks it
(``"reference": "<name>"``, default ``sim``); the reference is the
module ``bench/reference/<name>.py``.  A reference imports nothing of
the program under test, and exposes:

``STAT_FIELDS``
    the statistics it produces, by the names of the program's
    ``SimStats`` fields; each is compared exactly.
``generate_trace(workload, n, seed)``
    the base trace of ``n`` requests (arrivals, read flags, page counts,
    first pages) that the program's generator draws from ``seed`` for
    the configuration's ``workload``.
``simulate(drive, trace, condition, mechanism, seed, time_dtype=float)``
    the statistics of one cell, a dict keyed by ``STAT_FIELDS``, on the
    call's trace that the harness deals from the base trace
    (``harness.check.call_trace``, the same deal for every reference);
    ``time_dtype`` holds simulated time, and a narrower float is the
    benchmark's control.
``DRIVE_KEYS``, ``WORKLOAD_KEYS``
    the optional keys of the drive and of the workload, beyond the core
    every configuration states, that it models.
``accepts(drive, workload)``
    ``None``, or the reason it cannot model that drive and workload.

The harness (``bench/harness/spec.py``) refuses a configuration whose
reference is missing, does not keep to this, or does not accept it.
"""
