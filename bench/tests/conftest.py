"""CPU rehearsal tests of the benchmark harness.

Run from the repository root:  python -m pytest bench/tests
They use JAX's CPU backend and never look for a chip.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


def pytest_configure(config):
    # The reference keeps its characterization tables out of the
    # checkout's own cache while tests run.
    import tempfile

    os.environ.setdefault("BENCH_REFERENCE_CACHE_DIR",
                          tempfile.mkdtemp(prefix="bench-ref-tables-"))
