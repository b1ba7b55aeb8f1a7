"""CPU tests of the benchmark: ``python -m pytest bench/tests``.

They put ``bench/`` and ``src/`` on the path, as ``bench/run.py`` does, and
keep JAX on the CPU, where Pallas kernels run in interpret mode."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
