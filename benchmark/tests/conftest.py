"""The benchmark's own tests run on the CPU, on four virtual devices:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

They are not part of the repository's tier-1 suite (``tests/``)."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
for p in (str(BENCH.parent), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402  (imports no JAX)

harness.use_virtual_cpu(4)
