"""Shared example bootstrap: put the repo on sys.path and pick devices.

An example runs on the devices JAX finds (the TPU on a machine that has
one), with the persistent compile cache placed by the package's helper.
``EXAMPLES_FORCE_CPU=1`` runs it on a virtual 8-device CPU mesh instead,
so it runs anywhere."""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

if os.environ.get("EXAMPLES_FORCE_CPU") == "1":
    # the CPU-mesh bootstrap lives in ONE place, shared with
    # tests/conftest.py — see _cpu_harness.py
    import _cpu_harness
    _cpu_harness.force_cpu_mesh()
else:
    from distributedarrays_tpu.utils.compile_cache import \
        enable_compile_cache
    enable_compile_cache()
